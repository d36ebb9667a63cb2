"""Programs the inspector compiled or loaded from the persistent cache, as
the program counts them itself: its ``programs.inspect*`` counters
(``repro.core.telemetry``), which count JAX's backend-compile event under
the innermost open program span.  The inspector runs in set-up only, so
the process's count is set-up's.  Nothing without those counters."""


def read(run):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    return sum(n for name, n in telemetry.counters().items()
               if name == "programs.inspect"
               or name.startswith("programs.inspect."))
