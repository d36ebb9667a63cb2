"""Seconds from the start of the process to the first timed call: imports,
graph generation, the plan build, loading or compiling programs and the
warm-up call (host clock)."""


def read(run):
    return run.setup_s
