"""Mean host milliseconds inside the BFS driver per call of the window: the
program's ``bfs`` span (``repro.core.telemetry``) on the host clock, from
the driver's entry to the return of the jitted loop's dispatch, over the
window's calls (the last spans of the run).  Nothing without that span."""


def read(run):
    calls = run.work.get("calls")
    if run.kind != "bfs" or not calls:
        return None
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    spans = telemetry.recent_spans("bfs")[-calls:]
    if len(spans) < calls:
        return None
    return 1e3 * sum(end - start for start, end in spans) / calls
