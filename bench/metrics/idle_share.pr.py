"""Share of the traced window in which no operation ran on the device, in
a pagerank cell: 100 * (1 - busy / window), from the profiler trace."""


def read(run):
    if run.trace is None or run.kind != "pagerank":
        return None
    return 100.0 * run.trace.idle_share
