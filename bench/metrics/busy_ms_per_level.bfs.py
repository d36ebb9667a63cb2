"""Device busy milliseconds per BFS level in the traced window: the union
of device operation intervals, over the levels the window's calls ran (a
root's levels are its largest depth plus one, the last finding nothing)."""


def read(run):
    levels = run.work.get("levels")
    if run.trace is None or not levels:
        return None
    return 1e3 * run.trace.busy_s / levels
