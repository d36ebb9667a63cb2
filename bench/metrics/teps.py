"""Graph500 TEPS over the window: traversed edges of every call, summed,
over the window's wall time (host clock, each call ended in
``block_until_ready``).  Equals the specification's harmonic mean of the
per-root rates when every root lies in one component."""


def read(run):
    edges = run.work.get("edges")
    return None if edges is None else edges / run.window_s
