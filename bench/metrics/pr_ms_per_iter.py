"""Milliseconds per PageRank iteration: the window's wall time over every
iteration its calls ran (host clock, each call ended in
``block_until_ready``)."""


def read(run):
    iterations = run.work.get("iterations")
    return None if not iterations else 1e3 * run.window_s / iterations
