"""Share of the HBM roofline reached by the PageRank pull iterations.

The bytes are the algorithm's, whatever implements it: for every directed
edge its source index and the gathered contribution (4 B each); for every
vertex its row offset, rank, out-degree and new rank (4 B each).  Those
bytes times the window's iterations, at the device's peak bandwidth, over
the device busy time of the traced window.
"""


def pagerank_pull_bytes(vertices: int, edges: int) -> int:
    """Bytes one pull iteration has to move at the least."""
    return 8 * edges + 16 * vertices


def read(run):
    iterations = run.work.get("iterations")
    if run.trace is None or not iterations or run.trace.busy_s <= 0:
        return None
    least_s = pagerank_pull_bytes(run.vertices, run.edges) * iterations \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace.busy_s
