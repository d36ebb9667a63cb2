"""Programs compiled or loaded from the persistent cache inside the window
of a pagerank cell (JAX's backend-compile event, which fires for either);
should be 0."""


def read(run):
    return run.compiles_in_window if run.kind == "pagerank" else None
