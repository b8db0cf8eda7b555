"""Edges the run loop traversed (``run_stats['edges_traversed']``) over
the directed edges out of the vertices each answer reached, summed over
the window's roots.  1.0 is one look at every edge of the component."""


def read(run):
    if run.driver != "analytics":
        return None
    runs = run.records["runs"]
    need = sum(r["reached_edges"] for r in runs)
    if not need:
        return None
    return sum(r["edges_traversed"] for r in runs) / need
