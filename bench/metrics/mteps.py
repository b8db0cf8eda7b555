"""Graph500 TEPS over the window, in millions of edges per second.

The input edge tuples of each completed root's component (half the
directed edges out of the vertices its answer reached), summed over every
root of the window, over the time to the end of the last of them.
"""


def read(run):
    if run.driver != "analytics":
        return None
    runs = run.records["runs"]
    tuples = sum(r["reached_edges"] for r in runs) / 2
    return tuples / run.records["elapsed_s"] / 1e6
