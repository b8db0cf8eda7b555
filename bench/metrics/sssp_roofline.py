"""SSSP's share of the HBM roofline, in %.

The least bytes an exact SSSP moves from a root: every directed edge out
of a reached vertex has to be read once (4 B index, 4 B weight), and every
vertex's distance once (8 B with its offset), counted from the benchmark's
graph and the answer.  Their time at the chip's peak bandwidth, summed over
the window's roots, over the device's busy time in the traced window.
"""
BYTES_PER_EDGE = 8
BYTES_PER_VERTEX = 8


def read(run):
    if (run.driver != "analytics" or run.records["program"] != "sssp"
            or run.trace is None or run.trace["busy_s"] <= 0):
        return None
    V = run.records["num_vertices"]
    least = sum(r["reached_edges"] * BYTES_PER_EDGE + V * BYTES_PER_VERTEX
                for r in run.records["runs"])
    return least / run.peaks["hbm_bytes_per_s"] / run.trace["busy_s"] * 100
