"""Seconds in ``graph.from_edge_list`` (host CSR build and placement)."""


def read(run):
    return run.spans.total("ingest")
