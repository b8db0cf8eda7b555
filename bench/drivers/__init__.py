"""Window drivers: one module per shape of measured window.

A driver module defines ``make(cell) -> driver``.  The driver has
``setup()`` (everything before the window), ``window(seconds)`` (the
measured work), ``release()`` (drops the program's state), ``check()``
(the comparison with the references, returning ``{name: value}``) and
``records`` (what the metric readers read), plus ``attempted`` and
``failed`` counts once checked.
"""


def load_graph(cell):
    """Generate the configuration's graph on the device from the seed, then
    ingest it into the program (``graph.from_edge_list``, already sorted).
    Returns the benchmark's own edge list and the program's graph."""
    from repro.core import graph as G

    from bench.gen import kronecker

    with cell.spans("generate"):
        edges = kronecker.from_config(cell.config, cell.seed)
    with cell.spans("ingest"):
        g = G.from_edge_list(edges.src, edges.dst,
                             num_vertices=edges.num_vertices,
                             weights=edges.weights, sort=False)
        g.edges_dst.block_until_ready()
    return edges, g
