"""Input generators: Graph500 Kronecker graphs, from the seed alone."""
