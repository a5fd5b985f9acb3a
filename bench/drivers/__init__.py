"""One driver per kind of cell: serve, train."""
