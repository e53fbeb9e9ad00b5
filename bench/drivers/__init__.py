"""One module per kind of cell; a workload's ``driver`` key names it."""
