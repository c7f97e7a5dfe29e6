"""Launch layer: the command-line drivers and the compile cache."""
