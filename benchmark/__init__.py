"""The benchmark of cells: see README.md."""
