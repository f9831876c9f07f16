"""Traffic generators owned by the benchmark: the program receives only
the arrays these build."""
