"""Traffic generators: each reads a mix's parameters and the seed."""
