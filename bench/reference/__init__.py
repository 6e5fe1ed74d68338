"""Plain references of the model families the benchmark serves."""
