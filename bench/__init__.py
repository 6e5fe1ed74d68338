"""The benchmark: harness, traffic, references and metric readers."""
