"""Data generators of the benchmark, drawn from ``--seed``."""
