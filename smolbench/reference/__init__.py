"""Plain references the benchmark holds the served answers to."""
