"""The benchmark: harness, yardstick and data files (see README.md)."""
