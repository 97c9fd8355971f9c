"""The benchmark: cells, metrics and their readers; see ``harness``."""
