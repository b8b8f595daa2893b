"""Shared pieces of the benchmark: harness, trace reduction, counts, traffic."""
