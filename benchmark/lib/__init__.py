"""Shared code of the benchmark: manifest, timing, trace reduction, peaks, work counts."""
