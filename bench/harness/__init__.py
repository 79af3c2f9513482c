"""Shared yardstick of the benchmark: spans and traces, peaks, counts,
references, traffic and the comparisons that decide correct."""
