"""Harness library: cell loading, device checks, statistics, trace
reduction, operation counts."""
