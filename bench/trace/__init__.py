"""Reduction of a profiler trace to the benchmark's device metrics."""
