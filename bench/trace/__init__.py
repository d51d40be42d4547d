"""Reduction of a JAX profiler trace (`.xplane.pb`) to device metrics."""
