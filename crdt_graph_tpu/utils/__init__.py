"""Profiling, tracing, and table-introspection utilities."""
from .profiling import table_stats, trace

__all__ = ["table_stats", "trace"]
