"""Requests to the compile cache (hits + misses) between the window's
first step and its barrier: ``runtime.compile_cache_stats()``."""


def read(ctx):
    return ctx["counters"].get("compile_requests")
