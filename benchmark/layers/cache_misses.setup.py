"""Persistent-cache misses before the window opened (jax.monitoring):
programs set-up had to compile anew. 0 in every run after a cell's first."""


def read(bench):
    return bench.compile_log.setup["misses"]
