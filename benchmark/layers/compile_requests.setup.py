"""Backend compile requests before the window opened (jax.monitoring):
how many programs set-up builds, from the cache or not."""


def read(bench):
    return bench.compile_log.setup["requests"]
