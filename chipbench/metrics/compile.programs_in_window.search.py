"""Programs that were not in memory when the window needed them:
persistent-cache hits plus misses inside the window, from JAX's
monitoring events."""


def read(run):
    if not run.compile:
        return None
    return float(run.compile["cache_hits"] + run.compile["cache_misses"])
