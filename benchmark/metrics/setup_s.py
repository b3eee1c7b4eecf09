"""Seconds from the start of the run's work to the window: JAX start-up,
the caches, making the histories, and one check of each (which compiles
on a checkout's first run and loads from the cache after)."""


def read(run):
    return run.setup_s
