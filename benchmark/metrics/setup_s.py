"""``setup_s``: from the start of ``run.py`` to the start of the window:
imports, JAX start-up, generation, ingest and warm-up (compiles, or
loads from the persistent compilation cache)."""


def read(run):
    return run.setup_s
