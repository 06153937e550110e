"""Seconds to build the ``ServeLoop`` and run its warm-up request, which
compiles (or loads from the cache) the chunk and decode steps the window
runs.  Host clock.  Moves ``setup_s``."""


def read(ctx):
    return ctx["warmup_s"]
