"""Programs XLA compiled, or loaded from the persistent cache, while the
measured window ran (``ServeReport.window_compiles``, counted by a
compile listener the serve loop holds for its run): 0 when the warm-up
compiled every program the window runs.  Nothing where the program keeps
no such count.  Moves ``setup_s``."""


def read(ctx):
    return getattr(ctx["report"], "window_compiles", None)
