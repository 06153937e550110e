"""Device time of one call of the jitted decode step, mean over the
traced window, in the offline-batch cells.  Moves ``sim_tokens_per_s``."""
from bench.readers import decode_step_ms


def read(ctx):
    return decode_step_ms(ctx)
