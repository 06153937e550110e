"""Device time of one call of the jitted decode step, mean over the
traced window, in the chat cell.  Moves ``tpot_p90_ms``."""
from bench.readers import decode_step_ms


def read(ctx):
    return decode_step_ms(ctx)
