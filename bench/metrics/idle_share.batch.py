"""Share of the traced window in which no operation ran on the chip, in
the offline-batch cells.  Moves ``sim_tokens_per_s``."""
from bench.readers import idle_share


def read(ctx):
    return idle_share(ctx)
