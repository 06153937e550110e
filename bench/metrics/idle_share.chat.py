"""Share of the traced window in which no operation ran on the chip, in
the chat cell.  Moves ``tpot_p90_ms``."""
from bench.readers import idle_share


def read(ctx):
    return idle_share(ctx)
