"""Mean share of the decode lanes that were live per decode step
(``ServeReport.occupancy``, counted by the scheduler).  Moves
``sim_tokens_per_s``."""


def read(ctx):
    return 100.0 * ctx["report"].occupancy
