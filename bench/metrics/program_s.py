"""Seconds ``program_params`` took to program the crossbar state, by the
host clock around the call and its ``block_until_ready``.  Moves
``setup_s``."""


def read(ctx):
    return ctx["program_s"]
