"""Share of the window in which the host held a drained device: the host
time from each device sync's return to the next jitted call, outside the
waits for an arrival (``ServeReport.host_gap_s``, the serve loop's
spans), over the window, in the chat cell.  Nothing where the program
keeps no such record.  Moves ``tpot_p90_ms``."""


def read(ctx):
    rep = ctx["report"]
    gap = getattr(rep, "host_gap_s", None)
    if gap is None:
        return None
    return 100.0 * gap / rep.wall_s
