"""90th percentile of the time a request waited for a lane (admission
minus due time), over every request due in the window; one never
admitted counts as infinite.  From the scheduler's own timestamps.
Moves ``ttft_p90_s``."""
import math

from bench.traffic import percentile


def read(ctx):
    waits = [
        r.admit_time - r.submit_time if r.admit_time is not None else math.inf
        for r in ctx["results"]
    ]
    return percentile(waits, 90)
