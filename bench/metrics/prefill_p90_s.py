"""90th percentile of a request's prefill phase (first token minus
admission: its chunks, one per loop iteration), over every request due
in the window; one never admitted or never given a token counts as
infinite.  From the scheduler's own timestamps.  Moves ``ttft_p90_s``."""
import math

from bench.traffic import percentile


def read(ctx):
    phases = [
        r.first_token_time - r.admit_time
        if r.admit_time is not None and r.first_token_time is not None
        else math.inf
        for r in ctx["results"]
    ]
    return percentile(phases, 90)
