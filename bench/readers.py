"""What the per-layer readers share: the names the program's kernels
and step programs carry in the trace, and the arithmetic over them.

The names are today's: a Pallas kernel's operation carries the name of
the jitted function that wraps its ``pallas_call``, and a step program
the name of its Python function.  Nothing in the program pins them yet.
"""
from __future__ import annotations

from bench import work

DPE_KERNEL = r"^fused_sliced_matmul_pallas$"
PAGED_KERNELS = r"^(paged_decode_attention|_paged_chunk_call)$"
DECODE_PROGRAM = r"^jit_step$"


def calls(ctx) -> dict:
    """Model steps of the window: every prefill chunk and decode step
    runs all layers; decode steps and each prompt's last chunk run the
    vocabulary head."""
    rep = ctx["report"]
    return {
        "layer_calls": rep.decode_steps + rep.prefill_chunks_run,
        "head_calls": rep.decode_steps + len(ctx["served"]),
    }


def kernel_share(ctx, pattern: str, need: work.Work):
    seconds = ctx["trace"].op_seconds(pattern)
    return work.share(need, seconds, work.peaks(ctx["device_kind"]))


def decode_step_ms(ctx):
    steps = ctx["trace"].module_durations(DECODE_PROGRAM)
    if not steps:
        return None
    return 1e3 * sum(steps) / len(steps)


def idle_share(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
