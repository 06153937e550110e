"""The whole step's share of the chip's bf16 peak: the simulated
operations of every processed token (the GEMMs, with every slice pair
under the faithful engine, and attention over the live context) over
the traced window times the peak.  Moves ``sim_tokens_per_s``."""
from bench import work
from bench.readers import calls


def read(ctx):
    size, served = ctx["size"], ctx["served"]
    ops = work.dpe_gemm(size, ctx["cell"].conf["device"], served,
                        **calls(ctx)).ops
    ops += work.paged_decode(size, served, ctx["kv_itemsize"]).ops
    ops += work.paged_chunk(size, served, ctx["chunk"], ctx["kv_itemsize"]).ops
    peak = work.peaks(ctx["device_kind"])
    return 100.0 * ops / (ctx["trace"].window_s * peak["flops_bf16"])
