"""The fused faithful DPE kernel's share of its roofline: the least time
the simulated GEMM work of the window needs at the chip's bf16 peak and
HBM bandwidth (``bench/work.py``), over the summed device time of the
kernel's events.  Moves ``sim_tokens_per_s``."""
from bench import work
from bench.readers import DPE_KERNEL, calls, kernel_share


def read(ctx):
    need = work.dpe_gemm(ctx["size"], ctx["cell"].conf["device"],
                         ctx["served"], **calls(ctx))
    return kernel_share(ctx, DPE_KERNEL, need)
