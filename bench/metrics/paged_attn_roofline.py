"""The paged decode and chunk attention kernels' share of their
roofline: the least time the attention work of the window needs (the
QK and PV products over each query's live context, and the keys and
values read once per step), over the kernels' summed device time.
Moves ``sim_tokens_per_s``."""
from bench import work
from bench.readers import PAGED_KERNELS, kernel_share


def read(ctx):
    need = work.paged_decode(ctx["size"], ctx["served"], ctx["kv_itemsize"])
    need += work.paged_chunk(ctx["size"], ctx["served"], ctx["chunk"],
                             ctx["kv_itemsize"])
    return kernel_share(ctx, PAGED_KERNELS, need)
