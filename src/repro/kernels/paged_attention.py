"""Pallas paged-attention kernels for the serving hot paths.

The XLA paged path (``models/attention.py``) materialises the FULL
logical view of a slot's KV — ``_paged_gather`` indexes the block pool
with the whole (nb,) block table, so one decode step or one prefill
chunk costs O(max_len) HBM gather traffic regardless of how short the
prefix is.  These kernels instead walk the block table in-kernel
(vLLM-style): the table and the per-slot positions are scalar-prefetched
so the BlockSpec index maps can fetch exactly the *mapped* pool blocks,
and every grid step past the prefix limit clamps its index map to the
last mapped block — Mosaic elides the repeated DMA, so HBM traffic is
O(prefix), not O(max_len).

The kernels take the whole stacked arena ``(L, n_blocks, bs, KV*hd)``
of a layer segment and the layer index, scalar-prefetched like the
table: the index maps address ``(layer, block)`` directly, so the layer
scan never cuts a layer's pool out of the arena (DESIGN.md §7).  The
heads share the minor axis, which keeps the arena in the row-major
``(bs, KV*hd)`` tiling that both the scatter and Mosaic take.

Numerics contract (tests/test_paged_attention.py): the kernels are
BITWISE equal to the dense-gather path in interpret mode.  Mapped
blocks are copied into a full-S ``(KV, S, hd)`` VMEM scratch (unmapped
tail left zero), and the final grid step replays the math of
``attention_decode`` / ``attention_dense`` on that scratch as dots
batched over KV heads, accumulated in f32 (DESIGN.md §7).  Compiled by
Mosaic, the class against the XLA oracle is measured on the chip
(DESIGN.md §7 table, ``chip_smoke.py``).  Tail and trash positions
hold zeros here vs. junk in the gathered view, but both are masked to
-1e30 before the softmax, ``exp`` underflows to exactly 0.0, and a 0.0
probability contributes exactly 0.0 to the PV
contraction either way — so the difference is value-invisible.

The attention math is intentionally REPLICATED here rather than
imported from ``models.attention`` (which would be an import cycle);
the differential tests pin the two copies together.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention", "paged_chunk_attention"]

_NEG = -1e30  # models.attention._NEG
_CHUNK_VMEM_LIMIT = 64 * 1024 * 1024


def _copy_block(kb_ref, vb_ref, ks_ref, vs_ref, kb, bs):
    """Copy one mapped (bs, KV*hd) pool block into the (KV, S, hd)
    scratch, one KV head (an hd-wide lane slice) at a time: each head's
    rows land as a dense (bs, hd) slab, so no in-kernel reshape (Mosaic
    tiles only the last two dims)."""
    off = pl.multiple_of(kb * bs, bs)
    hd = ks_ref.shape[-1]
    for kh in range(ks_ref.shape[0]):
        lanes = pl.ds(kh * hd, hd)
        ks_ref[kh, pl.ds(off, bs), :] = kb_ref[0, 0, :, lanes]
        vs_ref[kh, pl.ds(off, bs), :] = vb_ref[0, 0, :, lanes]


def _scores(q, k, interpret):
    """(KV, R, dh) x (KV, S, dh) -> (KV, R, S) f32: one dot batched over
    KV heads — the oracle's (b, k)-batched contraction, per slot.

    Mosaic contracts the minor dim of ``k`` natively.  XLA:CPU (interpret
    mode) picks a different GEMM kernel for a minor-contracting operand
    than for the oracle's (batch, contract, free) copy of the gathered
    cache, so interpret mode materialises that layout — pinned, or the
    simplifier folds the transpose back into the dot — to replay the
    oracle's summation order bitwise."""
    prec = _precision(k.dtype)
    if interpret:
        kt = lax.optimization_barrier(jnp.swapaxes(k, 1, 2))
        return jnp.einsum(
            "krd,kds->krs", q, kt, precision=prec,
            preferred_element_type=jnp.float32,
        )
    return jnp.einsum(
        "krd,ksd->krs", q, k, precision=prec,
        preferred_element_type=jnp.float32,
    )


def _pv(p, v):
    """(KV, R, S) x (KV, S, dh) -> (KV, R, dh), accumulated in f32 and
    rounded once to the cache dtype — what the oracle's cache-dtype
    contraction computes (and the only accumulator Mosaic's matmul
    accepts)."""
    return jnp.einsum(
        "krs,ksd->krd", p.astype(v.dtype), v, precision=_precision(v.dtype),
        preferred_element_type=jnp.float32,
    ).astype(v.dtype)


def _precision(dtype):
    """models.attention._precision: full f32 contraction for an f32 cache
    (a TPU's default f32 matmul is one bf16 pass); bf16 is exact in one."""
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _decode_kernel(
    # scalar prefetch
    bt_ref,  # (B, nb) int32 block tables
    pos_ref,  # (B,) int32 current token index per slot
    layer_ref,  # (1,) int32 the layer's index in the stacked arena
    # inputs
    q_ref,  # (1, KV, G, dh) this slot's query, grouped by KV head
    kb_ref,  # (1, 1, bs, KV*hd) the mapped K pool block for this step
    vb_ref,  # (1, 1, bs, KV*hd)
    # outputs
    out_ref,  # (1, KV, G, dh) pool dtype (attention_decode returns v.dtype)
    # scratch
    ks_ref,  # (KV, S, hd) pool dtype — full logical K view per KV head
    vs_ref,  # (KV, S, hd)
    *,
    bs: int,
    nb: int,
    window: int,
    interpret: bool,
):
    b = pl.program_id(0)
    kb = pl.program_id(1)
    pos = pos_ref[b]
    lim = pos // bs  # last logical block holding a valid key (ki <= pos)

    @pl.when(kb == 0)
    def _zero():
        ks_ref[...] = jnp.zeros_like(ks_ref)
        vs_ref[...] = jnp.zeros_like(vs_ref)

    @pl.when(kb <= lim)
    def _copy():
        _copy_block(kb_ref, vb_ref, ks_ref, vs_ref, kb, bs)

    @pl.when(kb == nb - 1)
    def _attend():
        # exact replica of attention_decode on the (KV, S, hd) scratch
        dh = q_ref.shape[-1]
        scale = dh**-0.5
        k = ks_ref[...]
        s = _scores(q_ref[0].astype(k.dtype), k, interpret) * scale
        ki = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = ki <= pos
        if window > 0:
            mask &= ki > pos - window
        s = jnp.where(mask, s, _NEG)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        out_ref[0] = _pv(p / l, vs_ref[...])


def _scratch(pool, nb, hd):
    return pltpu.VMEM(
        (pool.shape[3] // hd, nb * pool.shape[2], hd), pool.dtype
    )


# jitted under its own name, the kernel's operation in the compiled
# program is named ``paged_decode_attention``: the benchmark reads it
@functools.partial(
    jax.jit, static_argnames=("window", "interpret")
)
def paged_decode_attention(
    q: jax.Array,  # (B, H, dh)
    pool_k: jax.Array,  # (L, n_blocks, bs, KV*hd) stacked arena
    pool_v: jax.Array,  # (L, n_blocks, bs, KV*hd)
    layer: jax.Array,  # () int32 which layer of the arena to attend
    block_tables: jax.Array,  # (B, nb) int32
    pos: jax.Array,  # (B,) int32
    *,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """One-token paged attention: bitwise ``attention_decode`` over the
    gathered logical view of layer ``layer``, reading only the mapped
    prefix blocks."""
    bsz, nb = pool_k.shape[2], block_tables.shape[1]
    b, h, dh = q.shape
    kvh = pool_k.shape[3] // dh
    qg = q.reshape(b, kvh, h // kvh, dh)
    blk = (1, 1, bsz, pool_k.shape[3])

    def q_map(i, kb, bt, p, lyr):
        return (i, 0, 0, 0)

    def kv_map(i, kb, bt, p, lyr):
        # clamp beyond-limit steps to the last mapped block: the index
        # map then repeats, Mosaic elides the DMA, and pl.when skips the
        # copy — beyond-prefix blocks cost no HBM traffic.
        return (lyr[0], bt[i, jnp.minimum(kb, p[i] // bsz)], 0, 0)

    kernel = functools.partial(
        _decode_kernel, bs=bsz, nb=nb, window=window, interpret=interpret
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1,) + qg.shape[1:], q_map),
            pl.BlockSpec(blk, kv_map),
            pl.BlockSpec(blk, kv_map),
        ],
        out_specs=pl.BlockSpec((1,) + qg.shape[1:], q_map),
        scratch_shapes=[_scratch(pool_k, nb, dh), _scratch(pool_v, nb, dh)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, pool_v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32)[None], qg, pool_k, pool_v)
    return out.reshape(b, h, dh)


def _chunk_kernel(
    # scalar prefetch
    bt_ref,  # (nb,) int32 this slot's block table
    lim_ref,  # (1,) int32 last logical block the chunk touches
    start_ref,  # (1,) int32 logical position of the chunk's first token
    layer_ref,  # (1,) int32 the layer's index in the stacked arena
    # inputs
    q_ref,  # (G, KV, C, dh) chunk queries, head-major
    kb_ref,  # (1, 1, bs, KV*hd)
    vb_ref,  # (1, 1, bs, KV*hd)
    # outputs
    out_ref,  # (G, KV, C, dh)
    # scratch
    ks_ref,  # (KV, S, hd)
    vs_ref,  # (KV, S, hd)
    *,
    bs: int,
    nb: int,
    window: int,
    interpret: bool,
):
    kb = pl.program_id(0)
    lim = lim_ref[0]
    start = start_ref[0]

    @pl.when(kb == 0)
    def _zero():
        ks_ref[...] = jnp.zeros_like(ks_ref)
        vs_ref[...] = jnp.zeros_like(vs_ref)

    @pl.when(kb <= lim)
    def _copy():
        _copy_block(kb_ref, vb_ref, ks_ref, vs_ref, kb, bs)

    @pl.when(kb == nb - 1)
    def _attend():
        # exact replica of attention_dense on the (KV, S, hd) scratch,
        # one query head per KV group at a time (bounds the (KV, C, S)
        # score block)
        g, kvh, c, dh = q_ref.shape
        scale = dh**-0.5
        k = ks_ref[...]
        v = vs_ref[...]
        shape = (kvh, c, nb * bs)
        qi = start + lax.broadcasted_iota(jnp.int32, shape, 1)
        ki = lax.broadcasted_iota(jnp.int32, shape, 2)
        mask = ki <= qi
        if window > 0:
            mask &= ki > qi - window
        # _gqa_scores contracts in the promoted operand dtype: round the
        # f32 accumulator to it before the f32 softmax
        sdt = jnp.promote_types(q_ref.dtype, k.dtype)
        for j in range(g):
            s = _scores(q_ref[j], k, interpret).astype(sdt)
            s = s.astype(jnp.float32) * scale
            s = jnp.where(mask, s, _NEG)
            out_ref[j] = _pv(jax.nn.softmax(s, axis=-1), v)


def paged_chunk_attention(
    q: jax.Array,  # (1, C, H, dh) chunk queries (batch of one slot)
    pool_k: jax.Array,  # (L, n_blocks, bs, KV*hd) stacked arena
    pool_v: jax.Array,
    layer: jax.Array,  # () int32 which layer of the arena to attend
    bt_row: jax.Array,  # (nb,) int32
    start: jax.Array,  # scalar int32 logical position of first token
    n_valid: jax.Array,  # scalar int32 valid tokens in the chunk
    *,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Chunk-prefill paged attention: bitwise ``attention_dense`` over
    the gathered logical view of layer ``layer``, reading only blocks
    0..ceil((start+C)/bs) — chunk cost is O(prefix), not O(max_len)."""
    _, c, h, dh = q.shape
    bsz, nb = pool_k.shape[2], bt_row.shape[0]
    # last block the chunk's causal view can reach: its final VALID
    # token sits at logical position start + n_valid - 1.  (Pad queries
    # past n_valid attend over a zero tail here vs junk on the dense
    # path — their outputs are discarded by the caller either way.)
    last = jnp.maximum(start + n_valid - 1, 0)
    lim = jnp.minimum(last // bsz, nb - 1).astype(jnp.int32)
    return _paged_chunk_call(
        q[0],
        pool_k,
        pool_v,
        bt_row.astype(jnp.int32),
        lim[None],
        jnp.asarray(start, jnp.int32)[None],
        jnp.asarray(layer, jnp.int32)[None],
        window=window,
        interpret=interpret,
    )[None]


# jitted under its own name, the kernel's operation in the compiled
# program is named ``_paged_chunk_call``: the benchmark reads it
@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _paged_chunk_call(
    q, pool_k, pool_v, bt_row, lim, start, layer, *, window, interpret
):
    c, h, dh = q.shape
    bsz, nb = pool_k.shape[2], bt_row.shape[0]
    kvh = pool_k.shape[3] // dh
    blk = (1, 1, bsz, pool_k.shape[3])
    # head-major (G, KV, C, dh): each query head is a dense (C, dh) slab
    qh = q.reshape(c, kvh, h // kvh, dh).transpose(2, 1, 0, 3)

    def q_map(kb, bt, lim, st, lyr):
        return (0, 0, 0, 0)

    def kv_map(kb, bt, lim, st, lyr):
        return (lyr[0], bt[jnp.minimum(kb, lim[0])], 0, 0)

    kernel = functools.partial(
        _chunk_kernel, bs=bsz, nb=nb, window=window, interpret=interpret
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(qh.shape, q_map),
            pl.BlockSpec(blk, kv_map),
            pl.BlockSpec(blk, kv_map),
        ],
        out_specs=pl.BlockSpec(qh.shape, q_map),
        scratch_shapes=[_scratch(pool_k, nb, dh), _scratch(pool_v, nb, dh)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, pool_v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # an f32 cache contracts at HIGHEST, which keeps bf16 hi/lo
            # splits of K, V and each (KV, C, S) probability block beside
            # them: ~23 MB at C=128, max_len=2048, over the 16 MB default
            # scoped limit (a v5e core has 128 MiB of VMEM)
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(bt_row, lim, start, layer, qh, pool_k, pool_v)
    return out.transpose(2, 1, 0, 3).reshape(c, h, dh)
