"""Pallas TPU kernel for the faithful bit-sliced DPE matmul.

This is the compute hot-spot of MemIntelli: every (input-slice x
weight-slice) pair is an analog crossbar matmul whose bit-line current is
ADC-quantised per crossbar tile, then digitally recombined with the slice
significances and per-block scales (paper §3.3, Fig. 5/6).

TPU adaptation (DESIGN.md §3): instead of the paper's S_x * S_w separate
GEMM launches, ONE kernel walks the K dimension in ``bk``-sized crossbar
blocks with a fused slice-pair loop.  Per grid step it holds

  * the input-slice tile   (Sx, bm, bk)  in VMEM,
  * the weight-slice tile  (Sw, bk, bn)  in VMEM,
  * a float32 accumulator  (bm, bn)      in the output VMEM block,

so X/W slice tiles are read from HBM exactly once, and all MXU matmuls
are 128-aligned.  The simulated crossbar tile is aligned with the MXU
tile (bk = array rows, bn = array cols), keeping per-block ADC semantics
faithful while hardware-efficient.

ADC dynamic range: the paper's "dynamic" mode takes the per-block max of
the partial sums.  The exact behavioural path reduces over all M rows;
the kernel necessarily reduces over its ``bm`` row tile (grid-parallel in
M).  ``ref.py`` mirrors the kernel's tiling so kernel<->oracle comparison
is exact; "fullscale" mode uses a static physical range and is
granularity-independent.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary") so the output tile is
revisited and accumulated in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.slicing import SliceSpec, slice_significances

__all__ = ["sliced_matmul_pallas", "fused_sliced_matmul_pallas"]

_EPS = 1e-30


def _pin(x, interpret: bool):
    """Rounding barrier for the oracle contract (interpret mode only).

    XLA's HLO simplifier contracts ``acc + scale * p`` chains into fmas
    whose skipped intermediate rounding the pure-jnp oracle cannot
    reproduce; pinning the multiply result stops that class.  A second
    class survives *below* HLO — the CPU (LLVM) backend contracts
    mul+add even across an ``optimization_barrier`` and even across a
    VMEM store — and is unfixable from jnp.  It is value-exact whenever
    the multiplier is a power of two, which is why the fp slice specs
    (pow2 block scales) are bitwise vs ``ref.py`` while the int specs
    carry a documented few-ulp cross-K bound (DESIGN.md §3,
    tests/test_kernel_oracle.py).  Compiled TPU lowering has no
    ``optimization_barrier`` rule (Mosaic controls contraction there),
    so the perf path is left untouched and sits under the norm-tolerance
    side of the contract.
    """
    return lax.optimization_barrier(x) if interpret else x


def _slice_dot(x, w):
    """One slice-pair crossbar read: an f32 contraction at full f32
    precision — a TPU's default f32 matmul is one bf16 pass, which would
    round the programmed (noisy) slice values to 8 mantissa bits."""
    return jnp.dot(
        x, w, precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _adc(p, i, j, *, bits_x, bits_w, bk, radc, adc_mode):
    """Per-pair ADC quantisation of one (bm, bn) partial-sum tile.

    ``dynamic`` ranges over the whole tile (rows and bit-lines — the
    kernel's rows are its bm tile, mirrored exactly by ``ref.py``);
    ``dynamic_row`` ranges per row over the bit-line axis only, which is
    m-tiling independent — the row-independence contract continuous
    batching relies on (DESIGN.md §7); ``fullscale`` is static.
    """
    if radc <= 1:
        return p
    if adc_mode == "dynamic":
        ymax = jnp.maximum(jnp.max(p), _EPS)
    elif adc_mode == "dynamic_row":
        ymax = jnp.maximum(jnp.max(p, axis=1, keepdims=True), _EPS)
    else:
        ymax = jnp.float32(
            bk * (2.0 ** bits_x[i] - 1.0) * (2.0 ** bits_w[j] - 1.0)
        )
    step = ymax / (radc - 1)
    return jnp.round(p / step) * step


def _kernel(
    xs_ref,  # (Sx, bm, bk)
    sx_ref,  # (bm, 1) this k-block's input scales
    ws_ref,  # (Sw, bk, bn)
    sw_ref,  # (nk, nn) weight scale table, whole, in SMEM
    out_ref,  # (bm, bn) float32 accumulator
    *,
    sigx: tuple[float, ...],
    sigw: tuple[float, ...],
    bits_x: tuple[int, ...],
    bits_w: tuple[int, ...],
    bk: int,
    radc: int,
    adc_mode: str,
    nk: int,
    interpret: bool,
):
    j_, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for i in range(len(sigx)):
        xi = xs_ref[i].astype(jnp.float32)
        for j in range(len(sigw)):
            wj = ws_ref[j].astype(jnp.float32)
            p = _slice_dot(xi, wj)
            p = _adc(p, i, j, bits_x=bits_x, bits_w=bits_w, bk=bk,
                     radc=radc, adc_mode=adc_mode)
            acc = acc + _pin(jnp.float32(sigx[i] * sigw[j]) * p, interpret)
    # Per-block scales: sx is per (row, k-block), sw per (k-block, n-block).
    acc = _pin(acc * sx_ref[...] * sw_ref[k, j_], interpret)
    out_ref[...] += acc


def _scale_table_spec():
    """The (nk, nn) weight-scale table, whole, in SMEM: the kernel reads
    one scalar per grid step at ``(k, j)``.  (A (1, 1) VMEM block of it
    breaks Mosaic's rule that a block's last two dims be multiples of
    (8, 128) or the array's own.)"""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(
    jax.jit,
    static_argnames=(
        "input_spec",
        "weight_spec",
        "array_size",
        "radc",
        "adc_mode",
        "bm",
        "interpret",
    ),
)
def sliced_matmul_pallas(
    xs: jax.Array,  # (Sx, M, Kp) slice values (DAC'd)
    sx: jax.Array,  # (M, nk) input block scales
    ws: jax.Array,  # (Sw, Kp, Np) programmed (noisy) weight slice values
    sw: jax.Array,  # (nk, nn) weight block scales
    *,
    input_spec: SliceSpec,
    weight_spec: SliceSpec,
    array_size: tuple[int, int],
    radc: int,
    adc_mode: str,
    bm: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused faithful DPE matmul.  Returns (M, Np) float32.

    M must be a multiple of ``bm``; Kp/Np must be multiples of the array
    tile (callers pad — see ``repro.kernels.ops``).
    """
    bk, bn = array_size
    sxn, m, kp = xs.shape
    swn, _, np_ = ws.shape
    nk, nn = kp // bk, np_ // bn
    if m % bm:
        raise ValueError(f"M={m} not a multiple of bm={bm}")
    if kp % bk or np_ % bn:
        raise ValueError("K/N must be padded to the array tile")

    sigx = tuple(float(s) for s in slice_significances(input_spec))
    sigw = tuple(float(s) for s in slice_significances(weight_spec))

    kernel = functools.partial(
        _kernel,
        sigx=sigx,
        sigw=sigw,
        bits_x=tuple(input_spec.bits),
        bits_w=tuple(weight_spec.bits),
        bk=bk,
        radc=radc,
        adc_mode=adc_mode,
        nk=nk,
        interpret=interpret,
    )
    grid = (m // bm, nn, nk)
    # input scales k-block-major (nk, M, 1): a (bm, 1) block of it keeps
    # the array's own last dim, which Mosaic accepts
    sx_t = sx.T[:, :, None]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sxn, bm, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((None, bm, 1), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((swn, bk, bn), lambda i, j, k: (0, k, j)),
            _scale_table_spec(),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, np_), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xs, sx_t, ws, sw)


# ---------------------------------------------------------------------------
# fused kernel: in-kernel prepare_input (quantise + bit-slice + DAC)
# ---------------------------------------------------------------------------


def _prep_input_tile(xt, *, spec: SliceSpec, rdac: int):
    """In-kernel ``prepare_input`` for one (bm, bk) input tile.

    Replicates ``core.dpe.prepare_input`` elementwise-exactly for this
    (row, k-block) tile: per-row absmax over the k-block -> block scale
    (``core.quant.block_scale``) -> round/clip quantise -> two's-
    complement bit-slice (``core.slicing.slice_int``) -> per-slice DAC
    (``core.quant.dac_quantize``).  All reductions are per row, so the
    result is independent of the bm tiling — bitwise the same slices the
    host pipeline hands the staged kernel.

    Returns (slices [(bm, bk) f32 per slice], sx (bm, 1) f32).
    """
    absmax = jnp.maximum(jnp.max(jnp.abs(xt), axis=1, keepdims=True), _EPS)
    b = spec.total_bits
    if spec.kind == "int":
        levels = 2.0 ** (b - 1) - 1.0 if spec.signed else 2.0**b - 1.0
        sx = absmax / jnp.float32(levels)
    else:
        # shared-exponent pre-alignment: power-of-two block scale
        sx = jnp.exp2(jnp.floor(jnp.log2(absmax)) - (b - 2))
    xq = jnp.clip(
        jnp.round(xt / sx), spec.qmin, spec.qmax
    ).astype(jnp.int32)
    u = jnp.bitwise_and(xq, (1 << b) - 1)  # two's-complement wrap
    slices = []
    for width, off in zip(spec.bits, spec.lsb_offsets):
        v = jnp.bitwise_and(
            jnp.right_shift(u, off), (1 << width) - 1
        ).astype(jnp.float32)
        vmax = float(2**width - 1)
        if rdac > 1 and (rdac - 1) % max(int(vmax), 1) != 0:
            dstep = vmax / (rdac - 1)
            v = jnp.round(v / dstep) * dstep
        slices.append(v)
    return slices, sx


def _fused_kernel(
    x_ref,  # (bm, bk) raw float input tile
    ws_ref,  # (Sw, bk, bn)
    sw_ref,  # (nk, nn) weight scale table, whole, in SMEM
    out_ref,  # (bm, bn) float32 accumulator
    *,
    input_spec: SliceSpec,
    sigx: tuple[float, ...],
    sigw: tuple[float, ...],
    bits_w: tuple[int, ...],
    bk: int,
    rdac: int,
    radc: int,
    adc_mode: str,
    interpret: bool,
):
    j_, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xs, sx = _prep_input_tile(
        x_ref[...].astype(jnp.float32), spec=input_spec, rdac=rdac
    )
    bits_x = tuple(input_spec.bits)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for i in range(len(sigx)):
        for j in range(len(sigw)):
            wj = ws_ref[j].astype(jnp.float32)
            p = _slice_dot(xs[i], wj)
            p = _adc(p, i, j, bits_x=bits_x, bits_w=bits_w, bk=bk,
                     radc=radc, adc_mode=adc_mode)
            acc = acc + _pin(jnp.float32(sigx[i] * sigw[j]) * p, interpret)
    acc = _pin(acc * sx * sw_ref[k, j_], interpret)
    out_ref[...] += acc


# jitted under its own name, the kernel's operation in the compiled
# program is named ``fused_sliced_matmul_pallas``: the benchmark reads it
@functools.partial(
    jax.jit,
    static_argnames=(
        "input_spec",
        "weight_spec",
        "array_size",
        "rdac",
        "radc",
        "adc_mode",
        "bm",
        "interpret",
    ),
)
def fused_sliced_matmul_pallas(
    x: jax.Array,  # (M, Kp) RAW float input (not yet quantised/sliced)
    ws: jax.Array,  # (Sw, Kp, Np) programmed (noisy) weight slice values
    sw: jax.Array,  # (nk, nn) weight block scales
    *,
    input_spec: SliceSpec,
    weight_spec: SliceSpec,
    array_size: tuple[int, int],
    rdac: int,
    radc: int,
    adc_mode: str,
    bm: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fully-fused faithful DPE matmul: ONE kernel launch runs
    prepare_input (quantise + bit-slice + DAC), all Sx*Sw slice-pair
    matmuls, the per-pair ADC and the digital recombination, with the
    slice tiles resident in VMEM.

    Vs the staged path (host ``prepare_input`` materialising an
    (Sx, M, Kp) slice stack in HBM, then ``sliced_matmul_pallas``
    reading it back), the fused kernel reads each raw (bm, bk) input
    tile once and derives its slices in registers/VMEM — the HBM input
    traffic drops from (1 + 2*Sx) * M * Kp floats (write + read of the
    stack plus the original read) to M * Kp per n-tile sweep.  Input
    prep is recomputed per n-tile j (nn passes): negligible VPU work
    next to the Sx*Sw MXU matmuls it unblocks.

    Returns (M, Np) float32.  M must be a multiple of ``bm``; Kp/Np of
    the array tile (callers pad — see ``repro.kernels.ops``).
    """
    bk, bn = array_size
    m, kp = x.shape
    swn, _, np_ = ws.shape
    nk, nn = kp // bk, np_ // bn
    if m % bm:
        raise ValueError(f"M={m} not a multiple of bm={bm}")
    if kp % bk or np_ % bn:
        raise ValueError("K/N must be padded to the array tile")

    sigx = tuple(float(s) for s in slice_significances(input_spec))
    sigw = tuple(float(s) for s in slice_significances(weight_spec))
    kernel = functools.partial(
        _fused_kernel,
        input_spec=input_spec,
        sigx=sigx,
        sigw=sigw,
        bits_w=tuple(weight_spec.bits),
        bk=bk,
        rdac=rdac,
        radc=radc,
        adc_mode=adc_mode,
        interpret=interpret,
    )
    grid = (m // bm, nn, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((swn, bk, bn), lambda i, j, k: (0, k, j)),
            _scale_table_spec(),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, np_), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, ws, sw)
