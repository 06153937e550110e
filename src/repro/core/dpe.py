"""Variable-precision bit-sliced dot-product engine — MemIntelli §3.3.

The pipeline for ``y ≈ x @ w`` (Fig. 5 / Fig. 6 / Fig. 7):

1. **Block mapping** — ``w (K,N)`` is tiled into ``array_size = (bk,bn)``
   crossbar tiles (zero-padded); ``x (M,K)`` is tiled along K.  Quantisation
   / pre-alignment coefficients are *per block* to bound dynamic-range error.
2. **Quantise + slice** — per block, operands become unsigned bit-slices
   (:mod:`repro.core.slicing`); weight slices go through the log-normal
   programming model (:mod:`repro.core.device`), inputs through the DAC.
3. **Analog matmul** — every (input-slice × weight-slice) pair is one
   crossbar operation per K-block; the bit-line current is ADC-quantised.
4. **Digital recombination** — partial sums are weighted by the slice
   significances and the per-block scales, then accumulated over K-blocks.

Three modes (DESIGN.md §4): ``faithful`` (paper semantics), ``fast``
(beyond-paper digital slice folding — exact when the ADC is ideal), and
``digital`` (software baseline).

Engine schedule (vectorized, PR 1): the faithful path computes every
(input-slice x weight-slice) pair of a K-block in ONE batched GEMM over
the stacked pair axis, applies the per-pair ADC to the whole
(Sx, M, Sw, nn, bn) partial stack in a single fused quantize+recombine
pass, and takes an exact folded single-GEMM shortcut when the ADC is
ideal.  The seed slice-pair loop survives as
:func:`_faithful_matmul_loop` — the equivalence oracle
(tests/test_exactness.py) and the perf baseline ``benchmarks/run.py
--json`` tracks speedups against.  Backend selection (xla / pallas /
circuit / auto) is resolved by :func:`resolve_backend`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .device import noisy_slice_values
from .drift import drift_now
from .engine import DPEConfig
from .quant import adc_quantize, block_scale, dac_quantize, quantize
from .slicing import SliceSpec, slice_int, slice_significances

__all__ = [
    "PreparedWeight",
    "FoldedWeight",
    "prepare_weight",
    "prepare_input",
    "program_weight",
    "dpe_matmul",
    "dpe_matmul_prepared",
    "dpe_matmul_folded",
    "dpe_apply",
    "resolve_backend",
    "relative_error",
]


class PreparedWeight(NamedTuple):
    """A weight matrix programmed onto (simulated) crossbar tiles.

    slices: (Sw, Kp, Np) float32 — noisy slice values (analog domain).
    scale:  (nk, nn)     float32 — per-block quant / pre-alignment scale.
    t_prog: ()           float32 — device-clock programming timestamp of
            this generation (drift reference point), or ``None`` when the
            state is untimed (drift then never applies; ``None`` adds no
            pytree leaf, so direct ``prepare_weight`` callers see the same
            leaf structure as before).
    """

    slices: jax.Array
    scale: jax.Array
    t_prog: jax.Array | None = None


class FoldedWeight(NamedTuple):
    """Fast-mode programmed state: the digitally-folded noisy effective
    weight (Kp, Np) in ``cfg.store_dtype`` (see :func:`fold_weight_noisy`).
    O(K*N) memory instead of the O(Sw*K*N) slice stack — what a
    weight-stationary deployment keeps resident per fast-mode layer
    (DESIGN.md §5).  ``t_prog`` as on :class:`PreparedWeight`."""

    w_eff: jax.Array
    t_prog: jax.Array | None = None


def _pad_to(a: jax.Array, axis: int, mult: int) -> jax.Array:
    size = a.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, rem)
    return jnp.pad(a, pads)


def prepare_weight(
    w: jax.Array, cfg: DPEConfig, key: jax.Array | None = None
) -> PreparedWeight:
    """Quantise, slice and 'program' a weight matrix (paper's
    ``update_weight()``).  ``key`` drives programming noise; pass None for
    ideal devices."""
    if w.ndim != 2:
        raise ValueError(f"weights must be 2-D, got {w.shape}")
    bk, bn = cfg.array_size
    spec = cfg.weight_spec
    wp = _pad_to(_pad_to(w.astype(jnp.float32), 0, bk), 1, bn)
    kp, np_ = wp.shape
    nk, nn = kp // bk, np_ // bn
    wb = wp.reshape(nk, bk, nn, bn)
    absmax = jnp.max(jnp.abs(wb), axis=(1, 3))  # (nk, nn)
    scale = block_scale(absmax, spec)
    wq = quantize(wb, scale[:, None, :, None], spec)  # int32 (nk,bk,nn,bn)
    ws = slice_int(wq, spec).astype(jnp.float32)  # (Sw,nk,bk,nn,bn)
    if cfg.cv > 0.0 and key is not None:
        outs = []
        for s, width in enumerate(spec.bits):
            outs.append(
                noisy_slice_values(
                    jax.random.fold_in(key, s),
                    ws[s],
                    width,
                    cfg.hgs,
                    cfg.lgs,
                    cfg.cv,
                )
            )
        ws = jnp.stack(outs, axis=0)
    # (Sw, nk, bk, nn, bn) -> (Sw, Kp, Np): adjacent axes merge directly.
    ws_flat = ws.reshape(spec.n_slices, kp, np_)
    return PreparedWeight(slices=ws_flat, scale=scale)


def prepare_input(
    x: jax.Array, cfg: DPEConfig
) -> tuple[jax.Array, jax.Array]:
    """Quantise + slice + DAC the input.

    Args:
      x: (M, K) float.
    Returns:
      xs: (Sx, M, Kp) float32 DAC'd slice values; sx: (M, nk) scales.
    """
    bk, _ = cfg.array_size
    spec = cfg.input_spec
    xp = _pad_to(x.astype(jnp.float32), 1, bk)
    m, kp = xp.shape
    nk = kp // bk
    xb = xp.reshape(m, nk, bk)
    absmax = jnp.max(jnp.abs(xb), axis=2)  # (M, nk)
    sx = block_scale(absmax, spec)
    xq = quantize(xb, sx[:, :, None], spec)
    xs = slice_int(xq, spec).astype(jnp.float32)  # (Sx, M, nk, bk)
    outs = []
    for s, width in enumerate(spec.bits):
        vmax = float(2**width - 1)
        outs.append(dac_quantize(xs[s], cfg.rdac, vmax))
    xs = jnp.stack(outs, axis=0)
    return xs.reshape(spec.n_slices, m, kp), sx


def _adc_fullscale(cfg: DPEConfig, bx: int, bw: int) -> float:
    bk, _ = cfg.array_size
    return float(bk) * (2.0**bx - 1.0) * (2.0**bw - 1.0)


def _pair_fullscale(cfg: DPEConfig) -> jax.Array:
    """Static per-pair ADC full-scale, shape (Sx, 1, Sw, 1, 1)."""
    fs = [
        [_adc_fullscale(cfg, bx, bw) for bw in cfg.weight_spec.bits]
        for bx in cfg.input_spec.bits
    ]
    sxn = cfg.input_spec.n_slices
    swn = cfg.weight_spec.n_slices
    return jnp.asarray(fs, jnp.float32).reshape(sxn, 1, swn, 1, 1)


def _pair_significances(cfg: DPEConfig) -> jax.Array:
    """Recombination weight of each (input-slice, weight-slice) pair —
    shape (Sx, Sw)."""
    sigx = slice_significances(cfg.input_spec)
    sigw = slice_significances(cfg.weight_spec)
    return jnp.asarray(sigx[:, None] * sigw[None, :], jnp.float32)


def _faithful_matmul(
    xs: jax.Array,
    sx: jax.Array,
    ws: jax.Array,
    sw: jax.Array,
    cfg: DPEConfig,
) -> jax.Array:
    """Per slice-pair, per K-block analog matmul with ADC (paper path).

    Vectorized engine: all Sx*Sw slice pairs of one K-block are computed
    by a single batched contraction — one (Sx·M, bk) x (bk, Sw·Np) GEMM
    on the MXU/AVX units instead of Sx*Sw small launches — the per-pair
    ADC quantisation is applied to the stacked (Sx, M, Sw, nn, bn)
    partial-sum tensor in one vectorized pass (one fused max reduction
    instead of Sx*Sw separate ones), and the digital recombination is one
    contraction against the (Sx, Sw) pair-significance table.  ADC
    arithmetic goes through the same :func:`repro.core.quant.adc_quantize`
    expression as the seed slice-pair loop (kept verbatim as
    :func:`_faithful_matmul_loop`), so outputs agree to float-reassociation
    ulps (<=1e-5 rel; see tests/test_exactness.py).

    When the ADC is ideal (``radc <= 1``) the per-pair partial sums are
    never observed individually — recombination is linear — so the whole
    computation collapses exactly to the digitally-folded single GEMM of
    :func:`_fast_matmul` (DESIGN.md §4).  We take that shortcut: it is the
    same math at ~Sx*Sw times less compute.

    xs: (Sx, M, Kp); sx: (M, nk); ws: (Sw, Kp, Np); sw: (nk, nn).
    Returns (M, Np) float32.
    """
    if cfg.radc <= 1:
        return _fast_matmul(xs, sx, ws, sw, cfg)
    bk, bn = cfg.array_size
    sxn, m, kp = xs.shape
    swn, _, np_ = ws.shape
    nk, nn = kp // bk, np_ // bn
    sig_pair = _pair_significances(cfg)[:, None, :, None, None]
    ymax_fs = _pair_fullscale(cfg)
    xsb = xs.reshape(sxn, m, nk, bk)
    wsb = ws.reshape(swn, nk, bk, np_)

    acc = jnp.zeros((m, np_), jnp.float32)
    # The K-block walk is a static Python loop (nk small): each iteration
    # is one fused GEMM + one reduction + one quantize-recombine pass,
    # and the (Sx, M, Sw, nn, bn) partial stack stays cache-resident.
    for kb in range(nk):
        # One batched GEMM over the stacked slice-pair axis, in the
        # transpose-free dot_general layout (Sx, M, Sw, Np).  HIGHEST:
        # a TPU's default f32 contraction is one bf16 pass, which would
        # round the programmed (noisy) slice values to 8 mantissa bits
        # before the ADC sees the partial sums.
        p = lax.dot_general(
            xsb[:, :, kb], wsb[:, kb], (((2,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).reshape(sxn, m, swn, nn, bn)
        if cfg.adc_mode == "dynamic":
            # per-pair, per-n-block dynamic range (max over the rows and
            # bit-lines of one crossbar) — same as the seed loop, but one
            # vectorized two-stage reduction (innermost bit-line axis
            # first, so both stages stream contiguously).
            ymax = jnp.max(
                jnp.max(p, axis=4, keepdims=True), axis=1, keepdims=True
            )
        elif cfg.adc_mode == "dynamic_row":
            # per-INPUT-VECTOR range: each row of M is a separate analog
            # read in real hardware, so its tracked ADC range must not
            # see the other rows — the row-independence contract that
            # continuous batching relies on (DESIGN.md §7).
            ymax = jnp.max(p, axis=4, keepdims=True)
        else:
            ymax = ymax_fs
        # adc_quantize (round(p/step)*step) with the *step and the pair
        # significance folded into one coefficient so the quantize and
        # the recombination reduce in a single pass over the stack.
        step = jnp.maximum(ymax, 1e-30) / (cfg.radc - 1)
        out = jnp.sum((sig_pair * step) * jnp.round(p / step), axis=(0, 2))
        out = out * sx[:, kb][:, None, None] * sw[kb][None, :, None]
        acc = acc + out.reshape(m, np_)
    return acc


def _faithful_matmul_loop(
    xs: jax.Array,
    sx: jax.Array,
    ws: jax.Array,
    sw: jax.Array,
    cfg: DPEConfig,
) -> jax.Array:
    """Seed (pre-vectorization) slice-pair loop — kept verbatim as the
    equivalence oracle for :func:`_faithful_matmul` and as the perf
    baseline that ``benchmarks/run.py --json`` reports speedups against.
    Do not optimise this function.
    """
    bk, bn = cfg.array_size
    sxn, m, kp = xs.shape
    swn, _, np_ = ws.shape
    nk, nn = kp // bk, np_ // bn
    sigx = slice_significances(cfg.input_spec)
    sigw = slice_significances(cfg.weight_spec)
    xsb = xs.reshape(sxn, m, nk, bk)
    wsb = ws.reshape(swn, nk, bk, np_)

    def kb_body(kb, acc):
        xk = lax.dynamic_index_in_dim(xsb, kb, axis=2, keepdims=False)
        wk = lax.dynamic_index_in_dim(wsb, kb, axis=1, keepdims=False)
        out = jnp.zeros((m, nn, bn), jnp.float32)
        for i in range(sxn):
            for j in range(swn):
                p = (xk[i] @ wk[j]).reshape(m, nn, bn)
                if cfg.radc > 1:
                    if cfg.adc_mode == "dynamic":
                        ymax = jnp.max(p, axis=(0, 2), keepdims=True)
                    elif cfg.adc_mode == "dynamic_row":
                        ymax = jnp.max(p, axis=2, keepdims=True)
                    else:
                        ymax = jnp.float32(
                            _adc_fullscale(
                                cfg,
                                cfg.input_spec.bits[i],
                                cfg.weight_spec.bits[j],
                            )
                        )
                    p = adc_quantize(p, cfg.radc, ymax)
                out = out + float(sigx[i] * sigw[j]) * p
        sxk = lax.dynamic_index_in_dim(sx, kb, axis=1, keepdims=False)
        swk = lax.dynamic_index_in_dim(sw, kb, axis=0, keepdims=False)
        out = out * sxk[:, None, None] * swk[None, :, None]
        return acc + out.reshape(m, np_)

    return lax.fori_loop(
        0, nk, kb_body, jnp.zeros((m, np_), jnp.float32), unroll=False
    )


def _fast_matmul(
    xs: jax.Array,
    sx: jax.Array,
    ws: jax.Array,
    sw: jax.Array,
    cfg: DPEConfig,
) -> jax.Array:
    """Beyond-paper: digitally fold slices *before* the GEMM.

    One GEMM instead of Sx*Sw; identical result when the ADC is ideal
    because recombination is linear and noise lives on individual slice
    values (already folded in).  See DESIGN.md §4 and §Perf.
    """
    bk, bn = cfg.array_size
    sxn, m, kp = xs.shape
    swn, _, np_ = ws.shape
    nk, nn = kp // bk, np_ // bn
    sigx = jnp.asarray(slice_significances(cfg.input_spec), jnp.float32)
    sigw = jnp.asarray(slice_significances(cfg.weight_spec), jnp.float32)
    # Fold slices: x_eff (M,Kp) carries sx per block; w_eff (Kp,Np) per blk.
    x_eff = jnp.einsum("s,smk->mk", sigx, xs)
    w_eff = jnp.einsum("s,skn->kn", sigw, ws)
    x_deq = (x_eff.reshape(m, nk, bk) * sx[:, :, None]).reshape(m, kp)
    w_deq = (
        w_eff.reshape(nk, bk, nn, bn) * sw[:, None, :, None]
    ).reshape(kp, np_)
    return x_deq @ w_deq


def fold_weight_noisy(
    w: jax.Array, cfg: DPEConfig, key: jax.Array | None = None
) -> jax.Array:
    """Single-pass fast-mode weight pipeline: quantise per block, apply
    per-slice programming noise, digitally recombine — WITHOUT ever
    materialising the (S_w, K, N) slice stack (O(K*N) memory instead of
    O(S_w*K*N); critical for trillion-parameter MoE steps).

    Returns the dequantised noisy effective weight (Kp, Np) in
    ``cfg.store_dtype``; identical math to prepare_weight + slice fold.
    """
    bk, bn = cfg.array_size
    spec = cfg.weight_spec
    wp = _pad_to(_pad_to(w.astype(jnp.float32), 0, bk), 1, bn)
    kp, np_ = wp.shape
    nk, nn = kp // bk, np_ // bn
    wb = wp.reshape(nk, bk, nn, bn)
    absmax = jnp.max(jnp.abs(wb), axis=(1, 3))
    scale = block_scale(absmax, spec)
    wq = quantize(wb, scale[:, None, :, None], spec)
    sig = slice_significances(spec)
    u = jnp.bitwise_and(wq, (1 << spec.total_bits) - 1)
    acc = jnp.zeros(wb.shape, jnp.float32)
    offs = spec.lsb_offsets
    for s, width in enumerate(spec.bits):
        v = jnp.bitwise_and(
            jnp.right_shift(u, offs[s]), (1 << width) - 1
        ).astype(jnp.float32)
        if cfg.cv > 0.0 and key is not None:
            v = noisy_slice_values(
                jax.random.fold_in(key, s), v, width, cfg.hgs, cfg.lgs,
                cfg.cv,
            )
        acc = acc + float(sig[s]) * v
    w_deq = acc * scale[:, None, :, None]
    out_dtype = jnp.bfloat16 if cfg.store_dtype == "bf16" else jnp.float32
    return w_deq.reshape(kp, np_).astype(out_dtype)


def fake_quant_input(x: jax.Array, cfg: DPEConfig) -> jax.Array:
    """Fast-mode input pipeline: per-block quantise + dequantise (the DAC
    is exact for the paper's defaults, and slicing+recombining an ideal
    input is the identity).  x: (M, K) -> (M, Kp) in store_dtype."""
    bk, _ = cfg.array_size
    spec = cfg.input_spec
    out_dtype = jnp.bfloat16 if cfg.store_dtype == "bf16" else jnp.float32
    xp = _pad_to(x.astype(jnp.float32), 1, bk)
    m, kp = xp.shape
    xb = xp.reshape(m, kp // bk, bk)
    absmax = jnp.max(jnp.abs(xb), axis=2)
    sxs = block_scale(absmax, spec)
    xq = quantize(xb, sxs[:, :, None], spec)
    return (
        (xq.astype(jnp.float32) * sxs[:, :, None])
        .astype(out_dtype)
        .reshape(m, kp)
    )


def _circuit_matmul(
    xs: jax.Array,
    sx: jax.Array,
    ws: jax.Array,
    sw: jax.Array,
    cfg: DPEConfig,
) -> jax.Array:
    """Highest-fidelity path: every slice-pair crossbar operation solved
    through the IR-drop circuit model (wire resistance + cross-iteration
    nodal solve) instead of the ideal dot product.  O(iters) costlier —
    for paper-repro experiments and small operators, not the LM hot path.

    Maps slice values to physical conductances/voltages, solves the
    resistive network per K-block, senses bit-line currents, converts
    back to slice units and recombines digitally.
    """
    from .crossbar import solve_crossbar
    from .device import slice_to_conductance

    bk, bn = cfg.array_size
    sxn, m, kp = xs.shape
    swn, _, np_ = ws.shape
    nk, nn = kp // bk, np_ // bn
    sigx = slice_significances(cfg.input_spec)
    sigw = slice_significances(cfg.weight_spec)
    v_read = 0.2  # word-line read voltage full-scale
    # (nk, m, np_) broadcastable per-K-block scale: rows carry sx, columns
    # carry sw repeated over each physical tile's bit-lines.
    kb_scale = (
        sx.T[:, :, None]
        * jnp.repeat(sw, bn, axis=1)[:, None, :]
    )  # (nk, M, Np)
    out = jnp.zeros((m, np_), jnp.float32)
    for i in range(sxn):
        vmax_x = 2.0 ** cfg.input_spec.bits[i] - 1.0
        # all K-blocks at once: (nk, M, bk) word-line voltages
        vin = xs[i].reshape(m, nk, bk).transpose(1, 0, 2) / vmax_x * v_read
        for j in range(swn):
            bits_w = cfg.weight_spec.bits[j]
            dg = (cfg.hgs - cfg.lgs) / (2.0**bits_w - 1.0)
            # one physical (bk x bn) tile per (k-block, n-block): word-line
            # IR-drop must not span across separate arrays.
            g_tiles = slice_to_conductance(
                ws[j]
                .reshape(nk, bk, nn, bn)
                .transpose(0, 2, 1, 3),
                bits_w, cfg.hgs, cfg.lgs,
            )  # (nk, nn, bk, bn)

            def solve_tile(g1, v1):
                return jax.vmap(
                    lambda v: solve_crossbar(g1, v, 2.93, 20).i_out
                )(v1)  # (M, bn)

            # de-looped per-K-block dispatch: vmap over k-blocks, then over
            # the n-block tiles sharing that k-block's word-line drive.
            res = jax.vmap(
                lambda gk, vk: jax.vmap(lambda g1: solve_tile(g1, vk))(gk)
            )(g_tiles, vin)  # (nk, nn, M, bn)
            y = (
                res.transpose(0, 2, 1, 3).reshape(nk, m, np_)
                / v_read * vmax_x
            )
            # invert the conductance offset: I = V·(LGS + v_w·dg)
            col_sum = jnp.sum(
                vin / v_read * vmax_x, axis=2, keepdims=True
            )  # (nk, M, 1)
            y = (y - col_sum * cfg.lgs) / dg
            pair = jnp.sum(y * kb_scale, axis=0)
            out = out + float(sigx[i] * sigw[j]) * pair
    return out


def resolve_backend(cfg: DPEConfig) -> str:
    """Concrete backend for ``cfg`` (resolves ``"auto"``).

    Auto-selection rule: ``auto`` picks ``pallas`` iff the mode is
    ``faithful`` (fast/digital modes never touch the slice-pair kernel)
    and :func:`repro.kernels.ops.kernels_enabled` says the kernels are
    live — real TPU hardware, or a forced interpret override (the CPU-CI
    kernel legs), so CPU CI and TPU runs share ONE selection path.  All
    faithful ADC modes are kernel-eligible: ``dynamic_row`` ranges per
    row over the bit-line axis, which is m-tiling independent, so the
    kernel reproduces the XLA engine's row-independent semantics exactly
    (DESIGN.md §3/§7).
    """
    if cfg.backend != "auto":
        return cfg.backend
    from repro.kernels import ops as _kops

    if cfg.mode == "faithful" and _kops.kernels_enabled():
        return "pallas"
    return "xla"


def _drift_factor(
    cfg: DPEConfig, t_prog, t_now
) -> jax.Array | None:
    """Multiplicative conductance-decay factor for programmed state aged
    from ``t_prog`` to ``t_now``, or ``None`` when drift does not apply
    (no model configured, untimed state, or no clock published).  The
    ``None`` path adds nothing to the traced graph — the bitwise-off
    contract for ``cfg.drift is None`` (DESIGN.md §5)."""
    if cfg.drift is None or t_prog is None:
        return None
    if t_now is None:
        t_now = drift_now()
    if t_now is None:
        return None
    dt = jnp.asarray(t_now, jnp.float32) - jnp.asarray(t_prog, jnp.float32)
    return cfg.drift.factor(dt)


def dpe_matmul_prepared(
    x: jax.Array,
    pw: PreparedWeight,
    n: int,
    cfg: DPEConfig,
    t_now: jax.Array | None = None,
) -> jax.Array:
    """``x @ w`` through an already-programmed weight (any leading dims).

    Drift (when ``cfg.drift`` is set, the state carries ``t_prog`` and a
    device clock is available) decays the stored slice values *before*
    the analog matmul + ADC — slice units are linear in the conductance
    window, so one scalar multiply on the slice stack models every cell
    of every tile aging uniformly, identically on the xla, pallas and
    circuit backends."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    xm = x.reshape(-1, k)
    f = _drift_factor(cfg, pw.t_prog, t_now)
    if f is not None:
        pw = pw._replace(slices=pw.slices * f)
    backend = resolve_backend(cfg)
    if backend == "pallas" and cfg.mode == "faithful":
        # fused kernel: prepare_input (quantise + slice + DAC) runs
        # IN-kernel on the raw activations — the (Sx, M, Kp) slice
        # stack never touches HBM on the serve hot path
        from repro.distributed.sharding import replicated_call
        from repro.kernels import ops as _kops

        y = replicated_call(
            functools.partial(
                _kops.fused_sliced_matmul,
                input_spec=cfg.input_spec, weight_spec=cfg.weight_spec,
                array_size=cfg.array_size, rdac=cfg.rdac, radc=cfg.radc,
                adc_mode=cfg.adc_mode,
            ),
            xm.astype(jnp.float32), pw.slices, pw.scale,
        )
        return y[:, :n].reshape(*lead, n)
    xs, sx = prepare_input(xm, cfg)
    if backend == "circuit":
        y = _circuit_matmul(xs, sx, pw.slices, pw.scale, cfg)
    elif cfg.mode == "faithful":
        y = _faithful_matmul(xs, sx, pw.slices, pw.scale, cfg)
    else:
        y = _fast_matmul(xs, sx, pw.slices, pw.scale, cfg)
    return y[:, :n].reshape(*lead, n)


def dpe_matmul_folded(
    x: jax.Array,
    fw: FoldedWeight,
    n: int,
    cfg: DPEConfig,
    t_now: jax.Array | None = None,
) -> jax.Array:
    """Fast-mode ``x @ w`` through an already-folded noisy weight.

    Drift commutes exactly through the digital fold (the fold is linear
    in the slice values), so decaying ``w_eff`` equals decaying every
    slice — applied in ``store_dtype`` so the drift-at-0 identity stays
    bitwise."""
    lead = x.shape[:-1]
    xm = x.reshape(-1, x.shape[-1])
    f = _drift_factor(cfg, fw.t_prog, t_now)
    if f is not None:
        fw = fw._replace(w_eff=fw.w_eff * f.astype(fw.w_eff.dtype))
    x_deq = fake_quant_input(xm, cfg).astype(fw.w_eff.dtype)
    y = (x_deq @ fw.w_eff)[:, :n]
    return y.reshape(*lead, n).astype(jnp.float32)


def program_weight(
    w: jax.Array,
    cfg: DPEConfig | None,
    key: jax.Array | None = None,
    t_prog: jax.Array | None = None,
) -> PreparedWeight | FoldedWeight | None:
    """Program one weight matrix for ``cfg``'s mode (the weight-stationary
    ``update_weight()`` artifact, DESIGN.md §5).

    Returns the per-layer programmed state a serving deployment keeps
    resident: :class:`PreparedWeight` (faithful / circuit — slices +
    block scales), :class:`FoldedWeight` (fast — store_dtype-compressed
    effective weight), or ``None`` for digital layers.  ``t_prog`` stamps
    the generation's device-clock programming time (drift reference);
    ``None`` leaves the state untimed (drift never applies to it).

    Determinism contract: programming is a pure function of
    ``(w, cfg, key)`` — the same key yields bit-identical state, which is
    what lets a weight-stationary deployment re-program only when the key
    changes (DESIGN.md §5).  ``t_prog`` stamps metadata only; it never
    perturbs the programmed values.
    """
    if cfg is None or cfg.mode == "digital":
        return None
    if t_prog is not None:
        t_prog = jnp.asarray(t_prog, jnp.float32)
    if cfg.mode == "fast":
        return FoldedWeight(fold_weight_noisy(w, cfg, key), t_prog=t_prog)
    return prepare_weight(w, cfg, key)._replace(t_prog=t_prog)


def dpe_apply(
    x: jax.Array,
    prog: PreparedWeight | FoldedWeight,
    n: int,
    cfg: DPEConfig,
    t_now: jax.Array | None = None,
) -> jax.Array:
    """``x @ w`` through programmed state from :func:`program_weight` —
    the decode-loop hot path pays only ``prepare_input`` + the GEMM.
    When ``t_now`` is None the device clock published by
    :func:`repro.core.drift.drift_clock` (if any) drives drift."""
    if isinstance(prog, FoldedWeight):
        return dpe_matmul_folded(x, prog, n, cfg, t_now)
    return dpe_matmul_prepared(x, prog, n, cfg, t_now)


def dpe_matmul(
    x: jax.Array,
    w: jax.Array,
    cfg: DPEConfig,
    key: jax.Array | None = None,
) -> jax.Array:
    """End-to-end simulated ``x @ w`` (programs the weight on the fly)."""
    if cfg.mode == "digital":
        return (
            x.astype(jnp.float32) @ w.astype(jnp.float32)
        )
    return dpe_apply(x, program_weight(w, cfg, key), w.shape[1], cfg)


def relative_error(sim: jax.Array, ideal: jax.Array) -> jax.Array:
    """Paper's RE metric: ||sim - ideal||_2 / ||ideal||_2."""
    return jnp.linalg.norm(sim - ideal) / jnp.maximum(
        jnp.linalg.norm(ideal), 1e-30
    )
