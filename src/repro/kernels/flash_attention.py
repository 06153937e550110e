"""Pallas TPU flash-attention kernel (beyond-paper §Perf deliverable).

The roofline analysis identified attention score traffic as the dominant
memory term for every attention architecture: the XLA lowering round-
trips (bq, bk) probability blocks through HBM.  This kernel keeps the
online-softmax state — running max ``m``, normaliser ``l`` and the
(bq, dh) accumulator — in VMEM scratch across the KV grid dimension, so
score blocks never leave VMEM.

Grid: (B*H, Sq/bq, Skv/bk), KV innermost ("arbitrary"); the output tile
is written once on the last KV step.  Causal masking from program ids.
Validated in interpret mode against ``attention_dense``
(tests/test_flash_kernel.py); deployment uses it through
``ops.flash_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG = -1e30


def _kernel(
    q_ref,  # (1, bq, dh)
    k_ref,  # (1, bk, dh)
    v_ref,  # (1, bk, dh)
    o_ref,  # (1, bq, dh)
    m_scr,  # (bq,) f32 running max
    l_scr,  # (bq,) f32 normaliser
    acc_scr,  # (bq, dh) f32 accumulator
    *,
    scale: float,
    causal: bool,
    window: int,
    bq: int,
    bk: int,
    nk: int,
    skv: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (bq, bk)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < skv
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, _NEG)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # (BH, Sq, dh)
    k: jax.Array,  # (BH, Skv, dh)
    v: jax.Array,  # (BH, Skv, dh)
    *,
    causal: bool = True,
    window: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, dh = q.shape
    _, skv, _ = k.shape
    scale = dh**-0.5
    pad_q = (-sq) % bq
    pad_k = (-skv) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    nq = q.shape[1] // bq
    nk = k.shape[1] // bk
    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        window=window,
        bq=bq,
        bk=bk,
        nk=nk,
        skv=skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
