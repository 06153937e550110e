"""Attention: GQA/MQA/MHA + RoPE + qk-norm + sliding window + KV cache.

Execution paths:

* ``attention_dense``   — full score matrix; short sequences.
* ``attention_chunked`` — online-softmax over KV chunks (flash-style in
  pure JAX); memory-bounded for 32k prefill.  Causality is enforced by
  masking; chunks entirely outside a sliding window contribute zero and
  the optimized variant skips them structurally (see §Perf).
* ``attention_decode``  — single new token vs. a (possibly length-
  sharded) KV cache with numerically-stable masked softmax; this is the
  flash-decode path used by decode_32k / long_500k where the KV sequence
  is sharded over the ``model`` mesh axis.
* paged variants (DESIGN.md §7) — ``decode_attention_block`` with
  ``block_tables``, ``chunk_attention_block`` and
  ``verify_attention_block`` address a *block pool* shared by every
  serving slot through per-slot block tables instead of a dense
  per-slot cache row.  They take a layer segment's whole stacked arena
  ``(L, n_blocks, block_size, KV*hd)`` plus the layer index, write
  their rows into it with one scatter at ``(layer, block, offset)`` and
  return it: the layer scan carries the arena and updates it in place.
  Two implementations, selected by
  ``repro.kernels.ops.resolve_attention_backend()``:

  - ``xla`` (the oracle): blocks are gathered into logical order before
    the attention math, so the scores/softmax see exactly the values a
    dense cache would hold — paged layouts are bitwise-invisible to the
    numerics.  Cost: the gather materialises the FULL ``(B, nb, bs, KV,
    hd)`` view, O(max_len) per step however short the prefix.
  - ``pallas`` (``repro.kernels.paged_attention``): the kernel walks the
    block table in-kernel and reads only the mapped prefix blocks,
    O(prefix) per step; bitwise equal to the gather path in interpret
    mode (tests/test_paged_attention.py).  Under a mesh the kernel runs
    replicated on every device (``replicated_call``: Mosaic kernels
    cannot be partitioned automatically), so it does not carry the
    flash-decode sharding constraints — length-sharded TPU meshes
    should pin the ``xla`` backend.

All projections route through ``dense`` (mem-policy aware).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import constrain, replicated_call
from repro.kernels import ops as _kops
from repro.kernels.paged_attention import (
    paged_chunk_attention,
    paged_decode_attention,
)

from .common import apply_rope, dense, make_dense_params, pget, rms_norm, rope

__all__ = [
    "init_attn_params",
    "attention_block",
    "decode_attention_block",
    "chunk_attention_block",
    "verify_attention_block",
    "init_kv_cache",
    "TRASH_BLOCK",
]

# Physical block 0 of every paged pool is reserved as the *trash block*:
# unallocated block-table entries point at it, padded prefill tokens and
# inactive decode lanes write into it, and every read of it is masked to
# -inf before the softmax (exp underflows to exactly 0.0) — so its
# contents, although junk, can never reach a logit.
TRASH_BLOCK = 0

_NEG = -1e30


def init_attn_params(key, cfg, dtype=jnp.float32):
    d, hd = cfg.d_model, cfg.head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 6)
    p = {
        "q_proj": make_dense_params(ks[0], d, nh * hd, cfg.qkv_bias, dtype),
        "k_proj": make_dense_params(ks[1], d, nkv * hd, cfg.qkv_bias, dtype),
        "v_proj": make_dense_params(ks[2], d, nkv * hd, cfg.qkv_bias, dtype),
        "o_proj": make_dense_params(ks[3], nh * hd, d, False, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), dtype)}
        p["k_norm"] = {"scale": jnp.ones((hd,), dtype)}
    return p


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def _precision(*dtypes):
    """Full f32 contraction for f32 operands: a TPU's default f32 matmul
    is one bf16 pass (a CPU ignores the flag).  bf16 is exact in one."""
    f32 = jnp.result_type(*dtypes) == jnp.float32
    return lax.Precision.HIGHEST if f32 else None


def _gqa_scores(q, k):
    """q: (B,Sq,H,dh), k: (B,Skv,KV,dh) -> scores (B,KV,H/KV,Sq,Skv)."""
    b, sq, h, dh = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, sq, kv, h // kv, dh)
    return jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k, precision=_precision(q.dtype, k.dtype)
    )


def _gqa_out(probs, v):
    """probs: (B,KV,G,Sq,Skv), v: (B,Skv,KV,dh) -> (B,Sq,KV*G,dh)."""
    b, kv, g, sq, skv = probs.shape
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", probs, v,
        precision=_precision(probs.dtype, v.dtype),
    )
    return out.reshape(b, sq, kv * g, out.shape[-1])


def _causal_mask(sq, skv, q_off, window):
    qi = q_off + jnp.arange(sq)[:, None]
    ki = jnp.arange(skv)[None, :]
    m = ki <= qi
    if window > 0:
        m &= ki > qi - window
    return m


def attention_dense(q, k, v, *, q_off=0, window=0, causal=True):
    scale = q.shape[-1] ** -0.5
    s = _gqa_scores(q, k).astype(jnp.float32) * scale
    if causal:
        mask = _causal_mask(q.shape[1], k.shape[1], q_off, window)
        s = jnp.where(mask[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return _gqa_out(p.astype(v.dtype), v)


def attention_chunked(
    q, k, v, *, window=0, causal=True, q_chunk=0, kv_chunk=512,
    schedule="masked",
):
    """Online-softmax attention, scanning KV chunks per Q chunk.

    Memory per step is O(q_chunk * kv_chunk) instead of O(S^2).
    ``q_chunk=0`` adapts the chunk so there are at most 32 q-chunks,
    keeping the triangular causal schedule (below) applicable at 32k+.
    """
    b, sq, h, dh = q.shape
    if q_chunk == 0:
        q_chunk = max(512, -(-sq // 32))
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = dh**-0.5
    nq = -(-sq // q_chunk)
    nk = -(-skv // kv_chunk)
    qp = jnp.pad(q, ((0, 0), (0, nq * q_chunk - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * kv_chunk - skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * kv_chunk - skv), (0, 0), (0, 0)))
    kb = kp.reshape(b, nk, kv_chunk, kvh, dh)
    vb = vp.reshape(b, nk, kv_chunk, kvh, dh)

    def one_q_chunk(qi, qc, kv_limit=None):
        """qc: (B, q_chunk, H, dh) -> attended output chunk.

        ``kv_limit``: static number of kv chunks to scan (triangular
        causal schedule); None scans all with masking."""
        qg = qc.reshape(b, q_chunk, kvh, g, dh)

        def kv_step(carry, inp):
            m_run, l_run, acc = carry
            ki, kc, vc = inp
            s = (
                jnp.einsum("bqkgd,bskd->bkgqs", qg, kc).astype(jnp.float32)
                * scale
            )
            q_pos = qi * q_chunk + jnp.arange(q_chunk)[:, None]
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)[None, :]
            mask = k_pos < skv
            if causal:
                mask &= k_pos <= q_pos
            if window > 0:
                mask &= k_pos > q_pos - window
            s = jnp.where(mask[None, None, None], s, _NEG)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_run * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32)
            )
            return (m_new, l_new, acc), None

        init = (
            jnp.full((b, kvh, g, q_chunk), _NEG, jnp.float32),
            jnp.zeros((b, kvh, g, q_chunk), jnp.float32),
            jnp.zeros((b, kvh, g, q_chunk, dh), jnp.float32),
        )
        lim = nk if kv_limit is None else min(kv_limit, nk)
        (m_run, l_run, acc), _ = lax.scan(
            kv_step,
            init,
            (
                jnp.arange(lim),
                kb.swapaxes(0, 1)[:lim],
                vb.swapaxes(0, 1)[:lim],
            ),
        )
        out = acc / jnp.maximum(l_run, 1e-30)[..., None]
        # (b, kvh, g, q_chunk, dh) -> (b, q_chunk, h, dh)
        return out.transpose(0, 3, 1, 2, 4).reshape(b, q_chunk, h, dh)

    qb = qp.reshape(b, nq, q_chunk, h, dh).swapaxes(0, 1)
    # checkpoint per q-chunk: backward recomputes the kv scan for one
    # chunk at a time instead of saving all (q x kv) probability blocks
    ckpt = lambda f: jax.checkpoint(
        f, policy=jax.checkpoint_policies.nothing_saveable
    )
    if schedule == "tri" and causal and sq == skv and nq <= 32:
        # statically triangular schedule: q-chunk i only scans kv chunks
        # 0..i — halves causal attention compute/traffic vs the masked
        # full scan while staying reverse-differentiable (§Perf).
        import functools

        outs = [
            ckpt(functools.partial(one_q_chunk, kv_limit=i + 1))(
                jnp.int32(i), qb[i]
            )
            for i in range(nq)
        ]
        out = jnp.stack(outs, axis=0)
    else:
        f = ckpt(one_q_chunk)
        out = lax.map(lambda t: f(t[0], t[1]), (jnp.arange(nq), qb))
    out = out.swapaxes(0, 1).reshape(b, nq * q_chunk, h, dh)[:, :sq]
    return out.astype(v.dtype)


def attention_decode(q1, k_cache, v_cache, pos, *, window=0):
    """One-token attention against the cache.

    q1: (B, H, dh); caches: (B, S_max, KV, dh); pos: (B,) current length
    (the new token's index).  Valid keys are indices <= pos (cache already
    updated at pos).  KV-length sharding over the ``model`` axis is
    expressed with logical constraints; XLA partitions the reductions
    (max/sum) into the flash-decode combine.
    """
    b, h, dh = q1.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    scale = dh**-0.5
    # flash-decode shards the KV *length*; heads stay local (sharding
    # both would duplicate the model axis in one spec)
    k_cache = constrain(k_cache, "batch", "kv_seq", None, "head_dim")
    v_cache = constrain(v_cache, "batch", "kv_seq", None, "head_dim")
    qg = q1.reshape(b, kvh, g, dh)
    # keep operands in the cache dtype and accumulate in f32: an f32
    # operand here would make XLA hoist an f32 COPY of the whole cache
    # out of the layer loop (2x decode HBM — §Perf, qwen1.5 decode cell)
    s = (
        jnp.einsum(
            "bkgd,bskd->bkgs",
            qg.astype(k_cache.dtype),
            k_cache,
            precision=_precision(k_cache.dtype),
            preferred_element_type=jnp.float32,
        )
        * scale
    )
    ki = jnp.arange(k_cache.shape[1])[None, :]
    mask = ki <= pos[:, None]
    if window > 0:
        mask &= ki > pos[:, None] - window
    s = jnp.where(mask[:, None, None, :], s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bkgs,bskd->bkgd", (p / l).astype(v_cache.dtype), v_cache,
        precision=_precision(v_cache.dtype),
    )
    return out.reshape(b, h, dh)


def init_kv_cache(cfg, batch, max_len, dtype=jnp.bfloat16, layers=None):
    n = layers if layers is not None else cfg.n_layers
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    shape = (n, batch, max_len, kvh, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# paged KV pool helpers (DESIGN.md §7)
# ---------------------------------------------------------------------------


def _paged_gather(pool, layer, block_tables, head_dim):
    """Materialise a slot-major logical view of one layer of the arena.

    pool: (L, n_blocks, bs, KV*hd) stacked arena; layer: its index;
    block_tables: (B, nb) physical block ids → (B, nb*bs, KV, hd).  The
    gather is a pure data movement: the returned buffer holds, at
    logical position ``p`` of slot ``b``, exactly the bytes a dense
    ``(B, max_len)`` arena would hold there (unallocated tail blocks
    alias the trash block; every read of those positions is masked
    before the softmax), so downstream attention is bitwise identical
    to the dense layout.
    """
    b, nb = block_tables.shape
    g = pool[layer, block_tables]  # (B, nb, bs, KV*hd)
    return g.reshape(b, nb * pool.shape[2], -1, head_dim)


def _paged_token_write(pool, layer, block_tables, pos, val, active):
    """Scatter one token's K or V into layer ``layer`` of the arena at
    logical ``pos``.

    val: (B, KV, hd) already in pool dtype.  Rows with ``active`` False
    (idle / still-prefilling serving lanes) are routed to the trash
    block instead — an inactive lane can never mutate live KV, even when
    its stale block table aliases blocks that were freed and re-allocated
    to another request (the no-leak half of the paged contract)."""
    bsz = pool.shape[2]
    blk = jnp.take_along_axis(
        block_tables, (pos // bsz)[:, None], axis=1
    )[:, 0]
    if active is not None:
        blk = jnp.where(active, blk, TRASH_BLOCK)
    return pool.at[layer, blk, pos % bsz].set(val.reshape(val.shape[0], -1))


def attention_verify(q, k_cache, v_cache, pos, *, window=0):
    """Multi-token decode attention for speculative verification.

    q: (B, C, H, dh) — per slot, C consecutive query positions starting
    at ``pos[b]``; caches: (B, S_max, KV, dh) in logical order (already
    gathered from the paged pool, the chunk's C new entries written).
    Position ``c`` of slot ``b`` attends under the ``ki <= pos[b] + c``
    mask, so keys written for LATER chunk positions — and any stale
    junk a rejected earlier draft left beyond the mask — contribute
    exactly 0.0 after ``exp`` (the trash-block argument): row
    ``(b, c)`` is bitwise the output :func:`attention_decode` computes
    for a single query at ``pos[b] + c`` over the same valid prefix.
    The C-axis rides along the einsum batch dims; the per-row reduction
    order over ``dh`` / ``S`` is unchanged.
    """
    b, c, h, dh = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    scale = dh**-0.5
    k_cache = constrain(k_cache, "batch", "kv_seq", None, "head_dim")
    v_cache = constrain(v_cache, "batch", "kv_seq", None, "head_dim")
    qg = q.reshape(b, c, kvh, g, dh)
    s = (
        jnp.einsum(
            "bckgd,bskd->bckgs",
            qg.astype(k_cache.dtype),
            k_cache,
            precision=_precision(k_cache.dtype),
            preferred_element_type=jnp.float32,
        )
        * scale
    )
    ki = jnp.arange(k_cache.shape[1])[None, None, :]
    qpos = (pos[:, None] + jnp.arange(c)[None, :])[:, :, None]
    mask = ki <= qpos
    if window > 0:
        mask &= ki > qpos - window
    s = jnp.where(mask[:, :, None, None, :], s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bckgs,bskd->bckgd", (p / l).astype(v_cache.dtype), v_cache,
        precision=_precision(v_cache.dtype),
    )
    return out.reshape(b, c, h, dh)


def _paged_multi_write(pool, layer, block_tables, pos, vals, active):
    """Scatter C consecutive tokens' K or V per slot into layer
    ``layer`` of the arena.

    vals: (B, C, KV, hd) in pool dtype; token ``c`` of slot ``b`` lands
    at logical position ``pos[b] + c``.  Rows with ``active`` False and
    positions past the slot's table capacity route to the trash block;
    positions inside capacity but in never-allocated table entries hit
    the trash block naturally (unallocated entries point at it).  A
    speculative chunk therefore only ever writes blocks the slot
    already owns — and only at positions >= ``pos`` (its own current
    frontier), so no live KV is overwritten."""
    b, c = vals.shape[0], vals.shape[1]
    bsz = pool.shape[2]
    nb = block_tables.shape[1]
    lp = pos[:, None] + jnp.arange(c)[None, :]  # (B, C) logical positions
    blk = jnp.take_along_axis(
        block_tables, jnp.clip(lp // bsz, 0, nb - 1), axis=1
    )
    ok = lp < nb * bsz
    if active is not None:
        ok &= active[:, None]
    blk = jnp.where(ok, blk, TRASH_BLOCK)
    return pool.at[layer, blk, lp % bsz].set(vals.reshape(b, c, -1))


def attention_block(
    p,
    x,
    cfg,
    *,
    policy,
    rng,
    positions,
    name,
    kv_in=None,
    dense_threshold=1024,
    attn_schedule="masked",
    prepared=None,
):
    """Full attention block on a sequence (train / prefill).

    Returns (output, (k, v)) so callers can build the serving cache.
    ``kv_in``: (k, v) for cross-attention (whisper decoder).
    ``prepared``: programmed state mirroring ``p`` (q_proj/k_proj/...).
    """
    b, s, d = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(p["q_proj"], x, name=f"{name}.q", policy=policy, rng=rng,
              prepared=pget(prepared, "q_proj"))
    q = _split_heads(q, nh, hd)
    if kv_in is None:
        k = dense(p["k_proj"], x, name=f"{name}.k", policy=policy, rng=rng,
                  prepared=pget(prepared, "k_proj"))
        v = dense(p["v_proj"], x, name=f"{name}.v", policy=policy, rng=rng,
                  prepared=pget(prepared, "v_proj"))
        k = _split_heads(k, nkv, hd)
        v = _split_heads(v, nkv, hd)
    else:
        k, v = kv_in
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"]["scale"])
        if kv_in is None:
            k = rms_norm(k, p["k_norm"]["scale"])
    if kv_in is None and cfg.rope_theta > 0:
        cos, sin = rope(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    causal = kv_in is None and not (cfg.family == "encdec" and "enc" in name)
    if max(s, k.shape[1]) <= dense_threshold:
        out = attention_dense(q, k, v, window=cfg.swa_window, causal=causal)
    else:
        # "tri" (forward-only paths, e.g. prefill): statically triangular
        # causal schedule, ~2x less attention work; "masked" for train —
        # the unrolled schedule's backward raises peak memory (§Perf)
        out = attention_chunked(
            q, k, v, window=cfg.swa_window, causal=causal,
            schedule=attn_schedule,
        )
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    out = out.reshape(b, s, nh * hd)
    y = dense(p["o_proj"], out, name=f"{name}.o", policy=policy, rng=rng,
              prepared=pget(prepared, "o_proj"))
    return y, (k, v)


def decode_attention_block(
    p, x1, cfg, *, policy, rng, cache_k, cache_v, pos, name, cross=False,
    prepared=None, active=None, block_tables=None, layer=None,
):
    """One-token attention block against the cache (dense or paged).

    x1: (B, d) the current token's activations; pos: (B,) index of the
    new token.  Two cache layouts:

    * dense (``block_tables=None``) — cache_k/v: (B, S, KV, dh) per-slot
      rows; returns (y, new_cache_k, new_cache_v).
    * paged — cache_k/v are a segment's stacked block POOL
      ``(L, n_blocks, bs, KV*dh)``, ``layer`` this layer's index in it,
      and ``block_tables`` (B, nb) maps each slot's logical blocks to
      physical ones.  The layer's pool is read in logical order by the
      attention math, so logits are bitwise identical to the dense
      layout for any block placement; the returned pools are the whole
      arena with this layer's row written.

    ``active``: optional (B,) bool — rows where it is False must not
    mutate live KV: on the dense path they re-write their OLD cache
    value at ``pos`` (a per-row no-op); on the paged path their write is
    routed to the trash block (their stale block table may alias blocks
    since re-allocated to another request).  The caller also freezes the
    row's ``pos``.
    """
    b, d = x1.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(p["q_proj"], x1, name=f"{name}.q", policy=policy, rng=rng,
              prepared=pget(prepared, "q_proj"))
    q = q.reshape(b, nh, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"]["scale"])
    if not cross:
        k1 = dense(p["k_proj"], x1, name=f"{name}.k", policy=policy, rng=rng,
                   prepared=pget(prepared, "k_proj"))
        v1 = dense(p["v_proj"], x1, name=f"{name}.v", policy=policy, rng=rng,
                   prepared=pget(prepared, "v_proj"))
        k1 = k1.reshape(b, nkv, hd)
        v1 = v1.reshape(b, nkv, hd)
        if cfg.qk_norm:
            k1 = rms_norm(k1, p["k_norm"]["scale"])
        if cfg.rope_theta > 0:
            cos, sin = rope(pos, hd, cfg.rope_theta)  # (B, half)
            q = apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
            k1 = apply_rope(k1[:, None], cos[:, None], sin[:, None])[:, 0]
        k1c = k1.astype(cache_k.dtype)
        v1c = v1.astype(cache_v.dtype)
        if block_tables is not None:
            cache_k = _paged_token_write(
                cache_k, layer, block_tables, pos, k1c, active
            )
            cache_v = _paged_token_write(
                cache_v, layer, block_tables, pos, v1c, active
            )
        else:
            if active is not None:
                # inactive slots re-write the value already stored at pos
                # — the update is a per-row no-op, the arena stays intact
                take = jax.vmap(
                    lambda c, i: lax.dynamic_slice(
                        c, (i, 0, 0), (1,) + c.shape[1:]
                    )[0]
                )
                sel = active[:, None, None]
                k1c = jnp.where(sel, k1c, take(cache_k, pos))
                v1c = jnp.where(sel, v1c, take(cache_v, pos))
            cache_k = jax.vmap(
                lambda c, u, i: lax.dynamic_update_slice(c, u[None], (i, 0, 0))
            )(cache_k, k1c, pos)
            cache_v = jax.vmap(
                lambda c, u, i: lax.dynamic_update_slice(c, u[None], (i, 0, 0))
            )(cache_v, v1c, pos)
    window = cfg.swa_window if not cross else 0
    if (
        block_tables is not None
        and _kops.resolve_attention_backend() == "pallas"
    ):
        # in-kernel block walk: only the mapped prefix blocks are read
        out = replicated_call(
            functools.partial(
                paged_decode_attention, window=window,
                interpret=_kops.kernel_interpret(),
            ),
            q, cache_k, cache_v, layer, block_tables, pos,
        )
    else:
        if block_tables is not None:
            att_k = _paged_gather(cache_k, layer, block_tables, hd)
            att_v = _paged_gather(cache_v, layer, block_tables, hd)
        else:
            att_k, att_v = cache_k, cache_v
        out = attention_decode(q, att_k, att_v, pos, window=window)
    y = dense(
        p["o_proj"], out.reshape(b, nh * hd), name=f"{name}.o",
        policy=policy, rng=rng, prepared=pget(prepared, "o_proj"),
    )
    return y, cache_k, cache_v


def chunk_attention_block(
    p, x, cfg, *, policy, rng, pool_k, pool_v, layer, bt_row, start,
    n_valid, positions, name, prepared=None,
):
    """Attention block for one CHUNK of a prompt against the paged pool
    (chunked prefill, serve/batching.py, DESIGN.md §7).

    x: (1, C, d) chunk activations, right-padded past ``n_valid``;
    ``start``: logical position of the chunk's first token; ``bt_row``:
    (nb,) this slot's block table; ``pool_k``/``pool_v``: the segment's
    stacked arena, ``layer`` this layer's index in it.  The chunk's K/V
    are written into the slot's blocks first (pad tokens route to the
    trash block), then the queries attend over the GATHERED logical
    view — prefix written by earlier chunks plus this chunk — under the
    causal ``ki <= qi`` mask.

    Numerics contract: per-token math is identical to single-shot
    prefill — same projections, same RoPE positions, same masked-softmax
    attention over the same values in the same logical order — so the
    fast path is bitwise chunk-size-invariant (masked tail keys
    contribute exactly 0.0 after ``exp``; pad-token activations are junk
    but causally invisible to real tokens).  Returns
    (y, new_pool_k, new_pool_v).
    """
    b, c, d = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(p["q_proj"], x, name=f"{name}.q", policy=policy, rng=rng,
              prepared=pget(prepared, "q_proj"))
    k = dense(p["k_proj"], x, name=f"{name}.k", policy=policy, rng=rng,
              prepared=pget(prepared, "k_proj"))
    v = dense(p["v_proj"], x, name=f"{name}.v", policy=policy, rng=rng,
              prepared=pget(prepared, "v_proj"))
    q = _split_heads(q, nh, hd)
    k = _split_heads(k, nkv, hd)
    v = _split_heads(v, nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"]["scale"])
        k = rms_norm(k, p["k_norm"]["scale"])
    if cfg.rope_theta > 0:
        cos, sin = rope(positions, hd, cfg.rope_theta)  # (1, C, half)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    # scatter the chunk's K/V into the slot's blocks; pad tokens (their
    # logical position is >= start + n_valid) go to the trash block
    bsz = pool_k.shape[2]
    lp = start + jnp.arange(c)
    valid = jnp.arange(c) < n_valid
    blk = jnp.where(valid, bt_row[jnp.clip(lp // bsz, 0, bt_row.shape[0] - 1)],
                    TRASH_BLOCK)
    off = lp % bsz
    pool_k = pool_k.at[layer, blk, off].set(
        k.astype(pool_k.dtype)[0].reshape(c, -1))
    pool_v = pool_v.at[layer, blk, off].set(
        v.astype(pool_v.dtype)[0].reshape(c, -1))
    # attend over the gathered logical view (prefix + this chunk); keys
    # past each query's position — including every pad position — are
    # masked to -inf by the causal mask inside attention_dense
    if _kops.resolve_attention_backend() == "pallas":
        # in-kernel block walk: chunk cost is O(prefix), not O(max_len).
        # Pad queries (>= n_valid) see a zero tail instead of the stale
        # gathered junk — their outputs are discarded by the caller, the
        # valid rows are bitwise equal (tests/test_paged_attention.py).
        out = replicated_call(
            functools.partial(
                paged_chunk_attention, window=cfg.swa_window,
                interpret=_kops.kernel_interpret(),
            ),
            q, pool_k, pool_v, layer, bt_row, start, n_valid,
        )
    else:
        g_k = _paged_gather(pool_k, layer, bt_row[None], hd)
        g_v = _paged_gather(pool_v, layer, bt_row[None], hd)
        out = attention_dense(q, g_k, g_v, q_off=start, window=cfg.swa_window)
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    y = dense(
        p["o_proj"], out.reshape(b, c, nh * hd), name=f"{name}.o",
        policy=policy, rng=rng, prepared=pget(prepared, "o_proj"),
    )
    return y, pool_k, pool_v


def verify_attention_block(
    p, x, cfg, *, policy, rng, pool_k, pool_v, layer, block_tables, pos,
    name, prepared=None, active=None,
):
    """Attention block for one SPECULATIVE VERIFY chunk against the
    paged pool (serve/batching.py, DESIGN.md §7).

    x: (B, C, d) — per slot, the activations of the last emitted token
    followed by C-1 draft proposals, at logical positions
    ``pos[b] .. pos[b]+C-1``; ``pool_k``/``pool_v``: the segment's
    stacked arena, ``layer`` this layer's index in it.  All C
    positions' K/V are written into the slot's already-allocated blocks
    FIRST (inactive lanes and out-of-capacity positions route to the
    trash block), then each
    position attends over the gathered logical view under the
    ``ki <= pos + c`` causal mask.

    Numerics contract: per-position math is identical to
    :func:`decode_attention_block` — same projections (row/batch-shape
    invariant), same RoPE positions, same masked-softmax reduction
    order — so row ``(b, c)`` is BITWISE the value a sequential
    single-token decode at ``pos + c`` computes over the same accepted
    prefix; keys at later chunk positions contribute exactly 0.0 after
    ``exp``.  Rejected draft tails stay dead by this same length mask
    until the next round overwrites them (``pos`` only ever rewinds to
    an accepted frontier).  This path has no Pallas kernel yet: it
    always takes the XLA gather, which the decode kernels are
    themselves bitwise against (tests/test_paged_attention.py), so
    backend flips stay invisible.  Returns (y, new_pool_k, new_pool_v).
    """
    b, c, d = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(p["q_proj"], x, name=f"{name}.q", policy=policy, rng=rng,
              prepared=pget(prepared, "q_proj"))
    k = dense(p["k_proj"], x, name=f"{name}.k", policy=policy, rng=rng,
              prepared=pget(prepared, "k_proj"))
    v = dense(p["v_proj"], x, name=f"{name}.v", policy=policy, rng=rng,
              prepared=pget(prepared, "v_proj"))
    q = _split_heads(q, nh, hd)
    k = _split_heads(k, nkv, hd)
    v = _split_heads(v, nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"]["scale"])
        k = rms_norm(k, p["k_norm"]["scale"])
    if cfg.rope_theta > 0:
        positions = pos[:, None] + jnp.arange(c)[None, :]  # (B, C)
        cos, sin = rope(positions, hd, cfg.rope_theta)  # (B, C, half)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    pool_k = _paged_multi_write(
        pool_k, layer, block_tables, pos, k.astype(pool_k.dtype), active
    )
    pool_v = _paged_multi_write(
        pool_v, layer, block_tables, pos, v.astype(pool_v.dtype), active
    )
    att_k = _paged_gather(pool_k, layer, block_tables, hd)
    att_v = _paged_gather(pool_v, layer, block_tables, hd)
    out = attention_verify(q, att_k, att_v, pos, window=cfg.swa_window)
    y = dense(
        p["o_proj"], out.reshape(b, c, nh * hd), name=f"{name}.o",
        policy=policy, rng=rng, prepared=pget(prepared, "o_proj"),
    )
    return y, pool_k, pool_v
