"""Serving: prefill (single-shot or chunked) and decode steps.

``prefill_32k`` lowers ``prefill_step``; ``decode_32k`` / ``long_500k``
lower ``decode_step`` (one new token against a KV cache of seq_len, the
cache's KV-length axis sharded over the ``model`` mesh axis =
flash-decode); ``make_chunk_prefill`` is the continuous-batching
engine's prefill — one fixed-size prompt chunk at a time against the
paged arena (DESIGN.md §7).  Programming noise is *static* across
decode steps (devices are programmed once for inference) — keys derive
from layer names only.

Weight-stationary serving (DESIGN.md §5): ``greedy_generate`` programs
the model ONCE via :func:`repro.models.program_params` and passes the
programmed state to every prefill/decode call, so the per-token cost is
``prepare_input`` + the GEMM — the weight quantise/slice/noise pipeline
drops out of the decode loop entirely.  Both step functions also accept
``programmed`` directly for callers that manage the lifecycle
themselves (launch.dryrun, sharded deployments).

Mesh-aware serving (DESIGN.md §6): pass ``mesh`` to ``greedy_generate``
and the programmed state is materialised SHARDED
(:func:`repro.distributed.sharding.programmed_sharding_rules` — each
leaf inherits its dense weight's partitioning), so per-device programmed
HBM shrinks with the model axis; the jitted prefill/decode steps follow
the committed input shardings, and KV-cache donation is preserved.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.core.drift import drift_clock
from repro.core.layers import MemPolicy
from repro.distributed.sharding import rules_context
from repro.models import decode_step as model_decode
from repro.models import decode_verify_step as model_verify
from repro.models import forward, program_params
from repro.models.config import ArchConfig
from repro.models.model import (
    DIGITAL,
    init_cache,
    prefill_chunk_step,
    segments,
)

from .sampling import request_keys, sample_row

__all__ = [
    "make_prefill_step",
    "make_slot_prefill",
    "make_chunk_prefill",
    "make_decode_step",
    "make_verify_step",
    "greedy_generate",
]


def _head_logits(params, hidden, *, policy, rng, programmed):
    """Route hidden states through the (possibly analog) lm_head — the
    single head semantics every prefill/decode path shares (bitwise the
    same head math for the first token as for every decoded token)."""
    from repro.models.common import dense, pget

    return dense(
        params["lm_head"], hidden, name="lm_head", policy=policy,
        rng=rng, prepared=pget(programmed, "lm_head"),
    ).astype(jnp.float32)


def _cache_from_prefill(cfg, states, batch, s_prefill, max_len, dtype):
    """Pad per-layer prefill KV to max_len and assemble the cache."""
    cache = {
        "pos": jnp.full((batch,), s_prefill, jnp.int32),
        "blocks": {},
    }
    for si, (start, steps, tmpl) in enumerate(segments(cfg)):
        st = states[f"seg{si}"]

        def pad_kv(path, x):
            # attention K/V leaves ("k"/"v"): (steps, B, S, KV, hd) ->
            # pad the length axis to max_len; SSM states pass through.
            key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if key in ("k", "v") and x.ndim == 5:
                pad = max_len - x.shape[2]
                return jnp.pad(
                    x.astype(dtype),
                    ((0, 0),) * 2 + ((0, pad),) + ((0, 0),) * 2,
                )
            return x

        cache["blocks"][f"seg{si}"] = jax.tree_util.tree_map_with_path(
            pad_kv, st
        )
    if cfg.encoder is not None and "cross_kv" in states:
        cache["cross_kv"] = jax.tree.map(
            lambda x: x.astype(dtype), states["cross_kv"]
        )
    return cache


def make_prefill_step(
    cfg: ArchConfig,
    policy: MemPolicy | None = None,
    *,
    max_len: int | None = None,
    compute_dtype=jnp.bfloat16,
    cache_dtype=jnp.bfloat16,
    remat: bool = True,
):
    """Lockstep-batch prefill: build the DENSE serving cache (padded to
    ``max_len``) plus first-token logits for a whole batch at once —
    the ``greedy_generate`` / dry-run path (the continuous-batching
    engine prefills through :func:`make_chunk_prefill` instead).

    Numerics contract: first-token logits route through the same
    (possibly analog) lm_head as every decode step, and programming
    noise is keyed statically (PRNGKey(0)) so reuse of a programmed
    pytree is bitwise identical to re-programming per call
    (DESIGN.md §5)."""
    policy = policy or DIGITAL
    rng = jax.random.PRNGKey(0)  # static programming noise for serving

    def prefill_step(params, batch, programmed=None, t_now=None):
        with drift_clock(t_now):
            return _prefill_step(params, batch, programmed)

    def _prefill_step(params, batch, programmed):
        hidden, states = forward(
            params, cfg, batch, policy=policy, rng=rng, mode="prefill",
            compute_dtype=compute_dtype, remat=remat, programmed=programmed,
        )
        b = hidden.shape[0]
        s = hidden.shape[1]
        # route the first-token logits through the same (possibly analog)
        # lm_head the decode steps use — the whole generation then sees
        # one consistent hardware semantics
        logits = _head_logits(
            params, hidden[:, -1], policy=policy, rng=rng,
            programmed=programmed,
        )
        ml = max_len or s
        cache = _cache_from_prefill(cfg, states, b, s, ml, cache_dtype)
        return logits, cache

    return prefill_step


def make_slot_prefill(
    cfg: ArchConfig,
    policy: MemPolicy | None = None,
    *,
    compute_dtype=jnp.bfloat16,
    cache_dtype=jnp.bfloat16,
    remat: bool = True,
):
    """Single-request bucket-padded prefill (dense layout).

    The returned function prefills ONE request whose prompt is padded to
    a static bucket length and returns

      * logits at the request's LAST REAL token (``prompt_len - 1`` —
        a traced index, so one compile serves every prompt length that
        shares a bucket), and
      * the per-layer serving states at bucket length.

    The continuous-batching engine now prefills through
    :func:`make_chunk_prefill` (paged arena, DESIGN.md §7); this
    function is retained as the dense single-shot reference — its
    numerics are the oracle the chunked path's chunk-size invariance is
    argued against.

    Numerics contract: right-padding is invisible to the real positions
    — attention is causal (padded keys sit strictly after every real
    query) and the DPE input pipeline quantises per row, so a padded
    prefill computes bitwise the same numbers for the real tokens as an
    exact-length one on the fast path.
    """
    policy = policy or DIGITAL
    rng = jax.random.PRNGKey(0)  # static programming noise for serving

    def slot_prefill(params, tokens, prompt_len, programmed=None,
                     t_now=None):
        """tokens: (1, bucket) right-padded; prompt_len: () int32."""
        with drift_clock(t_now):
            return _slot_prefill(params, tokens, prompt_len, programmed)

    def _slot_prefill(params, tokens, prompt_len, programmed):
        hidden, states = forward(
            params, cfg, {"tokens": tokens}, policy=policy, rng=rng,
            mode="prefill", compute_dtype=compute_dtype, remat=remat,
            programmed=programmed,
        )
        last = jax.lax.dynamic_index_in_dim(
            hidden, prompt_len - 1, axis=1, keepdims=False
        )  # (1, d)
        logits = _head_logits(
            params, last, policy=policy, rng=rng, programmed=programmed
        )
        states = jax.tree.map(lambda x: x.astype(cache_dtype), states)
        return logits, states

    return slot_prefill


def make_chunk_prefill(
    cfg: ArchConfig,
    policy: MemPolicy | None = None,
    *,
    compute_dtype=jnp.bfloat16,
):
    """Chunked prefill against the paged arena (DESIGN.md §7).

    The returned function runs ONE fixed-size chunk of ONE request's
    prompt through the model, writing its K/V into the slot's blocks,
    and returns the updated cache plus logits at the chunk's last real
    token — the request's first-token logits when ``final`` is True;
    non-final chunks skip the vocab head entirely and return zeros.
    One compile serves every ``(chunk_len,)`` shape — slot, start
    offset, valid-token count and finality are traced.  Because
    ``start`` is traced, a lane can begin anywhere in its prompt: the
    prefix cache admits a request with its first ``cached_len`` tokens'
    KV already resident (shared blocks mapped into the slot's table) and
    prefill simply resumes at ``start = cached_len`` — the chunk attends
    over the gathered table, cached blocks included, exactly as a cold
    run's later chunks attend over their own earlier writes.

    Numerics contract (tests/test_batching.py, tests/test_prefix_cache.py):
    fast-path logits are BITWISE identical across chunk sizes,
    block-table layouts, and cache-hit patterns (a resumed prefill is
    indistinguishable from a cold one); the faithful row-independent
    engine agrees to GEMM-kernel rounding with tokens equal — the same
    tolerance classes as the decode-path batched==solo contract.
    """
    policy = policy or DIGITAL
    rng = jax.random.PRNGKey(0)  # static programming noise for serving

    # jitted, this is the module ``jit_chunk_fn``: the benchmark reads
    # that name in the device trace
    def chunk_fn(
        params, cache, tokens, slot, start, n_valid, final,
        programmed=None, t_now=None,
    ):
        """tokens: (C,) right-padded chunk; slot/start/n_valid: () int32;
        final: () bool — non-final chunks skip the vocab head.  ``t_now``
        (traced f32 device-clock scalar, or None) is published to
        ``dpe_apply`` via :func:`repro.core.drift.drift_clock` while the
        body traces — the drift evaluation point for every analog matmul
        of the chunk."""
        with drift_clock(t_now):
            return prefill_chunk_step(
                params, cfg, cache, tokens, slot, start, n_valid, final,
                policy=policy, rng=rng, compute_dtype=compute_dtype,
                programmed=programmed,
            )

    return chunk_fn


def make_decode_step(
    cfg: ArchConfig,
    policy: MemPolicy | None = None,
    *,
    compute_dtype=jnp.bfloat16,
):
    """Slot-parallel decode step (dense or paged cache, detected from
    the cache pytree).  Numerics contract: per-row computations are
    independent — with a row-independent policy the fast path is bitwise
    identical across packings, the faithful path agrees to GEMM-kernel
    rounding across batch extents with tokens equal (DESIGN.md §7)."""
    policy = policy or DIGITAL
    rng = jax.random.PRNGKey(0)

    def decode_fn(params, cache, tokens, programmed=None, active=None,
                  t_now=None):
        with drift_clock(t_now):
            return model_decode(
                params, cfg, cache, tokens, policy=policy, rng=rng,
                compute_dtype=compute_dtype, programmed=programmed,
                active=active,
            )

    return decode_fn


def make_verify_step(
    cfg: ArchConfig,
    policy: MemPolicy | None = None,
    *,
    compute_dtype=jnp.bfloat16,
):
    """Speculative-verification step over the paged cache (DESIGN.md §7).

    The returned function consumes ``tokens`` (B, C) — per slot, the
    last emitted token followed by C-1 draft proposals — and returns
    per-position logits (B, C, V) plus the cache with all C positions'
    K/V written but ``pos`` NOT advanced: the caller commits the
    accepted frontier itself (accept/rollback, serve/batching.py).  Row
    ``(b, c)`` is bitwise the logits a sequential decode would produce
    at ``pos + c`` given the same accepted prefix
    (:func:`repro.models.decode_verify_step`), so verification amortises
    the expensive programmed engine over C rows without changing one
    emitted token."""
    policy = policy or DIGITAL
    rng = jax.random.PRNGKey(0)

    def verify_fn(params, cache, tokens, programmed=None, active=None,
                  t_now=None):
        with drift_clock(t_now):
            return model_verify(
                params, cfg, cache, tokens, policy=policy, rng=rng,
                compute_dtype=compute_dtype, programmed=programmed,
                active=active,
            )

    return verify_fn


def greedy_generate(
    params,
    cfg: ArchConfig,
    prompt_tokens,
    n_steps: int,
    *,
    policy: MemPolicy | None = None,
    max_len: int | None = None,
    compute_dtype=jnp.bfloat16,
    extra_batch: dict | None = None,
    programmed=None,
    weight_stationary: bool = True,
    jit_steps: bool = True,
    mesh=None,
    t_now=None,
    sampling=None,
):
    """Batched greedy decoding driver (example / integration tests).

    By default the model is programmed once (``weight_stationary=True``)
    and the prefill/decode steps are jitted, the decode step with KV-cache
    donation so the cache updates in place across tokens.  Pass
    ``weight_stationary=False`` to get the per-call re-programming
    behaviour (the equivalence oracle — bitwise-identical logits under a
    fixed programming key), or a pre-built ``programmed`` pytree to skip
    the programming pass here.  With ``mesh`` the programmed state is
    materialised sharded over it (``programmed_sharding_rules``) instead
    of replicated — bitwise-identical logits, per-device bytes divided by
    the model-axis size for TP-sharded layers.

    ``t_now`` (device-clock seconds, optional) is the drift evaluation
    time threaded to every prefill/decode step — with a drift-enabled
    policy the generation reads the programmed state as aged to
    ``t_now``; None (default) disables drift evaluation entirely.

    ``sampling`` (a :class:`repro.serve.sampling.SamplingParams`,
    optional) replaces the argmax pick with the per-request-seeded
    sampler: token ``i`` is drawn with ``fold_in(PRNGKey(seed), i)`` on
    the unchanged logits — the SOLO oracle of the sampled batched==solo
    contract (every batch row draws with the same key, so rows are
    replicas of the same request).  ``sampling=None`` keeps the exact
    greedy behaviour; ``temperature=0`` sampling equals it bitwise.
    """
    if t_now is not None:
        t_now = jnp.asarray(t_now, jnp.float32)
    b, s = prompt_tokens.shape
    ml = max_len or (s + n_steps + 1)
    batch = {"tokens": prompt_tokens}
    if extra_batch:
        batch.update(extra_batch)
    # an active mesh turns on the logical-axis constraints while the
    # steps trace, so activations follow the sharded programmed state
    ctx = rules_context(mesh) if mesh is not None else contextlib.nullcontext()
    with ctx:
        if programmed is None and weight_stationary and policy is not None:
            # PRNGKey(0) matches the static serving key of the step makers
            programmed = program_params(
                params, cfg, policy, jax.random.PRNGKey(0), mesh=mesh
            )
        prefill = make_prefill_step(
            cfg, policy, max_len=ml, compute_dtype=compute_dtype,
            cache_dtype=jnp.float32
            if compute_dtype == jnp.float32
            else jnp.bfloat16,
        )
        decode = make_decode_step(cfg, policy, compute_dtype=compute_dtype)
        if jit_steps:
            prefill = jax.jit(prefill)
            # donate the cache: each token's KV update aliases the previous
            # buffer instead of allocating a fresh max_len-sized cache
            decode = jax.jit(decode, donate_argnums=(1,))
        if sampling is None:
            pick = lambda logits, i: jnp.argmax(logits, axis=-1)
        else:
            # every row draws with the request's key for emission i —
            # a pure function of (seed, i), exactly the keys the
            # ServeLoop uses for this request in any slot/packing
            keys = request_keys(sampling.seed, n_steps + 1)
            samp = jax.vmap(sample_row, in_axes=(None, 0, None, None, None))
            if jit_steps:
                samp = jax.jit(samp)
            temp = jnp.float32(sampling.temperature)
            tk = jnp.int32(sampling.top_k)
            tp = jnp.float32(sampling.top_p)
            pick = lambda logits, i: samp(keys[i], logits, temp, tk, tp)
        logits, cache = prefill(params, batch, programmed, t_now)
        out = []
        tok = pick(logits, 0)
        for i in range(n_steps):
            out.append(tok)
            logits, cache = decode(params, cache, tok, programmed, None, t_now)
            tok = pick(logits, i + 1)
        out.append(tok)
    return jnp.stack(out, axis=1)
