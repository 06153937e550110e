"""Model entry points: init / forward / decode_step / loss.

Layer stacks are scanned (one trace per *segment* of structurally
identical layers) with full rematerialisation per step, so 94-layer MoE
models compile to compact HLO and fit activation memory at 32k context.

Families:
  * decoder-only (dense / moe / ssm / hybrid / vlm) — `forward`/`decode_step`
  * encoder-decoder (whisper) — same API; `batch["frames"]` feeds the
    stubbed conv frontend (precomputed frame embeddings).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.layers import MemPolicy
from repro.distributed.sharding import constrain

from .attention import (
    attention_block,
    decode_attention_block,
    init_attn_params,
)
from .common import (
    dense,
    make_dense_params,
    make_norm_params,
    norm,
    pget,
    uniform_init,
)
from .config import ArchConfig
from .ssm import init_mamba_state, init_rwkv6_state
from .transformer import (
    _ffn_forward,
    block_chunk,
    block_decode,
    block_forward,
    block_verify,
    group_size,
    init_block_params,
    n_groups,
)

__all__ = [
    "segments",
    "init_params",
    "forward",
    "decode_step",
    "decode_verify_step",
    "prefill_chunk_step",
    "loss_fn",
    "init_cache",
    "init_paged_cache",
]

DIGITAL = MemPolicy(default=None)


# ---------------------------------------------------------------------------
# segmentation: contiguous runs of structurally identical layers
# ---------------------------------------------------------------------------

def segments(cfg: ArchConfig) -> list[tuple[int, int, int]]:
    """[(start_group, n_steps, template_layer_idx), ...]."""
    if cfg.family == "hybrid":
        return [(0, n_groups(cfg), 0)]
    sigs = [cfg.layer_kind(i) for i in range(cfg.n_layers)]
    segs = []
    start = 0
    for i in range(1, cfg.n_layers + 1):
        if i == cfg.n_layers or sigs[i] != sigs[start]:
            segs.append((start, i - start, start))
            start = i
    return segs


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ArchConfig, key, dtype=jnp.float32):
    keys = jax.random.split(key, 8)
    d, v = cfg.d_model, cfg.vocab
    params = {
        "embed": {"w": uniform_init(keys[0], (v, d), scale=0.02, dtype=dtype)},
        "final_norm": make_norm_params(d, cfg.norm, dtype),
        "lm_head": make_dense_params(keys[1], d, v, False, dtype),
        "blocks": {},
    }
    for si, (start, steps, tmpl) in enumerate(segments(cfg)):
        seg_keys = jax.random.split(jax.random.fold_in(keys[2], si), steps)
        params["blocks"][f"seg{si}"] = jax.vmap(
            lambda k: init_block_params(k, cfg, tmpl, dtype)
        )(seg_keys)
    if cfg.encoder is not None:
        params["encoder"] = _init_encoder(keys[3], cfg, dtype)
        params["cross"] = _init_cross_stack(keys[4], cfg, dtype)
    return params


def _init_encoder(key, cfg, dtype):
    ks = jax.random.split(key, cfg.encoder.n_layers + 1)
    blocks = jax.vmap(lambda k: init_block_params(k, cfg, 0, dtype))(
        ks[: cfg.encoder.n_layers]
    )
    return {
        "blocks": blocks,
        "final_norm": make_norm_params(cfg.d_model, cfg.norm, dtype),
    }


def _init_cross_stack(key, cfg, dtype):
    """Per-decoder-layer cross-attention params (stacked)."""

    def one(k):
        p = init_attn_params(k, cfg, dtype)
        p["norm"] = make_norm_params(cfg.d_model, cfg.norm, dtype)
        return p

    return jax.vmap(one)(jax.random.split(key, cfg.n_layers))


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg, batch, compute_dtype):
    tokens = batch["tokens"]
    x = jnp.take(
        params["embed"]["w"].astype(compute_dtype), tokens, axis=0
    )
    if cfg.vision_prefix and "patch_embeds" in batch:
        x = jnp.concatenate(
            [batch["patch_embeds"].astype(compute_dtype), x], axis=1
        )
    return constrain(x, "batch", "seq", "embed")


def _sinusoid(positions, d):
    half = d // 2
    freq = jnp.exp(
        -jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half
    )
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _scan_blocks(
    params_seg, x, cfg, tmpl, *, policy, rng, positions, remat,
    collect_states=False, attn_schedule="masked", prog_seg=None,
):
    steps = jax.tree_util.tree_leaves(params_seg)[0].shape[0]

    def step(x, inp):
        p_l, prog_l, idx = inp
        rng_l = jax.random.fold_in(rng, idx)
        x, states = block_forward(
            p_l, x, cfg, tmpl, policy=policy, rng=rng_l,
            positions=positions, attn_schedule=attn_schedule,
            prepared=prog_l,
        )
        # Megatron-SP: shard the between-layer carry (and therefore each
        # layer's remat checkpoint) along the sequence over `model`.
        x = constrain(x, "batch", "seq_act", "embed")
        return x, states if collect_states else None

    fn = jax.checkpoint(step, policy=jax.checkpoint_policies.nothing_saveable) \
        if remat else step
    x, states = lax.scan(fn, x, (params_seg, prog_seg, jnp.arange(steps)))
    return x, states


def forward(
    params,
    cfg: ArchConfig,
    batch: dict,
    *,
    policy: MemPolicy = DIGITAL,
    rng=None,
    mode: str = "train",
    compute_dtype=jnp.bfloat16,
    remat: bool = True,
    programmed=None,
):
    """Returns hidden states (B, S, d) after final norm, plus per-segment
    serving states when ``mode == 'prefill'``.

    ``programmed``: weight-stationary state from
    :func:`repro.models.programmed.program_params` — when given, no
    hardware layer re-programs its crossbars (inference; training keeps
    the per-step re-programming semantics of the paper)."""
    rng = jax.random.PRNGKey(0) if rng is None else rng
    if cfg.encoder is not None:
        return _encdec_forward(
            params, cfg, batch, policy=policy, rng=rng, mode=mode,
            compute_dtype=compute_dtype, remat=remat, programmed=programmed,
        )
    x = _embed_inputs(params, cfg, batch, compute_dtype)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    all_states = {}
    prog_blocks = pget(programmed, "blocks")
    for si, (start, steps, tmpl) in enumerate(segments(cfg)):
        x, states = _scan_blocks(
            params["blocks"][f"seg{si}"], x, cfg, tmpl,
            policy=policy, rng=jax.random.fold_in(rng, si),
            positions=positions, remat=remat,
            collect_states=(mode == "prefill"),
            # "tri" halves causal attention traffic/compute; a
            # deployment can flip trains to "masked" if the unrolled
            # schedule's backward peak memory binds (EXPERIMENTS §Perf)
            attn_schedule="tri",
            prog_seg=pget(prog_blocks, f"seg{si}"),
        )
        all_states[f"seg{si}"] = states
    x = norm(x, params["final_norm"], cfg.norm)
    if mode == "prefill":
        return x, all_states
    return x


# ---------------------------------------------------------------------------
# loss (chunked cross-entropy over the sequence to bound logit memory)
# ---------------------------------------------------------------------------

def loss_fn(
    params,
    cfg: ArchConfig,
    batch: dict,
    *,
    policy: MemPolicy = DIGITAL,
    rng=None,
    compute_dtype=jnp.bfloat16,
    remat: bool = True,
    loss_chunk: int = 256,
):
    """Mean next-token cross entropy; labels < 0 are masked."""
    x = forward(
        params, cfg, batch, policy=policy, rng=rng, mode="train",
        compute_dtype=compute_dtype, remat=remat,
    )
    labels = batch["labels"]
    if cfg.vision_prefix and "patch_embeds" in batch:
        pref = jnp.full(
            (labels.shape[0], cfg.vision_prefix), -1, labels.dtype
        )
        labels = jnp.concatenate([pref, labels], axis=1)
    b, s, d = x.shape
    ck = min(loss_chunk, s)
    if s % ck:  # pad to a whole number of chunks; padded labels masked
        pad = ck - s % ck
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
        s += pad
    nck = s // ck
    head = params["lm_head"]["w"]
    rng = jax.random.PRNGKey(0) if rng is None else rng

    def chunk(carry, i):
        tot, cnt = carry
        xs = lax.dynamic_slice_in_dim(x, i * ck, ck, 1)
        ls = lax.dynamic_slice_in_dim(labels, i * ck, ck, 1)
        logits = dense(
            {"w": head}, xs, name="lm_head", policy=policy, rng=rng
        ).astype(jnp.float32)
        logits = constrain(logits, "batch", "seq", "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(ls, 0)[..., None], axis=-1
        )[..., 0]
        mask = (ls >= 0).astype(jnp.float32)
        tot = tot + jnp.sum((lse - picked) * mask)
        cnt = cnt + jnp.sum(mask)
        return (tot, cnt), None

    if remat:  # recompute per-chunk logits in backward: O(chunk) memory
        chunk = jax.checkpoint(
            chunk, policy=jax.checkpoint_policies.nothing_saveable
        )
    (tot, cnt), _ = lax.scan(
        chunk, (jnp.float32(0), jnp.float32(0)), jnp.arange(nck)
    )
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# serving cache & decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Allocate the serving cache pytree (what input_specs mirrors)."""
    cache = {"pos": jnp.zeros((batch,), jnp.int32), "blocks": {}}
    for si, (start, steps, tmpl) in enumerate(segments(cfg)):
        cache["blocks"][f"seg{si}"] = _seg_cache(
            cfg, tmpl, steps, batch, max_len, dtype
        )
    if cfg.encoder is not None:
        kvh, hd = cfg.n_kv_heads, cfg.head_dim
        cache["cross_kv"] = {
            "k": jnp.zeros(
                (cfg.n_layers, batch, cfg.encoder.n_frames, kvh, hd), dtype
            ),
            "v": jnp.zeros(
                (cfg.n_layers, batch, cfg.encoder.n_frames, kvh, hd), dtype
            ),
        }
    return cache


def _one_layer_cache(cfg, layer_idx, batch, max_len, dtype):
    kind, _ = cfg.layer_kind(layer_idx)
    if kind == "attn":
        kvh, hd = cfg.n_kv_heads, cfg.head_dim
        return {
            "k": jnp.zeros((batch, max_len, kvh, hd), dtype),
            "v": jnp.zeros((batch, max_len, kvh, hd), dtype),
        }
    if cfg.ssm.kind == "rwkv6":
        st = init_rwkv6_state(cfg, batch, 1, dtype)
        return {"s": st["s"][0], "x_prev": st["x_prev"][0]}
    st = init_mamba_state(cfg, batch, 1, dtype)
    return {"h": st["h"][0], "conv": st["conv"][0]}


def init_paged_cache(
    cfg: ArchConfig,
    slots: int,
    max_len: int,
    block_size: int,
    n_blocks: int,
    dtype=jnp.bfloat16,
):
    """Allocate the PAGED serving cache (DESIGN.md §7).

    Instead of a dense ``(slots, max_len)`` KV row per lane, every
    attention layer owns one block POOL ``(n_blocks, block_size, KV*hd)``
    shared by all slots, addressed through per-slot ``block_tables``
    ``(slots, ceil(max_len/block_size))``; a segment stacks its layers'
    pools into one ``(steps, n_blocks, block_size, KV*hd)`` arena per K
    and V side, which the layer scan updates in place.  The heads share
    the minor axis so a block is a lane-dense ``(block_size, KV*hd)``
    tile: the layout the chip keeps the arena in is the one the row
    scatter and the paged kernels take, and no step relayouts it.

    Physical block 0 is the reserved trash block (never allocated):
    table entries initialised to it are "unallocated", pad/idle writes
    are routed to it, and every read of it is masked before the
    softmax — so the pool can be sized to the live working set
    (``n_blocks`` < slots*blocks_per_slot) and freed blocks can be
    re-used across requests without KV leakage.

    Attention-only families (the serving loop enforces this): SSM /
    hybrid state is recurrent, not positional, so it has nothing to
    page.
    """
    kinds = {cfg.layer_kind(i)[0] for i in range(cfg.n_layers)}
    if kinds != {"attn"} or cfg.encoder is not None:
        raise NotImplementedError(
            "paged KV cache requires homogeneous all-attention layers"
        )
    if n_blocks < 2:
        raise ValueError("n_blocks must be >= 2 (block 0 is the trash block)")
    nb_per_slot = -(-max_len // block_size)
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    cache = {
        "pos": jnp.zeros((slots,), jnp.int32),
        "block_tables": jnp.zeros((slots, nb_per_slot), jnp.int32),
        "blocks": {},
    }
    for si, (start, steps, tmpl) in enumerate(segments(cfg)):
        cache["blocks"][f"seg{si}"] = {
            "k": jnp.zeros((steps, n_blocks, block_size, kvh * hd), dtype),
            "v": jnp.zeros((steps, n_blocks, block_size, kvh * hd), dtype),
        }
    return cache


def copy_paged_block(cache: dict, src, dst):
    """Clone physical block ``src``'s K/V rows into block ``dst`` across
    every layer's pool — the copy-on-write step of prefix sharing
    (DESIGN.md §7).

    When two requests share prefix blocks (refcount > 1) the block
    holding the first position a lane will WRITE must be cloned before
    that write: the sharer keeps reading ``src`` while the writer's
    block table points at ``dst``.  One ``(steps, block_size, kvh*hd)``
    row moves per layer segment and K/V side; ``src``/``dst`` are traced
    scalars so the serve loop jits this once (donating the arena) for
    any block pair.  Block tables and ``pos`` are untouched — the caller
    rebinds its own table row.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    blocks = {}
    for name, seg in cache["blocks"].items():
        out = {}
        for side, pool in seg.items():
            row = lax.dynamic_slice_in_dim(pool, src, 1, axis=1)
            out[side] = lax.dynamic_update_slice_in_dim(
                pool, row, dst, axis=1
            )
        blocks[name] = out
    return {**cache, "blocks": blocks}


def _seg_cache(cfg, tmpl, steps, batch, max_len, dtype):
    g = group_size(cfg)
    if g == 1:
        template = _one_layer_cache(cfg, tmpl, batch, max_len, dtype)
    else:
        template = {
            f"l{j}": _one_layer_cache(cfg, j, batch, max_len, dtype)
            for j in range(g)
        }
    return jax.tree.map(
        lambda a: jnp.zeros((steps,) + a.shape, a.dtype), template
    )


def _scan_layers(layer_fn, x, seg_p, prog_seg, seg_c, *, carried):
    """Run ``layer_fn(x, p_l, prog_l, state, idx) -> (x, state)`` over a
    segment's layers.

    ``carried`` (the paged arena): ``seg_c`` is the whole stacked
    ``(L, n_blocks, ...)`` arena, handed to every layer with its index
    and threaded through the scan CARRY, so each layer's scatter updates
    the one buffer in place and no layer's pool is cut out or written
    back.  Otherwise (the dense cache, recurrent state): each layer gets
    its own slice of ``seg_c`` as a scan input and returns it as an
    output."""
    idx = jnp.arange(jax.tree_util.tree_leaves(seg_p)[0].shape[0])
    if carried:
        def step(carry, inp):
            x, state = carry
            p_l, prog_l, i = inp
            return layer_fn(x, p_l, prog_l, state, i), None

        (x, seg_c), _ = lax.scan(step, (x, seg_c), (seg_p, prog_seg, idx))
        return x, seg_c

    def step(x, inp):
        p_l, prog_l, c_l, i = inp
        return layer_fn(x, p_l, prog_l, c_l, i)

    return lax.scan(step, x, (seg_p, prog_seg, seg_c, idx))


def decode_step(
    params,
    cfg: ArchConfig,
    cache: dict,
    tokens: jax.Array,  # (B,) next token ids
    *,
    policy: MemPolicy = DIGITAL,
    rng=None,
    compute_dtype=jnp.bfloat16,
    programmed=None,
    active=None,
):
    """One serving step: consume `tokens`, return (logits (B,V), cache).

    With ``programmed`` state the decode hot path never re-runs the
    weight pipeline — each token pays prepare_input + the GEMM only.

    ``active``: optional (B,) bool slot mask (continuous batching,
    serve/batching.py): rows where it is False neither advance ``pos``
    nor mutate their KV / recurrent state — an idle slot's row is
    completely frozen while its neighbours keep decoding.  Logits are
    still produced for every row; callers ignore the inactive ones.

    Cache layouts: the dense ``init_cache`` pytree, or the paged
    ``init_paged_cache`` pytree (detected by its ``block_tables`` leaf)
    — blocks are read in logical order by the attention math, so for
    the same stored KV the two layouts produce bitwise-identical logits
    on the fast path.  The paged arena rides the layer scan's carry and
    is updated in place (``_scan_layers``)."""
    rng = jax.random.PRNGKey(0) if rng is None else rng
    if cfg.encoder is not None:
        return _encdec_decode(
            params, cfg, cache, tokens, policy=policy, rng=rng,
            compute_dtype=compute_dtype, programmed=programmed,
            active=active,
        )
    block_tables = cache.get("block_tables")
    x1 = jnp.take(params["embed"]["w"].astype(compute_dtype), tokens, axis=0)
    pos = cache["pos"]
    inc = 1 if active is None else active.astype(jnp.int32)
    new_cache = {"pos": pos + inc, "blocks": {}}
    if block_tables is not None:
        new_cache["block_tables"] = block_tables
    prog_blocks = pget(programmed, "blocks")
    for si, (start, steps, tmpl) in enumerate(segments(cfg)):
        rng_s = jax.random.fold_in(rng, si)

        def layer(x1, p_l, prog_l, state, idx):
            return block_decode(
                p_l, x1, cfg, tmpl, policy=policy,
                rng=jax.random.fold_in(rng_s, idx), pos=pos, state=state,
                prepared=prog_l, active=active, block_tables=block_tables,
                layer=idx,
            )

        x1, new_cache["blocks"][f"seg{si}"] = _scan_layers(
            layer, x1, params["blocks"][f"seg{si}"],
            pget(prog_blocks, f"seg{si}"), cache["blocks"][f"seg{si}"],
            carried=block_tables is not None,
        )
    x1 = norm(x1, params["final_norm"], cfg.norm)
    logits = dense(
        params["lm_head"], x1, name="lm_head", policy=policy, rng=rng,
        prepared=pget(programmed, "lm_head"),
    ).astype(jnp.float32)
    logits = constrain(logits, "batch", "vocab")
    return logits, new_cache


def decode_verify_step(
    params,
    cfg: ArchConfig,
    cache: dict,
    tokens: jax.Array,  # (B, C) last emitted token + C-1 draft proposals
    *,
    policy: MemPolicy = DIGITAL,
    rng=None,
    compute_dtype=jnp.bfloat16,
    programmed=None,
    active=None,
):
    """Batched multi-token VERIFY forward for speculative decoding
    (DESIGN.md §7).

    Runs every slot's C candidate tokens through the layer stack in ONE
    forward and returns per-position logits ``(B, C, V)`` — row
    ``(b, c)`` is BITWISE the logits a sequential single-token decode
    would produce at position ``pos[b] + c`` given the same accepted
    prefix: every layer writes all C positions' K/V into the slot's
    already-allocated blocks first (inactive lanes route to the trash
    block), then position ``c`` attends under the ``ki <= pos + c``
    mask, so later-position keys contribute exactly 0.0 after ``exp``.
    This is how the programmed target amortises its expensive analog
    GEMMs over k draft tokens per step: C rows ride through the same
    weight-stationary matmuls one row would.

    ``cache["pos"]`` is NOT advanced: the caller decides how many
    candidates the target accepted and commits the new frontier itself
    (the accept/rollback pos rewind in serve/batching.py) — rejected
    tails stay dead by the length mask until the next round overwrites
    them.  Paged cache only (there is no rollback story for a dense
    per-slot cache's recurrent siblings).

    Layer names and the PRNG fold chain mirror ``decode_step`` exactly,
    so programmed-state lookup and programming noise agree.
    """
    rng = jax.random.PRNGKey(0) if rng is None else rng
    block_tables = cache.get("block_tables")
    if block_tables is None:
        raise NotImplementedError(
            "decode_verify_step requires the paged cache "
            "(init_paged_cache): accept/rollback is a block-table pos "
            "rewind"
        )
    x = jnp.take(
        params["embed"]["w"].astype(compute_dtype), tokens, axis=0
    )  # (B, C, d)
    pos = cache["pos"]
    new_cache = {"pos": pos, "block_tables": block_tables, "blocks": {}}
    prog_blocks = pget(programmed, "blocks")
    for si, (start, steps, tmpl) in enumerate(segments(cfg)):
        rng_s = jax.random.fold_in(rng, si)

        def layer(x, p_l, prog_l, state, idx):
            return block_verify(
                p_l, x, cfg, tmpl, policy=policy,
                rng=jax.random.fold_in(rng_s, idx), pos=pos, state=state,
                layer=idx, block_tables=block_tables, prepared=prog_l,
                active=active,
            )

        x, new_cache["blocks"][f"seg{si}"] = _scan_layers(
            layer, x, params["blocks"][f"seg{si}"],
            pget(prog_blocks, f"seg{si}"), cache["blocks"][f"seg{si}"],
            carried=True,
        )
    x = norm(x, params["final_norm"], cfg.norm)
    logits = dense(
        params["lm_head"], x, name="lm_head", policy=policy, rng=rng,
        prepared=pget(programmed, "lm_head"),
    ).astype(jnp.float32)
    logits = constrain(logits, "batch", "seq", "vocab")
    return logits, new_cache


def prefill_chunk_step(
    params,
    cfg: ArchConfig,
    cache: dict,
    tokens: jax.Array,  # (C,) one chunk of one prompt, right-padded
    slot: jax.Array,  # () int32
    start: jax.Array,  # () int32 logical position of tokens[0]
    n_valid: jax.Array,  # () int32 real tokens in this chunk
    final: jax.Array = None,  # () bool: is this the prompt's last chunk?
    *,
    policy: MemPolicy = DIGITAL,
    rng=None,
    compute_dtype=jnp.bfloat16,
    programmed=None,
):
    """One CHUNKED-PREFILL step against the paged cache (DESIGN.md §7).

    Runs ``tokens`` (one fixed-size chunk of one request's prompt)
    through the full layer stack, writing each layer's K/V into slot
    ``slot``'s blocks at logical positions ``start .. start+n_valid-1``
    (pad tokens route to the trash block), and returns
    ``(logits, cache)`` where ``logits`` (1, V) are taken at the chunk's
    LAST REAL token — the request's first-token logits on a prompt's
    final chunk.  When ``final`` (traced bool) is False the final-norm +
    lm_head are skipped (``lax.cond``) and zeros are returned: only a
    prompt's last chunk pays the (possibly analog) vocab projection.
    ``cache["pos"][slot]`` advances to ``start + n_valid`` so a
    completed prefill leaves the lane decode-ready.

    Numerics contract: layer names and the PRNG fold chain mirror
    ``forward``/``decode_step`` exactly (programmed-state lookup and
    programming noise agree), and per-token math is chunk-size-invariant
    — on the fast path the final logits are BITWISE identical for every
    chunk size, and token-identical to solo ``greedy_generate`` prefill
    (tests/test_batching.py).
    """
    rng = jax.random.PRNGKey(0) if rng is None else rng
    c = tokens.shape[0]
    x = jnp.take(
        params["embed"]["w"].astype(compute_dtype), tokens[None], axis=0
    )  # (1, C, d)
    positions = (start + jnp.arange(c))[None]  # (1, C)
    bt_row = lax.dynamic_index_in_dim(
        cache["block_tables"], slot, axis=0, keepdims=False
    )
    new_cache = {
        "pos": lax.dynamic_update_slice(
            cache["pos"], (start + n_valid)[None].astype(jnp.int32), (slot,)
        ),
        "block_tables": cache["block_tables"],
        "blocks": {},
    }
    prog_blocks = pget(programmed, "blocks")
    for si, (seg_start, steps, tmpl) in enumerate(segments(cfg)):
        rng_s = jax.random.fold_in(rng, si)

        def layer(x, p_l, prog_l, state, idx):
            return block_chunk(
                p_l, x, cfg, tmpl, policy=policy,
                rng=jax.random.fold_in(rng_s, idx), state=state, layer=idx,
                bt_row=bt_row, start=start, n_valid=n_valid,
                positions=positions, prepared=prog_l,
            )

        x, new_cache["blocks"][f"seg{si}"] = _scan_layers(
            layer, x, params["blocks"][f"seg{si}"],
            pget(prog_blocks, f"seg{si}"), cache["blocks"][f"seg{si}"],
            carried=True,
        )
    last = lax.dynamic_index_in_dim(
        x, n_valid - 1, axis=1, keepdims=False
    )  # (1, d) pre-norm hidden of the chunk's last real token

    def head(h):
        # norm is per-position, so norm(x)[i] == norm(x[i]) — running it
        # on the extracted token computes the same values single-shot
        # prefill computes on the full sequence
        h = norm(h, params["final_norm"], cfg.norm)
        return dense(
            params["lm_head"], h, name="lm_head", policy=policy, rng=rng,
            prepared=pget(programmed, "lm_head"),
        ).astype(jnp.float32)

    if final is None:
        logits = head(last)
    else:
        logits = lax.cond(
            final, head, lambda h: jnp.zeros((1, cfg.vocab), jnp.float32),
            last,
        )
    return logits, new_cache


# ---------------------------------------------------------------------------
# encoder-decoder (whisper)
# ---------------------------------------------------------------------------

def _encdec_forward(
    params, cfg, batch, *, policy, rng, mode, compute_dtype, remat,
    programmed=None,
):
    frames = batch["frames"].astype(compute_dtype)  # (B, F, d) stubbed
    b, f, d = frames.shape
    pos_e = jnp.broadcast_to(jnp.arange(f), (b, f))
    x = frames + _sinusoid(pos_e, d).astype(compute_dtype)
    enc_blocks = params["encoder"]["blocks"]
    prog_enc = pget(pget(programmed, "encoder"), "blocks")

    def enc_step(x, inp):
        p_l, prog_l, idx = inp
        h = norm(x, p_l["norm1"], cfg.norm)
        y, _ = attention_block(
            p_l["attn"], h, cfg, policy=policy,
            rng=jax.random.fold_in(rng, 1000 + idx),
            positions=pos_e, name="enc.attn",
            prepared=pget(prog_l, "attn"),
        )
        x = x + y
        h = norm(x, p_l["norm2"], cfg.norm)
        x = x + _ffn_forward(
            p_l, h, cfg, policy=policy,
            rng=jax.random.fold_in(rng, 2000 + idx), name="enc",
            prepared=prog_l,
        )
        return x, None

    nenc = cfg.encoder.n_layers
    if remat:
        enc_step = jax.checkpoint(
            enc_step, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, _ = lax.scan(enc_step, x, (enc_blocks, prog_enc, jnp.arange(nenc)))
    enc_out = norm(x, params["encoder"]["final_norm"], cfg.norm)

    tokens = batch["tokens"]
    bt, s = tokens.shape
    xd = jnp.take(params["embed"]["w"].astype(compute_dtype), tokens, axis=0)
    pos_d = jnp.broadcast_to(jnp.arange(s), (bt, s))
    xd = xd + _sinusoid(pos_d, d).astype(compute_dtype)

    prog_seg0 = pget(pget(programmed, "blocks"), "seg0")
    prog_cross = pget(programmed, "cross")

    def dec_step(xd, inp):
        p_l, p_x, prog_l, prog_x, idx = inp
        rng_l = jax.random.fold_in(rng, idx)
        xd, st = block_forward(
            p_l, xd, cfg, 0, policy=policy, rng=rng_l, positions=pos_d,
            prepared=prog_l,
        )
        # cross-attention sublayer
        h = norm(xd, p_x["norm"], cfg.norm)
        kx = dense(p_x["k_proj"], enc_out, name="dec.cross.k", policy=policy,
                   rng=rng_l, prepared=pget(prog_x, "k_proj"))
        vx = dense(p_x["v_proj"], enc_out, name="dec.cross.v", policy=policy,
                   rng=rng_l, prepared=pget(prog_x, "v_proj"))
        kx = kx.reshape(b, f, cfg.n_kv_heads, cfg.head_dim)
        vx = vx.reshape(b, f, cfg.n_kv_heads, cfg.head_dim)
        y, _ = attention_block(
            p_x, h, cfg, policy=policy, rng=rng_l, positions=pos_d,
            name="dec.cross", kv_in=(kx, vx), prepared=prog_x,
        )
        xd = xd + y
        return xd, (st, (kx, vx))

    if remat:
        dec_step = jax.checkpoint(
            dec_step, policy=jax.checkpoint_policies.nothing_saveable
        )
    xd, (self_states, cross_kv) = lax.scan(
        dec_step,
        xd,
        (
            params["blocks"]["seg0"],
            params["cross"],
            prog_seg0,
            prog_cross,
            jnp.arange(cfg.n_layers),
        ),
    )
    xd = norm(xd, params["final_norm"], cfg.norm)
    if mode == "prefill":
        return xd, {
            "seg0": self_states,
            "cross_kv": {"k": cross_kv[0], "v": cross_kv[1]},
        }
    return xd


def _encdec_decode(params, cfg, cache, tokens, *, policy, rng, compute_dtype,
                   programmed=None, active=None):
    d = cfg.d_model
    x1 = jnp.take(params["embed"]["w"].astype(compute_dtype), tokens, axis=0)
    pos = cache["pos"]
    x1 = x1 + _sinusoid(pos, d).astype(compute_dtype)
    inc = 1 if active is None else active.astype(jnp.int32)
    new_cache = {"pos": pos + inc, "blocks": {}, "cross_kv": cache["cross_kv"]}
    seg_p = params["blocks"]["seg0"]
    seg_c = cache["blocks"]["seg0"]
    prog_seg0 = pget(pget(programmed, "blocks"), "seg0")
    prog_cross = pget(programmed, "cross")
    fr = cfg.encoder.n_frames

    def step(x1, inp):
        p_l, p_x, prog_l, prog_x, c_l, kx, vx, idx = inp
        rng_l = jax.random.fold_in(rng, idx)
        x1, st = block_decode(
            p_l, x1, cfg, 0, policy=policy, rng=rng_l, pos=pos, state=c_l,
            prepared=prog_l, active=active,
        )
        h = norm(x1, p_x["norm"], cfg.norm)
        enc_pos = jnp.full_like(pos, fr - 1)
        y, _, _ = decode_attention_block(
            p_x, h, cfg, policy=policy, rng=rng_l, cache_k=kx, cache_v=vx,
            pos=enc_pos, name="dec.cross", cross=True, prepared=prog_x,
        )
        x1 = x1 + y
        return x1, st

    x1, new_states = lax.scan(
        step,
        x1,
        (
            seg_p,
            params["cross"],
            prog_seg0,
            prog_cross,
            seg_c,
            cache["cross_kv"]["k"],
            cache["cross_kv"]["v"],
            jnp.arange(cfg.n_layers),
        ),
    )
    new_cache["blocks"]["seg0"] = new_states
    x1 = norm(x1, params["final_norm"], cfg.norm)
    logits = dense(
        params["lm_head"], x1, name="lm_head", policy=policy, rng=rng,
        prepared=pget(programmed, "lm_head"),
    ).astype(jnp.float32)
    return logits, new_cache
