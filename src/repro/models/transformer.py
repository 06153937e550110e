"""Decoder blocks, heterogeneous layer groups, and scan-over-layers.

Homogeneous architectures scan one block per step; hybrids (jamba) scan a
*period group* (e.g. 8 layers: 1 attention + 7 mamba, MoE on odd
indices).  Every block routes its projections through the mem policy.

Block functions return per-layer serving state (KV / SSM) so the same
code path builds the prefill cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .attention import (
    attention_block,
    chunk_attention_block,
    decode_attention_block,
    init_attn_params,
    verify_attention_block,
)
from repro.distributed.sharding import constrain

from .common import (
    activation,
    dense,
    make_dense_params,
    make_norm_params,
    norm,
    pget,
)
from .moe import init_moe_params, moe_block
from .ssm import (
    init_mamba_params,
    init_rwkv6_params,
    mamba_block,
    mamba_decode,
    rwkv6_block,
    rwkv6_decode,
)

__all__ = [
    "init_block_params",
    "block_forward",
    "block_decode",
    "block_chunk",
    "block_verify",
    "group_size",
    "n_groups",
]


def group_size(cfg) -> int:
    return cfg.hybrid_period if cfg.family == "hybrid" else 1


def n_groups(cfg) -> int:
    g = group_size(cfg)
    assert cfg.n_layers % g == 0, (cfg.n_layers, g)
    return cfg.n_layers // g


def _init_ffn(key, cfg, layer_idx, dtype):
    kind, has_moe = cfg.layer_kind(layer_idx)
    if has_moe:
        return {"moe": init_moe_params(key, cfg, dtype)}
    ks = jax.random.split(key, 3)
    return {
        "mlp": {
            "wi": make_dense_params(ks[0], cfg.d_model, cfg.d_ff, False, dtype),
            "wg": make_dense_params(ks[1], cfg.d_model, cfg.d_ff, False, dtype),
            "wo": make_dense_params(ks[2], cfg.d_ff, cfg.d_model, False, dtype),
        }
    }


def _init_one_layer(key, cfg, layer_idx, dtype, force_kind=None):
    kind, _ = cfg.layer_kind(layer_idx)
    if force_kind:
        kind = force_kind
    k1, k2 = jax.random.split(key)
    p = {
        "norm1": make_norm_params(cfg.d_model, cfg.norm, dtype),
        "norm2": make_norm_params(cfg.d_model, cfg.norm, dtype),
    }
    if kind == "attn":
        p["attn"] = init_attn_params(k1, cfg, dtype)
    elif cfg.ssm.kind == "rwkv6":
        p["ssm"] = init_rwkv6_params(k1, cfg, dtype)
    else:
        p["ssm"] = init_mamba_params(k1, cfg, dtype)
    p.update(_init_ffn(k2, cfg, layer_idx, dtype))
    return p


def init_block_params(key, cfg, group_idx, dtype=jnp.float32):
    """Params for one scan step (a single layer or a hybrid group)."""
    g = group_size(cfg)
    if g == 1:
        return _init_one_layer(key, cfg, group_idx, dtype)
    ks = jax.random.split(key, g)
    return {
        f"l{j}": _init_one_layer(ks[j], cfg, group_idx * g + j, dtype)
        for j in range(g)
    }


def _ffn_forward(p, x, cfg, *, policy, rng, name, prepared=None):
    if "moe" in p:
        return moe_block(p["moe"], x, cfg, policy=policy, rng=rng, name=name,
                         prepared=pget(prepared, "moe"))
    mlp = p["mlp"]
    prog = pget(prepared, "mlp")
    h = dense(mlp["wi"], x, name=f"{name}.mlp.wi", policy=policy, rng=rng,
              prepared=pget(prog, "wi"))
    g = dense(mlp["wg"], x, name=f"{name}.mlp.wg", policy=policy, rng=rng,
              prepared=pget(prog, "wg"))
    h = activation(g, cfg.act) * h
    return dense(mlp["wo"], h, name=f"{name}.mlp.wo", policy=policy, rng=rng,
                 prepared=pget(prog, "wo"))


def _layer_forward(p, x, cfg, layer_idx, *, policy, rng, positions, states,
                   attn_schedule="masked", prepared=None):
    """One layer on a full sequence.  ``states`` carries optional incoming
    SSM state; returns (x, serving_state_dict)."""
    kind, _ = cfg.layer_kind(layer_idx)
    name = f"L.{kind}"
    h = norm(x, p["norm1"], cfg.norm)
    out_state = {}
    if kind == "attn":
        y, (k, v) = attention_block(
            p["attn"], h, cfg, policy=policy, rng=rng,
            positions=positions, name=name, attn_schedule=attn_schedule,
            prepared=pget(prepared, "attn"),
        )
        out_state["k"] = k
        out_state["v"] = v
    elif cfg.ssm.kind == "rwkv6":
        y, (s, x_last) = rwkv6_block(
            p["ssm"], h, cfg, policy=policy, rng=rng, name=name,
            state=None if states is None else states.get("s"),
            x_prev=None if states is None else states.get("x_prev"),
            prepared=pget(prepared, "ssm"),
        )
        out_state["s"] = s
        out_state["x_prev"] = x_last
    else:
        y, (s, conv) = mamba_block(
            p["ssm"], h, cfg, policy=policy, rng=rng, name=name,
            state=None if states is None else states.get("h"),
            conv_cache=None if states is None else states.get("conv"),
            prepared=pget(prepared, "ssm"),
        )
        out_state["h"] = s
        out_state["conv"] = conv
    # Constrain sublayer outputs to the sequence-sharded layout of the
    # between-layer carry: the TP/EP partial-sum then lowers to a
    # reduce-scatter into the carry's shards instead of a full
    # all-reduce (16x less ICI traffic on the model axis — §Perf).
    if x.ndim == 3:
        y = constrain(y, "batch", "seq_act", "embed")
    x = x + y
    h = norm(x, p["norm2"], cfg.norm)
    y2 = _ffn_forward(
        p, h, cfg, policy=policy, rng=rng, name=name, prepared=prepared
    )
    if x.ndim == 3:
        y2 = constrain(y2, "batch", "seq_act", "embed")
    x = x + y2
    return x, out_state


def block_forward(p, x, cfg, template_idx, *, policy, rng, positions,
                  attn_schedule="masked", prepared=None):
    """One scan step (layer or hybrid group) on a full sequence.

    ``template_idx``: a representative global layer index — all layers in
    a scanned segment share its (kind, has_moe) signature.
    """
    g = group_size(cfg)
    if g == 1:
        return _layer_forward(
            p, x, cfg, template_idx,
            policy=policy, rng=rng, positions=positions, states=None,
            attn_schedule=attn_schedule, prepared=prepared,
        )
    states = {}
    for j in range(g):
        x, st = _layer_forward(
            p[f"l{j}"], x, cfg, j, policy=policy, rng=rng,
            positions=positions, states=None, attn_schedule=attn_schedule,
            prepared=pget(prepared, f"l{j}"),
        )
        states[f"l{j}"] = st
    return x, states


def _freeze_inactive(active, new, old):
    """Keep ``old`` state on rows where ``active`` is False (idle serving
    slots must not evolve their recurrent state — serve/batching.py)."""
    if active is None:
        return new
    sel = active.reshape((-1,) + (1,) * (new.ndim - 1))
    return jnp.where(sel, new, old)


def _layer_decode(p, x1, cfg, layer_idx, *, policy, rng, pos, state,
                  prepared=None, active=None, block_tables=None, layer=None):
    kind, _ = cfg.layer_kind(layer_idx)
    name = f"L.{kind}"
    h = norm(x1, p["norm1"], cfg.norm)
    new_state = dict(state)
    if kind == "attn":
        y, ck, cv = decode_attention_block(
            p["attn"], h, cfg, policy=policy, rng=rng,
            cache_k=state["k"], cache_v=state["v"], pos=pos, name=name,
            prepared=pget(prepared, "attn"), active=active,
            block_tables=block_tables, layer=layer,
        )
        new_state["k"], new_state["v"] = ck, cv
    elif cfg.ssm.kind == "rwkv6":
        y, s, x_last = rwkv6_decode(
            p["ssm"], h, cfg, policy=policy, rng=rng, name=name,
            state=state["s"], x_prev=state["x_prev"],
            prepared=pget(prepared, "ssm"),
        )
        new_state["s"] = _freeze_inactive(active, s, state["s"])
        new_state["x_prev"] = _freeze_inactive(active, x_last, state["x_prev"])
    else:
        y, s, conv = mamba_decode(
            p["ssm"], h, cfg, policy=policy, rng=rng, name=name,
            state=state["h"], conv_cache=state["conv"],
            prepared=pget(prepared, "ssm"),
        )
        new_state["h"] = _freeze_inactive(active, s, state["h"])
        new_state["conv"] = _freeze_inactive(active, conv, state["conv"])
    x1 = x1 + y
    h = norm(x1, p["norm2"], cfg.norm)
    x1 = x1 + _ffn_forward(
        p, h[:, None, :], cfg, policy=policy, rng=rng, name=name,
        prepared=prepared,
    )[:, 0]
    return x1, new_state


def block_decode(p, x1, cfg, template_idx, *, policy, rng, pos, state,
                 prepared=None, active=None, block_tables=None, layer=None):
    """One scan step of decode.  Dense cache: ``state`` is this step's
    slice of the segment cache.  Paged cache (``block_tables`` given,
    homogeneous attention only): ``state`` is the segment's stacked
    arena and ``layer`` this step's index in it."""
    g = group_size(cfg)
    if g == 1:
        return _layer_decode(
            p, x1, cfg, template_idx,
            policy=policy, rng=rng, pos=pos, state=state, prepared=prepared,
            active=active, block_tables=block_tables, layer=layer,
        )
    new_states = {}
    for j in range(g):
        x1, st = _layer_decode(
            p[f"l{j}"], x1, cfg, j, policy=policy, rng=rng, pos=pos,
            state=state[f"l{j}"], prepared=pget(prepared, f"l{j}"),
            active=active, block_tables=block_tables,
        )
        new_states[f"l{j}"] = st
    return x1, new_states


def block_chunk(p, x, cfg, template_idx, *, policy, rng, state, layer,
                bt_row, start, n_valid, positions, prepared=None):
    """One scan step of CHUNKED PREFILL (DESIGN.md §7): run a prompt
    chunk ``x`` (1, C, d) through one attention layer, writing its K/V
    into layer ``layer`` of the segment's stacked arena ``state`` at
    this slot's block table.

    Attention-only — recurrent layers cannot replay a right-padded chunk
    (the serving loop rejects those families at construction).  Uses the
    same layer names and the caller's per-layer rng, so programmed-state
    lookup and programming-noise keys match ``block_forward`` /
    ``block_decode`` exactly.
    """
    kind, _ = cfg.layer_kind(template_idx)
    if group_size(cfg) != 1 or kind != "attn":
        raise NotImplementedError(
            "chunked prefill requires homogeneous all-attention layers"
        )
    name = f"L.{kind}"
    h = norm(x, p["norm1"], cfg.norm)
    y, pk, pv = chunk_attention_block(
        p["attn"], h, cfg, policy=policy, rng=rng,
        pool_k=state["k"], pool_v=state["v"], layer=layer, bt_row=bt_row,
        start=start, n_valid=n_valid, positions=positions, name=name,
        prepared=pget(prepared, "attn"),
    )
    x = x + y
    h = norm(x, p["norm2"], cfg.norm)
    x = x + _ffn_forward(
        p, h, cfg, policy=policy, rng=rng, name=name, prepared=prepared
    )
    return x, {"k": pk, "v": pv}


def block_verify(p, x, cfg, template_idx, *, policy, rng, pos, state,
                 layer, block_tables, prepared=None, active=None):
    """One scan step of SPECULATIVE VERIFY (DESIGN.md §7): run the
    C-token verify chunk ``x`` (B, C, d) — last emitted token + draft
    proposals per slot — through one attention layer against layer
    ``layer`` of the segment's stacked arena ``state``.

    Attention-only, like chunked prefill (rejected drafts cannot be
    rolled back out of a recurrent carry; the serving loop rejects
    those families at construction).  Uses the same layer names and the
    caller's per-layer rng, so programmed-state lookup and
    programming-noise keys match ``block_decode`` exactly — the per-row
    bitwise claim of ``verify_attention_block`` then extends through
    the residual/FFN stack (all row-independent).
    """
    kind, _ = cfg.layer_kind(template_idx)
    if group_size(cfg) != 1 or kind != "attn":
        raise NotImplementedError(
            "speculative verify requires homogeneous all-attention layers"
        )
    name = f"L.{kind}"
    h = norm(x, p["norm1"], cfg.norm)
    y, pk, pv = verify_attention_block(
        p["attn"], h, cfg, policy=policy, rng=rng,
        pool_k=state["k"], pool_v=state["v"], layer=layer,
        block_tables=block_tables, pos=pos, name=name,
        prepared=pget(prepared, "attn"), active=active,
    )
    x = x + y
    h = norm(x, p["norm2"], cfg.norm)
    x = x + _ffn_forward(
        p, h, cfg, policy=policy, rng=rng, name=name, prepared=prepared
    )
    return x, {"k": pk, "v": pv}
