"""Logical-axis sharding rules (MaxText-style) for the (pod, data, model)
production mesh.

Models annotate activations with *logical* axis names via
:func:`constrain`; a rules table maps logical names to mesh axes.  Outside
a configured-mesh context ``constrain`` is the identity, so the same model
code runs on 1 CPU device in tests and on 512 devices in the dry-run.

Parameter shardings are resolved from the parameter pytree path with
:func:`param_sharding_rules` — heads/ffn/experts/vocab shard over
``model``, batch over ``(pod, data)``, bit-slice and layer-stack axes stay
local.
"""
from __future__ import annotations

import contextlib
import re
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "LOGICAL_RULES",
    "set_rules",
    "clear_rules",
    "constrain",
    "logical_sharding",
    "param_sharding_rules",
    "programmed_sharding_rules",
    "replicated_call",
]

# logical axis -> mesh axis (or tuple of mesh axes)
LOGICAL_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,          # sequence stays unsharded by default
    "kv_seq": "model",    # flash-decode: KV length sharded over model
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ffn": "model",
    "experts": "model",
    "vocab": "model",
    "layers": None,
    "slices": None,
    # FSDP/ZeRO-3: weight matrices shard their non-TP dimension over
    # (pod, data) (GSPMD all-gathers them per layer).  Spanning the pod
    # axis is what lets 1T-parameter training fit: params+grads in bf16
    # already equal a full pod's HBM (see EXPERIMENTS.md §Dry-run).
    "fsdp": ("pod", "data"),
    # Megatron-SP: the between-layer activation carry (and its per-layer
    # remat checkpoint) shards its sequence axis over model; XLA inserts
    # the all-gather/reduce-scatter pairs around the TP matmuls.
    "seq_act": "model",
}

_state = threading.local()


def _ctx():
    if not hasattr(_state, "mesh"):
        _state.mesh = None
        _state.rules = None
    return _state


def set_rules(mesh: Mesh, rules: dict | None = None) -> None:
    st = _ctx()
    st.mesh = mesh
    st.rules = dict(LOGICAL_RULES if rules is None else rules)


def clear_rules() -> None:
    st = _ctx()
    st.mesh = None
    st.rules = None


@contextlib.contextmanager
def rules_context(mesh: Mesh, rules: dict | None = None):
    """Activate (mesh, rules) for the block; reentrant — restores the
    enclosing context on exit instead of clearing it."""
    st = _ctx()
    prev = (st.mesh, st.rules)
    set_rules(mesh, rules)
    try:
        yield
    finally:
        st.mesh, st.rules = prev


def replicated_call(fn, *args):
    """``fn(*args)``, run as one replicated program per device of the
    active mesh (a plain call without one).  For Pallas kernels: Mosaic
    kernels cannot be partitioned automatically, so under a mesh the
    sharded operands are gathered and every device runs the whole
    kernel."""
    mesh = _ctx().mesh
    if mesh is None:
        return fn(*args)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
    )(*args)


def _mesh_axes(logical: str, mesh: Mesh, rules: dict):
    ax = rules.get(logical)
    if ax is None:
        return None
    axes = ax if isinstance(ax, tuple) else (ax,)
    present = tuple(a for a in axes if a in mesh.axis_names)
    if not present:
        return None
    return present if len(present) > 1 else present[0]


def logical_spec(
    logical_axes: tuple, mesh: Mesh, rules: dict, shape: tuple | None = None
) -> P:
    """Resolve logical axes to a PartitionSpec.

    When ``shape`` is given, mesh axes whose size does not divide the
    corresponding dimension are dropped (replicated) — e.g. 14 attention
    heads on a 16-way model axis fall back to replication instead of
    failing (the §Perf log tracks the cost of such fallbacks).
    """
    out = []
    used: set = set()
    for i, a in enumerate(logical_axes):
        ax = _mesh_axes(a, mesh, rules) if a is not None else None
        # a mesh axis may appear at most once per spec: first use wins
        if ax is not None:
            axes = ax if isinstance(ax, tuple) else (ax,)
            fresh = tuple(m for m in axes if m not in used)
            ax = fresh if len(fresh) > 1 else (fresh[0] if fresh else None)
        if ax is not None and shape is not None:
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for m in axes:
                size *= mesh.shape[m]
            if shape[i] % size != 0:
                # try a divisible prefix of the axis tuple
                kept = []
                prod = 1
                for m in axes:
                    if shape[i] % (prod * mesh.shape[m]) == 0:
                        kept.append(m)
                        prod *= mesh.shape[m]
                    else:
                        break
                ax = tuple(kept) if len(kept) > 1 else (
                    kept[0] if kept else None
                )
        if ax is not None:
            used.update(ax if isinstance(ax, tuple) else (ax,))
        out.append(ax)
    return P(*out)


def logical_sharding(
    logical_axes: tuple, mesh: Mesh | None = None, shape: tuple | None = None
):
    st = _ctx()
    mesh = mesh or st.mesh
    rules = st.rules or LOGICAL_RULES
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_spec(logical_axes, mesh, rules, shape))


def constrain(x: jax.Array, *logical_axes) -> jax.Array:
    """Attach a sharding constraint by logical axis names (no-op without
    an active mesh).  Shape-aware: non-divisible axes replicate."""
    sh = logical_sharding(tuple(logical_axes), shape=tuple(x.shape))
    if sh is None:
        return x
    return jax.lax.with_sharding_constraint(x, sh)


# ---------------------------------------------------------------------------
# Parameter path -> logical axes.  Paths are '/'-joined pytree key paths,
# e.g. "layers/attn/q_proj/w" (stacked layer leaves carry a leading
# "layers" axis).  First match wins.
# ---------------------------------------------------------------------------

PARAM_RULES: tuple[tuple[str, tuple], ...] = (
    # embeddings / head
    (r"embed/w$", ("vocab", "embed")),
    (r"lm_head/w$", ("fsdp", "vocab")),
    # attention projections: 2-D sharding (fsdp x tensor-parallel)
    (r"(q_proj|qkv_proj)/w$", ("fsdp", "heads")),
    (r"(k_proj|v_proj)/w$", ("fsdp", "heads")),
    (r"o_proj/w$", ("heads", "fsdp")),
    (r"(q_proj|qkv_proj|k_proj|v_proj)/b$", ("heads",)),
    # MoE: EP on the expert axis (model), FSDP on d_model
    (r"router/w$", ("embed", "experts")),
    (r"experts/(wi|wg)$", ("experts", "fsdp", None)),
    (r"experts/wo$", ("experts", None, "fsdp")),
    # gated MLP
    (r"mlp/(wi|wg)/w$", ("fsdp", "ffn")),
    (r"mlp/wo/w$", ("ffn", "fsdp")),
    (r"mlp/(wi|wg)/b$", ("ffn",)),
    (r"mlp/wo/b$", ()),
    # SSM projections: inner dim tensor-parallel, d_model FSDP
    (r"(in_proj|in_proj_z|x_proj)/w$", ("fsdp", "ffn")),
    (r"dt_proj/w$", (None, "ffn")),
    (r"out_proj/w$", ("ffn", "fsdp")),
    (r"conv/w$", (None, "ffn")),
    # rwkv6
    (r"(r_proj|k_proj_ssm|v_proj_ssm|g_proj)/w$", ("fsdp", "heads")),
    (r"wkv_out/w$", ("heads", "fsdp")),
    (r"(w_lora_a|w_lora_b)$", ()),
    # norms / scalars / small LoRA tables: replicate
    (r".*", ()),
)


def param_logical_axes(path: str, ndim: int) -> tuple:
    for pattern, axes in PARAM_RULES:
        if re.search(pattern, path):
            if not axes:
                return (None,) * ndim
            if len(axes) < ndim:
                # leading stacked-layer axes (scan) are unsharded
                return (None,) * (ndim - len(axes)) + tuple(axes)
            if len(axes) > ndim:
                return tuple(axes[-ndim:])
            return tuple(axes)
    return (None,) * ndim


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_sharding_rules(params, mesh: Mesh, rules: dict | None = None):
    """NamedSharding pytree for a parameter (or optimizer-state) pytree.

    Optimizer states nest params under e.g. "m/", "v/", "f/" — the rules
    match anywhere in the path, so states shard exactly like their
    parameters (ZeRO-1 falls out of pjit)."""
    rules = dict(LOGICAL_RULES if rules is None else rules)

    def leaf_sharding(path, leaf):
        axes = param_logical_axes(_path_str(path), leaf.ndim)
        return NamedSharding(
            mesh, logical_spec(axes, mesh, rules, tuple(leaf.shape))
        )

    return jax.tree_util.tree_map_with_path(leaf_sharding, params)


# ---------------------------------------------------------------------------
# Programmed-state shardings (weight-stationary serving, DESIGN.md §5/§6).
#
# A programmed pytree mirrors the params structure with the dense leaf dict
# {"w": ...} replaced by a PreparedWeight / FoldedWeight node, so each node
# inherits the partitioning of the dense weight it was programmed from —
# with one deliberate restriction.  Axis contract:
#
# * The OUTPUT (N) crossbar dim takes the dense weight's logical axis
#   (heads/ffn/vocab -> model for column-parallel projections,
#   fsdp -> (pod, data) for row-parallel ones like o_proj / mlp.wo), and
#   stacked expert axes shard like the dense expert stack
#   (experts -> model); layer-scan stack axes stay local.
# * The CONTRACTION (K) dim always stays LOCAL, even where the dense
#   weight shards it (fsdp/ZeRO-3).  Splitting K turns each decode GEMM
#   into partial sums + an all-reduce, which changes the float
#   accumulation order — sharded decode would no longer be bitwise
#   identical to replicated decode (the reuse contract,
#   tests/test_distributed.py).  Sharding N keeps every output element's
#   full-K dot product on exactly one device, so only data movement —
#   never arithmetic — differs from the replicated path.
# * The bit-slice axis of PreparedWeight.slices is always local (every
#   device holds all Sw significances of its crossbar columns —
#   recombination is per-element), and the sampled programming noise
#   rides the slice values, so it shards with them (jax's partitionable
#   threefry makes the sampled values sharding-invariant; repro enables
#   it at import).
#
# The slice stack divides at ELEMENT granularity like the dense weight
# (production N dims — 14x64 heads, 4864 ffn, 151936 vocab — divide the
# 16-way model axis, while their 128-wide CROSSBAR-BLOCK counts often do
# not); the per-block scale table additionally requires its block count
# (nn) to divide, so a scale entry is sharded only when its (bk, bn)
# tiles land on one device, and replicates otherwise (it is the small
# O(nk*nn) table — the HBM lives in the slices).  Non-divisible dims drop
# to replicated exactly like param_sharding_rules.
# ---------------------------------------------------------------------------


def _dense_logical_axes(base: str) -> tuple:
    """Logical axes of the dense weight a programmed node came from.

    ``base`` is the '/'-joined path of the PreparedWeight/FoldedWeight
    node (e.g. "blocks/seg0/attn/q_proj").  Dense 2-D weights live at
    ``base + "/w"``; MoE expert stacks match ``base`` directly
    (PARAM_RULES "experts/wi" has no "/w" suffix).  The catch-all rule is
    excluded — an unmatched node replicates via the empty tuple."""
    for cand in (base + "/w", base):
        for pattern, axes in PARAM_RULES[:-1]:
            if re.search(pattern, cand):
                return axes
    return ()


def programmed_sharding_rules(programmed, mesh: Mesh, rules: dict | None = None):
    """NamedSharding pytree for a programmed-state pytree.

    Accepts the output (or ``jax.eval_shape``) of
    :func:`repro.models.programmed.program_params` and returns a matching
    pytree of :class:`NamedSharding` usable as jit ``in_shardings`` /
    ``out_shardings`` — the step that lets weight-stationary serving keep
    per-device programmed HBM shrinking with the model axis instead of
    replicating every layer's crossbar state."""
    from repro.core.dpe import FoldedWeight, PreparedWeight

    rules = dict(LOGICAL_RULES if rules is None else rules)

    def lead_axes_for(stacked: tuple, lead: int) -> tuple:
        stacked = stacked[-lead:] if lead else ()
        return (None,) * (lead - len(stacked)) + stacked

    def node_sharding(path, node):
        axes = _dense_logical_axes(_path_str(path))
        # K local (bitwise-reuse contract, see module comment); N inherits
        kn = (None, axes[-1]) if len(axes) >= 2 else (None, None)
        stacked = tuple(axes[:-2])
        # t_prog programming timestamps (drift reference, DESIGN.md §5)
        # are O(1) scalars per node — replicated, whatever their
        # stack rank (scan / expert axes broadcast by program_params).
        def t_sh(t):
            return None if t is None else NamedSharding(mesh, P())

        if isinstance(node, FoldedWeight):
            # FoldedWeight is a plain (K, N) effective weight — no block
            # structure survives folding, so divide at element granularity
            lead = node.w_eff.ndim - 2
            spec = logical_spec(
                lead_axes_for(stacked, lead) + kn, mesh, rules,
                tuple(node.w_eff.shape),
            )
            return FoldedWeight(
                w_eff=NamedSharding(mesh, spec), t_prog=t_sh(node.t_prog)
            )
        lead = node.slices.ndim - 3  # layer-scan / expert-stack axes
        lead_axes = lead_axes_for(stacked, lead)
        nn = node.scale.shape[-1]
        spec_sl = logical_spec(
            lead_axes + (None,) + kn, mesh, rules, tuple(node.slices.shape)
        )
        # scale rows follow the slices' N sharding only when the shard
        # boundary is block-aligned (nn divides); else replicate the table
        n_ax = spec_sl[node.slices.ndim - 1]
        if n_ax is not None:
            size = 1
            for m in (n_ax if isinstance(n_ax, tuple) else (n_ax,)):
                size *= mesh.shape[m]
            if nn % size != 0:
                n_ax = None
        spec_sc = P(*(tuple(spec_sl)[:lead] + (None, n_ax)))
        return PreparedWeight(
            slices=NamedSharding(mesh, spec_sl),
            scale=NamedSharding(mesh, spec_sc),
            t_prog=t_sh(node.t_prog),
        )

    return jax.tree_util.tree_map_with_path(
        node_sharding,
        programmed,
        is_leaf=lambda x: isinstance(x, (PreparedWeight, FoldedWeight)),
    )


# ---------------------------------------------------------------------------
# Cache and batch shardings
# ---------------------------------------------------------------------------

CACHE_RULES: tuple[tuple[str, tuple], ...] = (
    (r"(^|/)pos$", ("batch",)),
    (r"/(k|v)$", ("layers", "batch", "kv_seq", None, "head_dim")),
    (r"/s$", ("layers", "batch", "heads", None, None)),
    (r"/x_prev$", ("layers", "batch", None)),
    (r"/h$", ("layers", "batch", "ffn", None)),
    (r"/conv$", ("layers", "batch", None, "ffn")),
    (r".*", ()),
)


def cache_sharding_rules(cache, mesh: Mesh, rules: dict | None = None):
    rules = dict(LOGICAL_RULES if rules is None else rules)

    def leaf_sharding(path, leaf):
        p = _path_str(path)
        for pattern, axes in CACHE_RULES:
            if re.search(pattern, p):
                if not axes:
                    axes = (None,) * leaf.ndim
                elif len(axes) != leaf.ndim:
                    axes = (None,) * (leaf.ndim - len(axes)) + tuple(axes) \
                        if len(axes) < leaf.ndim else tuple(axes[-leaf.ndim:])
                return NamedSharding(
                    mesh, logical_spec(axes, mesh, rules, tuple(leaf.shape))
                )
        raise AssertionError

    return jax.tree_util.tree_map_with_path(leaf_sharding, cache)


def batch_sharding_rules(batch, mesh: Mesh, rules: dict | None = None):
    rules = dict(LOGICAL_RULES if rules is None else rules)

    def leaf_sharding(path, leaf):
        axes = ("batch",) + (None,) * (leaf.ndim - 1)
        return NamedSharding(
            mesh, logical_spec(axes, mesh, rules, tuple(leaf.shape))
        )

    return jax.tree_util.tree_map_with_path(leaf_sharding, batch)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
