"""Program-once weight-stationary inference (DESIGN.md §5).

MemIntelli's inference semantics are weight-stationary: devices are
programmed once (``update_weight()``) and then reused for many analog
matmuls (§3.3-3.4).  The per-call forward path nevertheless re-runs the
whole weight pipeline — quantise + bit-slice + log-normal programming
noise — on every ``mem_linear`` call, so a 16-token decode re-programs
every crossbar 16 times.

:func:`program_params` walks the model pytree ONCE, resolves each
logical layer name through the :class:`~repro.core.layers.MemPolicy`,
and materialises the per-layer programmed state
(:class:`~repro.core.dpe.PreparedWeight` for faithful/circuit layers,
:class:`~repro.core.dpe.FoldedWeight` for fast layers, ``None`` for
digital ones) in a pytree that mirrors the params structure.  The
forward stack threads it down to every ``dense`` call, so the serving
hot path pays only ``prepare_input`` + the GEMM per token.

Equivalence contract (tests/test_programmed.py): the layer names and the
PRNG fold chain here MUST mirror ``model.forward`` / ``model.decode_step``
exactly, so for a fixed base ``rng`` the programmed state is the same
state the per-call path programs.  Programming once and reusing it is
bitwise identical to re-programming before every step (programming is a
deterministic pure function of ``(w, cfg, key)`` and the decode graph is
the same either way).  Against the legacy *inline* per-call graph
(programming fused into the forward HLO) the math is identical but XLA
fuses the two different programs differently, so logits agree to float-
fusion rounding (~1 ulp) — greedy-decoded tokens are asserted equal.
Training keeps per-call programming: fresh noise per ``update_weight()``
step is the paper's semantics.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.dpe import program_weight
from repro.core.layers import MemPolicy, layer_key

from .config import ArchConfig
from .model import segments
from .transformer import group_size

__all__ = ["program_params", "programmed_byte_size"]


def _prog_dense(p: dict, name: str, rng, policy: MemPolicy, t_prog):
    """Programmed state for one dense param dict ({"w": ..}) or None."""
    cfg = policy.config_for(name)
    if cfg is None or cfg.mode == "digital":
        return None
    return program_weight(p["w"], cfg, layer_key(rng, name), t_prog)


def _prog_attn(p: dict, name: str, rng, policy: MemPolicy, t_prog):
    return {
        pk: _prog_dense(p[pk], f"{name}.{suffix}", rng, policy, t_prog)
        for pk, suffix in (
            ("q_proj", "q"),
            ("k_proj", "k"),
            ("v_proj", "v"),
            ("o_proj", "o"),
        )
        if pk in p
    }


_RWKV6_PROJ = (
    ("r_proj", "r"),
    ("k_proj_ssm", "k"),
    ("v_proj_ssm", "v"),
    ("g_proj", "g"),
    ("wkv_out", "o"),
)
_MAMBA_PROJ = (
    ("in_proj", "in"),
    ("in_proj_z", "z"),
    ("x_proj", "xp"),
    ("dt_proj", "dt"),
    ("out_proj", "out"),
)


def _prog_ssm(p: dict, name: str, rng, policy: MemPolicy, t_prog):
    table = _RWKV6_PROJ if "r_proj" in p else _MAMBA_PROJ
    return {
        pk: _prog_dense(p[pk], f"{name}.{suffix}", rng, policy, t_prog)
        for pk, suffix in table
    }


def _prog_moe(p: dict, name: str, rng, policy: MemPolicy, t_prog):
    out = {
        "router": _prog_dense(
            p["router"], f"{name}.router", rng, policy, t_prog
        )
    }
    mem_cfg = policy.config_for(f"{name}.experts")
    if mem_cfg is not None and mem_cfg.mode != "digital":
        # mirror moe_block's per-expert key schedule: fold_in(key, i) with
        # i in [0,E) for wi, [E,2E) for wg, [2E,3E) for wo
        key = layer_key(rng, f"{name}.experts")
        e = p["experts"]["wi"].shape[0]

        def stack(w, i0):
            return jax.vmap(
                lambda w2, i: program_weight(
                    w2, mem_cfg, jax.random.fold_in(key, i), t_prog
                )
            )(w, jnp.arange(e) + i0)

        out["experts"] = {
            "wi": stack(p["experts"]["wi"], 0),
            "wg": stack(p["experts"]["wg"], e),
            "wo": stack(p["experts"]["wo"], 2 * e),
        }
    return out


def _prog_ffn(p: dict, name: str, rng, policy: MemPolicy, t_prog):
    if "moe" in p:
        return {"moe": _prog_moe(p["moe"], name, rng, policy, t_prog)}
    mlp = p["mlp"]
    return {
        "mlp": {
            k: _prog_dense(mlp[k], f"{name}.mlp.{k}", rng, policy, t_prog)
            for k in ("wi", "wg", "wo")
        }
    }


def _prog_layer(
    p: dict, cfg: ArchConfig, layer_idx: int, rng, policy, t_prog
):
    kind, _ = cfg.layer_kind(layer_idx)
    name = f"L.{kind}"
    out = {}
    if kind == "attn":
        out["attn"] = _prog_attn(p["attn"], name, rng, policy, t_prog)
    else:
        out["ssm"] = _prog_ssm(p["ssm"], name, rng, policy, t_prog)
    out.update(_prog_ffn(p, name, rng, policy, t_prog))
    return out


def _prog_block(
    p: dict, cfg: ArchConfig, template_idx: int, rng, policy, t_prog
):
    """One scan step (a single layer or a hybrid group) — mirrors
    ``block_forward``'s structure and its shared-rng group convention."""
    g = group_size(cfg)
    if g == 1:
        return _prog_layer(p, cfg, template_idx, rng, policy, t_prog)
    return {
        f"l{j}": _prog_layer(p[f"l{j}"], cfg, j, rng, policy, t_prog)
        for j in range(g)
    }


def _prog_segment(seg_p, cfg, tmpl, rng_seg, policy, t_prog):
    """Program a stacked segment: vmap over the scan (steps) axis with the
    per-step key fold ``fold_in(rng_seg, idx)`` used by the forward scan.
    A scalar ``t_prog`` is broadcast onto the stack axis by vmap, so the
    stamped leaf stays scan-compatible with the stacked slices."""
    steps = jax.tree_util.tree_leaves(seg_p)[0].shape[0]
    return jax.vmap(
        lambda p, i: _prog_block(
            p, cfg, tmpl, jax.random.fold_in(rng_seg, i), policy, t_prog
        )
    )(seg_p, jnp.arange(steps))


def _prog_encdec(params, cfg, rng, policy, t_prog):
    nenc = cfg.encoder.n_layers

    def one_enc(p, i):
        return {
            "attn": _prog_attn(
                p["attn"], "enc.attn", jax.random.fold_in(rng, 1000 + i),
                policy, t_prog,
            ),
            "mlp": _prog_ffn(
                p, "enc", jax.random.fold_in(rng, 2000 + i), policy, t_prog
            )["mlp"],
        }

    def one_dec(p, i):
        return _prog_block(
            p, cfg, 0, jax.random.fold_in(rng, i), policy, t_prog
        )

    def one_cross(p, i):
        return _prog_attn(
            p, "dec.cross", jax.random.fold_in(rng, i), policy, t_prog
        )

    nl = cfg.n_layers
    return {
        "encoder": {
            "blocks": jax.vmap(one_enc)(
                params["encoder"]["blocks"], jnp.arange(nenc)
            )
        },
        "blocks": {
            "seg0": jax.vmap(one_dec)(
                params["blocks"]["seg0"], jnp.arange(nl)
            )
        },
        "cross": jax.vmap(one_cross)(params["cross"], jnp.arange(nl)),
        "lm_head": _prog_dense(
            params["lm_head"], "lm_head", rng, policy, t_prog
        ),
    }


def _program_params_body(
    params, cfg: ArchConfig, policy: MemPolicy, rng, t_prog=None
):
    if cfg.encoder is not None:
        return _prog_encdec(params, cfg, rng, policy, t_prog)
    prog = {"blocks": {}}
    for si, (start, steps, tmpl) in enumerate(segments(cfg)):
        prog["blocks"][f"seg{si}"] = _prog_segment(
            params["blocks"][f"seg{si}"], cfg, tmpl,
            jax.random.fold_in(rng, si), policy, t_prog,
        )
    prog["lm_head"] = _prog_dense(
        params["lm_head"], "lm_head", rng, policy, t_prog
    )
    return prog


# the programming pass runs as the module ``jit__program_params_body``:
# the benchmark reads that name in the device trace
_program_params_impl = partial(jax.jit, static_argnums=(1, 2))(
    _program_params_body
)


def program_params(
    params,
    cfg: ArchConfig,
    policy: MemPolicy | None,
    rng=None,
    *,
    out_shardings=None,
    mesh=None,
    t_prog=0.0,
):
    """Program every hardware layer of a model once (weight-stationary).

    Walks the model pytree, resolves each layer name through ``policy``
    and materialises its programmed state next to the digital params.
    Returns a pytree mirroring ``params`` (PreparedWeight / FoldedWeight
    leaves; ``None`` for digital layers and non-matmul params), or
    ``None`` when the policy has no hardware layers at all.

    ``rng`` must equal the base rng the forward/decode calls will use
    (serving uses ``PRNGKey(0)``) so the programmed state matches what
    the per-call path would program.  The pass is jitted with static
    ``(cfg, policy)`` — programming the whole model is one fused XLA
    program, and repeated calls with the same key return bit-identical
    state (the re-program-only-when-the-key-changes contract).

    Mesh-aware deployments pass ``out_shardings`` (a pytree of
    ``NamedSharding`` from
    :func:`repro.distributed.sharding.programmed_sharding_rules`) or just
    ``mesh`` (the rules are resolved here) so programming LOWERS sharded:
    every leaf materialises directly in its decode-time layout instead of
    replicate-then-reshard, and per-device programmed HBM shrinks with
    the model axis (DESIGN.md §6).

    ``t_prog`` is the device-clock programming time stamped onto every
    programmed node (the drift reference a refresh advances; DESIGN.md
    §5).  It is a traced scalar — re-programming at a new time re-runs
    the SAME compiled program — and defaults to 0.0 (generation zero).
    Pass ``t_prog=None`` for untimed state with the pre-drift leaf
    structure.
    """
    rng = jax.random.PRNGKey(0) if rng is None else rng
    if policy is None or not policy.enabled:
        return None
    if t_prog is not None:
        t_prog = jnp.asarray(t_prog, jnp.float32)
    if out_shardings is None and mesh is not None:
        from repro.distributed.sharding import programmed_sharding_rules

        prog_abs = jax.eval_shape(
            lambda p, r, t: _program_params_body(p, cfg, policy, r, t),
            params, rng, t_prog,
        )
        out_shardings = programmed_sharding_rules(prog_abs, mesh)
    fn = _program_params_impl
    if out_shardings is not None:
        fn = jax.jit(
            _program_params_body, static_argnums=(1, 2),
            out_shardings=out_shardings,
        )
    with jax.profiler.TraceAnnotation("program.pass"):
        return fn(params, cfg, policy, rng, t_prog)


def programmed_byte_size(programmed, shardings=None) -> int:
    """Bytes of resident programmed state (capacity planning).

    Without ``shardings`` this is the global (replicated per-device)
    footprint.  With a matching pytree of ``NamedSharding`` — e.g. from
    :func:`repro.distributed.sharding.programmed_sharding_rules` — it is
    the PER-DEVICE footprint: each leaf contributes its shard size, so
    the return value is what one device actually keeps resident."""
    if programmed is None:
        return 0
    leaves = jax.tree_util.tree_leaves(programmed)
    if shardings is None:
        return sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)
    sh_leaves = jax.tree_util.tree_leaves(shardings)
    assert len(sh_leaves) == len(leaves), "shardings must mirror programmed"
    total = 0
    for leaf, sh in zip(leaves, sh_leaves):
        shard = sh.shard_shape(tuple(leaf.shape))
        n = 1
        for s in shard:
            n *= s
        total += n * leaf.dtype.itemsize
    return total
