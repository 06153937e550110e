"""Chip smoke: the serving path of this repo, brought up on a TPU and checked.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --four-chips      # the sharded path, four chips

One chip runs three phases on qwen2-0.5b at its published widths, with
random weights made from ``--seed``:

(a) device: the platform is a TPU, the Pallas kernels are compiled by
    Mosaic (no interpret mode) and every ``auto`` backend resolves to
    them;
(b) kernels against their XLA oracles on the chip: the fused bit-sliced
    GEMM at the q/o, up, down and lm_head shapes, and the paged decode
    and chunk attention kernels with f32 and bf16 caches, each within
    the bound DESIGN.md states for its class;
(c) the main path: ``ServeLoop`` serves 16 requests under ``mem_faithful``
    with the kernels on, then the same requests on the same programmed
    state with the kernels off (the XLA engine); first-token logits agree
    within the stated bound and every request completes.

``--four-chips`` runs only the sharded path: ``mem_fast`` programmed
state sharded over a (1, 4) mesh against the same requests served with
replicated state on one chip.

The last line of standard output is one JSON object naming the device;
it is printed only when every phase passed.  Without a TPU, or without
the repository beside this file, the script exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Bounds of the compiled-Mosaic vs XLA classes (DESIGN.md §4, §7).  The
# GEMM class is stated in relative Frobenius error ||y - ref|| / ||ref||
# (ADC code flips at rounding ties, with programming noise); the others
# in max rel error max|y - ref| / max|ref| over the whole output.
GEMM_BOUND = 5e-3
ATTN_BOUND = {"float32": 1e-4, "bfloat16": 1e-2}  # f32 order; bf16 ulps
LOGITS_BOUND = 1e-1  # one model, two programs: flips compound over layers

# the main-path workload (c)
REQUESTS, GEN, PROMPT_LENS = 16, 32, (64, 512)
SLOTS, CHUNK, BLOCK, MAX_LEN = 8, 128, 16, 1024
GEMM_ROWS = (8, 128)


class SmokeFailure(Exception):
    pass


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


def _f64(*arrays):
    import numpy as np

    return [np.asarray(a, np.float64) for a in arrays]


def max_rel(out, ref):
    import numpy as np

    out, ref = _f64(out, ref)
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


def rel_fro(out, ref):
    import numpy as np

    out, ref = _f64(out, ref)
    return float(np.linalg.norm(out - ref) / max(np.linalg.norm(ref), 1e-30))


def serving_policy(mode):
    """The policy ``launch/serve.py`` serves ``--requests`` with."""
    from repro.launch.dryrun import make_policy
    from repro.launch.serve import _row_independent

    return _row_independent(make_policy(mode))


# --------------------------------------------------------------------------
# (a) device
# --------------------------------------------------------------------------


def phase_device(policy, cache_dir):
    import jax

    from repro.core.dpe import resolve_backend
    from repro.kernels import ops as kops

    devs = jax.devices()
    print(f"[device] platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)} "
          f"compile_cache={cache_dir}")
    check(not kops.kernel_interpret(), "kernels would run interpreted")
    check(resolve_backend(policy.default) == "pallas",
          "faithful DPE backend does not resolve to pallas")
    check(kops.resolve_attention_backend() == "pallas",
          "paged attention backend does not resolve to pallas")


# --------------------------------------------------------------------------
# (b) kernels against their XLA oracles
# --------------------------------------------------------------------------


def phase_gemm(cfg, policy, seed):
    import functools

    import jax

    from repro.core.dpe import dpe_apply, program_weight

    dcfg = policy.default
    apply = {
        backend: jax.jit(functools.partial(
            dpe_apply, cfg=dcfg.replace(backend=backend)
        ), static_argnames="n")
        for backend in ("pallas", "xla")
    }
    shapes = (
        ("q/o", cfg.d_model, cfg.n_heads * cfg.head_dim),
        ("up", cfg.d_model, cfg.d_ff),
        ("down", cfg.d_ff, cfg.d_model),
        ("lm_head", cfg.d_model, cfg.vocab),
    )
    key = jax.random.PRNGKey(seed)
    for name, k, n in shapes:
        kw, kn, kx = jax.random.split(jax.random.fold_in(key, k * 7 + n), 3)
        w = 0.02 * jax.random.normal(kw, (k, n))
        pw = program_weight(w, dcfg, kn)
        for m in GEMM_ROWS:
            x = jax.random.normal(jax.random.fold_in(kx, m), (m, k))
            y_k = apply["pallas"](x, pw, n=n)
            y_x = apply["xla"](x, pw, n=n)
            err = rel_fro(y_k, y_x)
            print(f"[kernels] fused GEMM {name:7s} {k}x{n} M={m:3d}: "
                  f"rel Fro err {err:.3e} (bound {GEMM_BOUND:g}), max rel "
                  f"err {max_rel(y_k, y_x):.3e}")
            check(err <= GEMM_BOUND, f"fused GEMM {name} M={m}: {err:.3e}")
        del pw


def phase_attention(cfg, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops
    from repro.kernels.paged_attention import (
        paged_chunk_attention,
        paged_decode_attention,
    )
    from repro.models.attention import (
        _paged_gather,
        attention_decode,
        attention_dense,
    )

    nb = MAX_LEN // BLOCK
    n_blocks = SLOTS * nb + 1
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(seed)
    bt = jnp.asarray(
        (1 + rng.permutation(n_blocks - 1)).reshape(SLOTS, nb), jnp.int32
    )
    pos = jnp.asarray(rng.integers(0, MAX_LEN, SLOTS), jnp.int32)
    start = MAX_LEN - CHUNK - 37
    interpret = kops.kernel_interpret()
    # a stacked three-layer arena, attended at its middle layer
    layers, layer = 3, jnp.int32(1)
    for dtype in (jnp.float32, jnp.bfloat16):
        ks = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
        pool = lambda k: jax.random.normal(
            k, (layers, n_blocks, BLOCK, kvh * hd)
        ).astype(dtype)
        pk, pv = pool(ks[0]), pool(ks[1])
        q = jax.random.normal(ks[2], (SLOTS, h, hd)).astype(dtype)
        qc = jax.random.normal(ks[3], (1, CHUNK, h, hd)).astype(dtype)
        out_d = paged_decode_attention(q, pk, pv, layer, bt, pos,
                                       interpret=interpret)
        out_c = paged_chunk_attention(
            qc, pk, pv, layer, bt[0], jnp.int32(start), jnp.int32(CHUNK),
            interpret=interpret,
        )
        gk = _paged_gather(pk, layer, bt, hd)
        gv = _paged_gather(pv, layer, bt, hd)
        ref_d = attention_decode(q, gk, gv, pos)
        ref_c = attention_dense(qc, gk[:1], gv[:1], q_off=start)
        bound = ATTN_BOUND[jnp.dtype(dtype).name]
        for name, out, ref in (("decode", out_d, ref_d),
                               ("chunk", out_c, ref_c)):
            err = max_rel(out, ref)
            print(f"[kernels] paged {name:6s} {jnp.dtype(dtype).name:8s} "
                  f"max_len={MAX_LEN}: max rel err {err:.3e} "
                  f"(bound {bound:g})")
            check(err <= bound, f"paged {name} {dtype}: {err:.3e}")


# --------------------------------------------------------------------------
# (c) the main path
# --------------------------------------------------------------------------


def make_requests(cfg, seed):
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, REQUESTS)
    return [
        Request(
            rid=i,
            tokens=rng.integers(0, cfg.vocab, int(n)).astype(np.int32),
            max_new_tokens=GEN,
        )
        for i, n in enumerate(lens)
    ]


def serve(params, cfg, config, programmed, requests, label):
    """One ServeLoop run as ``launch/serve.py`` makes it: a short warm-up
    pass over the same prompts compiles every step, then the timed run."""
    import numpy as np

    from repro.serve import Request, ServeLoop

    t = time.perf_counter()
    loop = ServeLoop(params, cfg, config, programmed=programmed)
    loop.run([
        Request(rid=-1 - r.rid, tokens=r.tokens, max_new_tokens=2)
        for r in requests
    ])
    compile_s = time.perf_counter() - t
    report = loop.run(requests)
    done = report.completed()
    print(f"[serve] {label}: compile+warm-up {compile_s:.1f}s, "
          f"{len(done)}/{len(requests)} requests, {report.generated_tokens} "
          f"tokens in {report.wall_s:.2f}s = {report.tok_per_s:.1f} tok/s "
          "(smoke figure, not a benchmark)")
    check(len(done) == len(requests), f"{label}: requests did not complete")
    for r in done:
        check(len(r.tokens) == GEN, f"{label}: request {r.rid} emitted "
              f"{len(r.tokens)} tokens")
        check(all(np.isfinite(row).all() for row in r.logits),
              f"{label}: request {r.rid} has non-finite logits")
    return loop, {r.rid: r for r in done}


def decode_hlo(loop, cfg):
    """The decode step's compiled program, as the loop runs it."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import init_paged_cache

    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, loop.slots, loop.max_len, loop.block_size, loop.kv_blocks,
        loop.cache_dtype,
    ))
    tokens = jax.ShapeDtypeStruct((loop.slots,), jnp.int32)
    active = jax.ShapeDtypeStruct((loop.slots,), jnp.bool_)
    return loop._decode.lower(
        loop.params, cache, tokens, loop.programmed, active, None
    ).compile().as_text()


def phase_serve(cfg, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops
    from repro.models import init_params, program_params
    from repro.serve import ServeConfig

    policy = serving_policy("mem_faithful")
    params = init_params(cfg, jax.random.PRNGKey(seed))
    t = time.perf_counter()
    programmed = program_params(params, cfg, policy, jax.random.PRNGKey(0))
    jax.block_until_ready(programmed)
    print(f"[serve] programmed mem_faithful state in "
          f"{time.perf_counter() - t:.1f}s (compile included)")
    requests = make_requests(cfg, seed)
    config = ServeConfig(
        policy=policy, slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK,
        block_size=BLOCK, compute_dtype=jnp.float32, collect_logits=True,
    )
    loop, kern = serve(params, cfg, config, programmed, requests,
                       "kernels on")
    check("tpu_custom_call" in decode_hlo(loop, cfg),
          "the decode step runs no Mosaic kernel")
    print("[serve] decode step HLO contains tpu_custom_call")
    del loop
    prev = kops.set_kernels_enabled(False)
    try:
        _, xla = serve(params, cfg, config, programmed, requests,
                       "kernels off (XLA engine)")
    finally:
        kops.set_kernels_enabled(prev)
    errs = [max_rel(kern[i].logits[0], xla[i].logits[0]) for i in kern]
    same = sum(
        int(np.sum(np.asarray(kern[i].tokens) == np.asarray(xla[i].tokens)))
        for i in kern
    )
    print(f"[serve] first-token logits, kernels vs XLA: max rel err "
          f"{max(errs):.3e} (bound {LOGITS_BOUND:g}); token agreement "
          f"{same}/{REQUESTS * GEN}")
    check(max(errs) <= LOGITS_BOUND, f"first-token logits: {max(errs):.3e}")


# --------------------------------------------------------------------------
# --four-chips: sharded programmed state
# --------------------------------------------------------------------------


def phase_four_chips(cfg, seed):
    """Sharded vs replicated programmed state: the state itself must be
    bitwise equal (sharding moves data), one column-parallel GEMM within
    its output dtype's rounding, the served logits within the two-program
    class of phase (c)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.dpe import dpe_apply
    from repro.launch.mesh import make_test_mesh
    from repro.models import init_params, program_params
    from repro.serve import ServeConfig

    check(len(jax.devices()) >= 4, "--four-chips needs four devices")
    policy = serving_policy("mem_fast")
    mesh = make_test_mesh((1, 4))
    params = init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(0)
    state = {
        "sharded": program_params(params, cfg, policy, key, mesh=mesh),
        "replicated": program_params(params, cfg, policy, key),
    }
    leaves = [jax.tree.leaves(p) for p in state.values()]
    same_state = all(np.array_equal(a, b) for a, b in zip(*leaves))
    # layer 0's mlp.wi: column-parallel (ffn -> model), bf16 store dtype
    wi = {k: jax.tree.map(lambda a: a[0], p["blocks"]["seg0"]["mlp"]["wi"])
          for k, p in state.items()}
    apply = jax.jit(functools.partial(dpe_apply, cfg=policy.default),
                    static_argnames="n")
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (SLOTS, cfg.d_model))
    y = {k: apply(x, w, n=cfg.d_ff) for k, w in wi.items()}
    gemm_err = max_rel(y["sharded"], y["replicated"])
    # mem_fast folds its weights into bf16 (make_policy): the GEMM output
    # carries bf16 rounding, the class of a bf16 attention output
    gemm_bound = ATTN_BOUND["bfloat16"]
    print(f"[four-chips] programmed state sharded vs replicated: bitwise="
          f"{same_state}; layer-0 mlp.wi GEMM: bitwise="
          f"{bool(np.array_equal(y['sharded'], y['replicated']))} max rel "
          f"err {gemm_err:.3e} (bound {gemm_bound:g})")
    check(same_state, "sharded programming changed the programmed state")
    check(gemm_err <= gemm_bound, f"sharded GEMM: {gemm_err:.3e}")

    requests = make_requests(cfg, seed)
    config = ServeConfig(
        policy=policy, slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK,
        block_size=BLOCK, compute_dtype=jnp.float32, collect_logits=True,
    )
    _, sharded = serve(params, cfg, config.replace(mesh=mesh),
                       state["sharded"], requests,
                       "mem_fast sharded over 4 chips")
    _, single = serve(params, cfg, config, state["replicated"], requests,
                      "mem_fast replicated on one chip")
    rows = [
        (a, b)
        for i in single
        for a, b in zip(sharded[i].logits, single[i].logits)
    ]
    bitwise = all(np.array_equal(a, b) for a, b in rows)
    err = max(max_rel(sharded[i].logits[0], single[i].logits[0])
              for i in single)
    same = sum(sharded[i].tokens == single[i].tokens for i in single)
    print(f"[four-chips] logits sharded vs replicated: bitwise={bitwise} "
          f"over {len(rows)} rows; first-token max rel err {err:.3e} "
          f"(bound {LOGITS_BOUND:g}); identical token streams "
          f"{same}/{REQUESTS}")
    check(err <= LOGITS_BOUND, f"sharded first-token logits: {err:.3e}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, data and requests")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded programmed-state path")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devs[0].platform})",
              file=sys.stderr)
        return 2
    from repro.configs import get
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    cfg = get("qwen2-0.5b")
    try:
        if args.four_chips:
            phase_four_chips(cfg, args.seed)
        else:
            phase_device(serving_policy("mem_faithful"), cache_dir)
            phase_gemm(cfg, serving_policy("mem_faithful"), args.seed)
            phase_attention(cfg, args.seed)
            phase_serve(cfg, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    stats = devs[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
