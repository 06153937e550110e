"""Serving driver: batched greedy generation through the (optionally
memristive) model.

Weight-stationary by default (DESIGN.md §5): the model is programmed
once via ``program_params`` and every decode step reuses the resident
crossbar state.  ``--per_call`` reverts to the legacy inline
re-programming path (the paper's training-time semantics) — useful for
measuring what program-once buys:

    PYTHONPATH=src python -m repro.launch.serve \
        --arch rwkv6-1.6b --smoke --batch 4 --prompt_len 16 --gen 16 \
        --policy mem_fast

With ``--requests N`` the driver switches to the continuous-batching
engine (``serve/batching.py``, DESIGN.md §7): N variable-length requests
stream through a ``--slots K`` slot table backed by a paged KV arena
(``--block_size``/``--kv_blocks``) against ONE shared programmed state,
prompts prefilled in ``--prefill_chunk``-token chunks interleaved with
decode steps, optionally with Poisson arrivals.  The report splits
latency into time-to-first-token (queueing + chunked prefill) and
inter-token latency (decode-phase smoothness):

    PYTHONPATH=src python -m repro.launch.serve \
        --smoke --policy mem_fast --requests 8 --slots 4 \
        --arrival poisson --rate 20 --prefill_chunk 16

``--priority_mix F`` tags a fraction F of the requests as
``priority="interactive"``; the class-aware admission scheduler
(``--interactive_weight``, ``--max_queue_skip``, DESIGN.md §7) then
protects their TTFT from the batch traffic and the report breaks
TTFT/ITL out per class:

    PYTHONPATH=src python -m repro.launch.serve \
        --smoke --policy mem_fast --requests 16 --slots 2 \
        --priority_mix 0.5 --arrival poisson --rate 50

Numerics contract (DESIGN.md §7): every request's tokens are identical
to solo ``greedy_generate`` on that prompt; none of the knobs here
(slots, chunk size, block size, arrival order) change a logit bit on
the fast path.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as arch_configs
from repro.core import DriftModel
from repro.launch.compile_cache import use_compile_cache
from repro.launch.dryrun import make_policy
from repro.models import init_params, program_params, programmed_byte_size
from repro.serve import (
    Request, SamplingParams, ServeConfig, ServeLoop, greedy_generate,
)


def _onoff(ap, name, default, help):
    # normalized boolean flag convention: --flag / --flag on / --flag off
    ap.add_argument(name, nargs="?", const="on", default=default,
                    choices=("on", "off"), help=help)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    _onoff(ap, "--smoke", "off", "tiny smoke-sized architecture")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt_len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--policy", default="digital",
                    choices=["digital", "mem_fast", "mem_faithful"])
    _onoff(ap, "--per_call", "off",
           "re-program every call (legacy path) instead of programming "
           "once")
    ap.add_argument("--shard_model", type=int, default=None,
                    help="shard the programmed state over N local devices "
                         "(model mesh axis, programmed_sharding_rules); "
                         "default replicated.  A CPU host shows N devices "
                         "only under XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N, set before the launch")
    ap.add_argument("--requests", type=int, default=0,
                    help="serve N variable-length requests through the "
                         "continuous-batching engine instead of one "
                         "lockstep batch")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-slot count of the continuous-batching "
                         "engine")
    ap.add_argument("--arrival", default="all",
                    choices=["all", "poisson"],
                    help="request arrival process: all at t=0, or Poisson "
                         "with --rate")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--max_len", type=int, default=None,
                    help="KV arena length per slot (default: fitted to "
                         "the workload)")
    ap.add_argument("--prefill_chunk", type=int, default=32,
                    help="prefill chunk length in tokens (0 = unchunked: "
                         "one bucket-padded chunk per prompt)")
    ap.add_argument("--block_size", type=int, default=16,
                    help="paged KV arena block size in tokens")
    ap.add_argument("--kv_blocks", type=int, default=None,
                    help="total paged-arena blocks (default: slots x "
                         "ceil(max_len/block_size) + trash block)")
    ap.add_argument("--priority_mix", type=float, default=0.0,
                    help="fraction of requests tagged priority="
                         "'interactive' (rest are 'batch'); the "
                         "class-aware scheduler protects interactive "
                         "TTFT from batch floods (DESIGN.md §7)")
    ap.add_argument("--interactive_weight", type=int, default=4,
                    help="weighted round-robin share of the interactive "
                         "class: consecutive interactive admissions "
                         "before one batch request goes first under "
                         "contention")
    ap.add_argument("--max_queue_skip", type=int, default=8,
                    help="aging bound: max later-submitted requests ever "
                         "admitted ahead of a waiting one (0 = strict "
                         "submit-order FIFO, the pre-scheduler behaviour)")
    ap.add_argument("--prefix_cache", nargs="?", const="on", default="on",
                    choices=("on", "off"),
                    help="refcounted prefix block cache (DESIGN.md §7): "
                         "shared prompt prefixes map to resident KV "
                         "blocks and skip their prefill chunks; 'off' "
                         "reverts to the plain free-list allocator")
    ap.add_argument("--shared_prefix", type=int, default=0,
                    help="prepend a common N-token preamble to every "
                         "request's prompt (system-prompt simulation — "
                         "what the prefix cache deduplicates)")
    ap.add_argument("--sample", type=float, default=0.0,
                    help="sampling temperature for the served requests "
                         "(0 = greedy).  Per-request seeds: request i "
                         "draws with fold_in(PRNGKey(seed_base + i), "
                         "emission_index), so tokens are identical to "
                         "solo decoding whatever the packing")
    ap.add_argument("--top_k", type=int, default=0,
                    help="top-k truncation for --sample (0 = off)")
    ap.add_argument("--top_p", type=float, default=1.0,
                    help="nucleus truncation for --sample (1.0 = off)")
    ap.add_argument("--sample_seed", type=int, default=0,
                    help="base of the per-request sampling seeds")
    ap.add_argument("--spec_k", type=int, default=0,
                    help="speculative decoding: draft tokens proposed "
                         "per slot per round (0 = off).  The draft "
                         "engine proposes, the programmed target "
                         "verifies all k+1 positions in one batched "
                         "forward; emitted tokens are EXACTLY the "
                         "non-speculative trajectory")
    ap.add_argument("--draft_policy", default="digital",
                    choices=["digital", "mem_fast", "mem_faithful"],
                    help="numerics of the speculative draft engine "
                         "(folded from the same params; digital = the "
                         "cheap software draft)")
    ap.add_argument("--kernels", default="auto",
                    choices=("auto", "off", "interpret", "on"),
                    help="Pallas serving kernels: auto (on iff TPU), off "
                         "(XLA oracle paths), interpret (force the "
                         "kernels in interpret mode — CPU CI / "
                         "differential debugging), on (force compiled)")
    ap.add_argument("--refresh_every", type=float, default=None,
                    help="device-clock seconds between background "
                         "crossbar re-programs (generation N+1 swapped "
                         "in at request boundaries; default: never)")
    _onoff(ap, "--drift", "off",
           "conductance drift on the programmed state (power-law decay "
           "aged by the device clock; see also --drift_nu/--drift_t0)")
    ap.add_argument("--drift_nu", type=float, default=0.05,
                    help="power-law drift exponent nu")
    ap.add_argument("--drift_t0", type=float, default=1.0,
                    help="power-law drift reference time t0 (seconds)")
    args = ap.parse_args(argv)
    args.smoke = args.smoke == "on"
    args.per_call = args.per_call == "on"
    if args.kernels != "auto":
        from repro.kernels import ops as _kops

        if args.kernels == "off":
            _kops.set_kernels_enabled(False)
        elif args.kernels == "interpret":
            _kops.set_interpret(True)
        else:  # "on": compiled kernels even off-TPU (will fail on CPU)
            _kops.set_kernels_enabled(True)
            _kops.set_interpret(False)
    shard_model = args.shard_model or 0
    use_compile_cache()

    cfg = (
        arch_configs.get_smoke(args.arch)
        if args.smoke
        else arch_configs.get(args.arch)
    )
    policy = make_policy(args.policy)
    if args.requests:
        policy = _row_independent(policy)
    if args.drift == "on":
        policy = _with_drift(policy, args.drift_nu, args.drift_t0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab
    )
    extra = {}
    if cfg.vision_prefix:
        extra["patch_embeds"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.vision_prefix, cfg.d_model),
        )
    if cfg.encoder is not None:
        extra["frames"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(3),
            (args.batch, cfg.encoder.n_frames, cfg.d_model),
        )
    mesh = None
    if shard_model > 1:
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh((1, shard_model))
    programmed = None
    if not args.per_call and policy.enabled:
        t0 = time.time()
        sh = None
        if mesh is not None:
            from repro.distributed.sharding import programmed_sharding_rules

            prog_abs = jax.eval_shape(
                lambda: program_params(
                    params, cfg, policy, jax.random.PRNGKey(0)
                )
            )
            sh = programmed_sharding_rules(prog_abs, mesh)
        programmed = program_params(
            params, cfg, policy, jax.random.PRNGKey(0), out_shardings=sh
        )
        jax.block_until_ready(jax.tree.leaves(programmed))
        mb = programmed_byte_size(programmed) / 1e6
        print(f"programmed {mb:.1f} MB of crossbar state in "
              f"{time.time() - t0:.2f}s")
        if sh is not None:
            per = programmed_byte_size(programmed, sh) / 1e6
            print(f"sharded over {shard_model} devices: "
                  f"{per:.1f} MB/device resident")
    if args.requests:
        return _serve_continuous(args, cfg, policy, params, programmed, mesh)
    t0 = time.time()
    out = greedy_generate(
        params, cfg, prompts, args.gen, policy=policy,
        compute_dtype=jnp.float32, extra_batch=extra or None,
        programmed=programmed,
        weight_stationary=not args.per_call,
        mesh=mesh,
    )
    dt = time.time() - t0
    mode = "per-call" if args.per_call else "programmed"
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({args.batch*args.gen/dt:.1f} tok/s, {mode})")
    print("sample:", out[0][:16].tolist())
    return out


def _row_independent(policy):
    """Continuous batching requires row-independent numerics: remap any
    faithful batch-coupled ``adc_mode="dynamic"`` config to
    ``"dynamic_row"`` (per-analog-read ranging — the serving semantics,
    DESIGN.md §7) before the model is programmed."""
    from dataclasses import replace as dc_replace

    def fix(c):
        if c is not None and not c.row_independent:
            print(f"[serve] {c.mode} adc_mode=dynamic -> dynamic_row "
                  "(continuous batching needs row-independent numerics)")
            return c.replace(adc_mode="dynamic_row")
        return c

    return dc_replace(
        policy,
        default=fix(policy.default),
        overrides=tuple((pat, fix(c)) for pat, c in policy.overrides),
    )


def _with_drift(policy, nu, t0):
    """Attach a power-law conductance :class:`DriftModel` to every DPE
    config of the policy — programmed state then ages by the serve
    loop's device clock until the next re-program (DESIGN.md §5)."""
    from dataclasses import replace as dc_replace

    drift = DriftModel(kind="power", nu=nu, t0=t0)
    fix = lambda c: None if c is None else c.replace(drift=drift)
    return dc_replace(
        policy,
        default=fix(policy.default),
        overrides=tuple((pat, fix(c)) for pat, c in policy.overrides),
    )


def _serve_continuous(args, cfg, policy, params, programmed, mesh):
    """Continuous-batching mode: N variable-length requests through a
    K-slot table over one shared programmed state (DESIGN.md §7)."""
    rng = np.random.default_rng(0)
    lens = rng.integers(
        max(1, args.prompt_len // 2), args.prompt_len + 1,
        size=args.requests,
    )
    arrivals = np.zeros(args.requests)
    if args.arrival == "poisson":
        arrivals = np.cumsum(
            rng.exponential(1.0 / args.rate, size=args.requests)
        )
    preamble = rng.integers(
        0, cfg.vocab, size=args.shared_prefix
    ).astype(np.int32)
    max_len = args.max_len or int(
        lens.max() + args.shared_prefix + args.gen + 1
    )
    draft_policy = None
    if args.spec_k and args.draft_policy != "digital":
        draft_policy = _row_independent(make_policy(args.draft_policy))
    loop = ServeLoop(
        params, cfg, ServeConfig(
            policy=policy, slots=args.slots, max_len=max_len,
            prefill_chunk=args.prefill_chunk or None,
            block_size=args.block_size,
            kv_blocks=args.kv_blocks or None,
            compute_dtype=jnp.float32,
            weight_stationary=not args.per_call, mesh=mesh,
            prefix_cache=args.prefix_cache == "on",
            interactive_weight=args.interactive_weight,
            max_queue_skip=args.max_queue_skip,
            refresh_every=args.refresh_every,
            spec_k=args.spec_k,
            draft_policy=draft_policy,
        ), programmed=programmed,
    )
    # priority assignment: the first ceil(mix*N) requests of a random
    # permutation are interactive — deterministic under the driver seed
    interactive = set(
        rng.permutation(args.requests)[
            : int(np.ceil(args.priority_mix * args.requests))
        ].tolist()
    )
    def _sampling(i):
        if args.sample <= 0:
            return None
        return SamplingParams(
            temperature=args.sample, top_k=args.top_k, top_p=args.top_p,
            seed=args.sample_seed + i,
        )

    reqs = [
        Request(
            rid=i,
            tokens=np.concatenate([
                preamble,
                rng.integers(0, cfg.vocab, size=int(lens[i])).astype(
                    np.int32
                ),
            ]),
            max_new_tokens=args.gen,
            submit_time=float(arrivals[i]),
            priority="interactive" if i in interactive else "batch",
            sampling=_sampling(i),
        )
        for i in range(args.requests)
    ]
    # warmup pass (same buckets/slots) so the report reflects the
    # steady-state engine, not jit compiles
    loop.run([
        Request(rid=-1 - r.rid, tokens=r.tokens, max_new_tokens=2)
        for r in reqs
    ])
    report = loop.run(reqs)
    mode = "per-call" if args.per_call else "programmed"
    print(
        f"served {args.requests} requests through {args.slots} slots in "
        f"{report.wall_s:.2f}s: {report.tok_per_s:.1f} tok/s aggregate "
        f"({report.decode_steps} decode steps, "
        f"occupancy {report.occupancy:.2f}, {mode})"
    )
    lat = report.latency_percentiles()
    print(
        "per-request latency s: "
        f"mean={lat['mean']:.3f} p50={lat['p50']:.3f} "
        f"p95={lat['p95']:.3f} max={lat['max']:.3f}"
    )
    ttft = report.ttft_percentiles()
    print(
        "time-to-first-token s: "
        f"mean={ttft['mean']:.3f} p50={ttft['p50']:.3f} "
        f"p95={ttft['p95']:.3f} max={ttft['max']:.3f}"
    )
    if interactive:
        for cls in ("interactive", "batch"):
            t = report.ttft_percentiles(cls)
            i = report.itl_percentiles(cls)
            if not t:
                continue
            itl_part = f" itl_p50={i['p50']:.4f}" if i else ""
            print(
                f"  {cls:>11}: {len(report.completed(cls))} reqs, "
                f"ttft p50={t['p50']:.3f} p95={t['p95']:.3f}" + itl_part
            )
        print(
            f"scheduler: {report.scheduler_skips} skips, "
            f"{report.aged_admissions} aged admissions "
            f"(weight {args.interactive_weight}, "
            f"skip bound {args.max_queue_skip})"
        )
    itl = report.itl_percentiles()
    if itl:
        print(
            "inter-token latency s: "
            f"mean={itl['mean']:.4f} p50={itl['p50']:.4f} "
            f"p95={itl['p95']:.4f}"
        )
    print(
        f"paged arena: {report.kv_blocks} blocks x "
        f"{loop.block_size} tokens, {report.kv_blocks_reused} reused, "
        f"{report.admission_deferrals} admission deferrals"
    )
    print(
        f"prefix cache [{args.prefix_cache}]: "
        f"{report.prefix_cache_hits} block hits / "
        f"{report.prefix_cache_misses} misses, "
        f"{report.prefix_cache_cow_copies} COW copies, "
        f"{report.prefix_cache_evictions} evictions, "
        f"{report.prefill_chunks_run} prefill chunks run"
    )
    if args.refresh_every is not None:
        print(f"crossbar refresh: {report.reprogram_swaps} generation "
              f"swaps (every {args.refresh_every:g}s of device time)")
    if args.spec_k:
        acc = report.acceptance_rate
        per_req = [
            r.acceptance for r in report.completed()
            if r.acceptance is not None
        ]
        print(
            f"speculative k={args.spec_k} [{args.draft_policy} draft]: "
            f"{report.tokens_accepted}/{report.tokens_drafted} drafts "
            f"accepted"
            + (f" ({acc:.3f})" if acc is not None else "")
            + (
                f", per-request acceptance p50="
                f"{float(np.median(per_req)):.3f}"
                if per_req else ""
            )
        )
    print("counters:", report.counters())
    print("sample:", report.results[0].tokens[:16])
    return report


if __name__ == "__main__":
    main()
