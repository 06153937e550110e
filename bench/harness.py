"""One run of one cell: set-up, the measured window, the metrics and
the comparison with the plain reference that decides ``correct``.

``bench/run.py`` is the command; it checks the chips and calls
:func:`run_cell`.  Tests call :func:`run_cell` directly at a small size.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import traffic, work
from bench.model import BENCH, load, make_weights, noise_key, sizes, to_program

ROOT = BENCH.parent
SPAN = "bench.serve_window"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclass
class Cell:
    """A cell of ``BENCHMARK.json`` with every file it names, loaded."""
    name: str
    conf: dict
    mix: dict
    limits: dict
    chips: int
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_json: dict | None = None) -> Cell:
    """Resolve a cell's configuration, traffic and limits by name."""
    if bench_json is None:
        bench_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench_json["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench_json["configs"]}
    conf = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    return Cell(
        name=name,
        conf=conf,
        mix=load("traffic", w["traffic"]),
        limits=load("limits", name),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench_json["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench_json["per_layer"] if _applies(m, name)],
    )


def reader(metric: str):
    """The per-layer metric's own reader, ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {metric}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# the program under test
# --------------------------------------------------------------------------


def program_setup(conf: dict):
    """The program's model config and device policy, checked against
    what the configuration file states.  The policy is the one the serve
    launcher serves: row-independent ADC ranging (``dynamic_row``)."""
    from repro.configs import get
    from repro.launch.dryrun import make_policy
    from repro.launch.serve import _row_independent

    s = sizes(conf)
    prog = conf["program"]
    cfg = get(prog["arch"]).replace(
        n_layers=s["num_hidden_layers"], d_model=s["hidden_size"],
        n_heads=s["num_attention_heads"], n_kv_heads=s["num_key_value_heads"],
        head_dim=s["head_dim"], d_ff=s["intermediate_size"],
        vocab=s["vocab_size"], rope_theta=s["rope_theta"],
    )
    policy = _row_independent(make_policy(prog["policy"]))
    dpe = policy.default
    dev = conf["device"]
    stated = {
        "mode": dpe.mode, "array": list(dpe.array_size),
        "input_bits": list(dpe.input_spec.bits),
        "weight_bits": list(dpe.weight_spec.bits),
        "rdac": dpe.rdac, "radc": dpe.radc, "hgs": dpe.hgs, "lgs": dpe.lgs,
        "cv": dpe.cv,
    }
    if dpe.mode == "faithful":
        stated["adc"] = dpe.adc_mode
    else:
        stated["store"] = {"bf16": "bfloat16", "f32": "float32"}[
            dpe.store_dtype]
    wrong = {k: (v, dev.get(k)) for k, v in stated.items() if dev.get(k) != v}
    if wrong:
        raise ValueError(f"the program's device differs from the "
                         f"configuration file: {wrong}")
    return cfg, policy


def _requests(dues):
    from repro.serve import Request

    return [
        Request(rid=d.rid, tokens=d.tokens, max_new_tokens=d.max_new_tokens,
                submit_time=d.due)
        for d in dues
    ]


def _done(result) -> bool:
    return (result.finish_reason != "refused"
            and len(result.tokens) > 0)


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float, fault=None, controls=None,
             verify: bool = True) -> dict:
    """Run ``cell`` once and return the result line's object.

    ``t_start`` is when the process started (set-up runs from it).  The
    rest serves the tools and tests, never ``bench/run.py``: ``fault`` is
    called with the ServeLoop before the window and may break the timed
    path underneath; ``controls`` adds the controls' readings on the same
    sample (``bench/calibrate.py``), each with its verdict under the
    cell's own limits; ``verify=False`` skips the reference (a rate
    sweep)."""
    import jax
    import jax.numpy as jnp

    from repro.models import program_params
    from repro.serve import Request, ServeConfig, ServeLoop

    used = devices[: cell.chips]
    size = sizes(cell.conf)
    cfg, policy = program_setup(cell.conf)
    serving = cell.mix["serving"]
    compute = jnp.dtype(cell.conf["program"]["compute_dtype"])

    # ---- set-up: weights, programming, the loop and its warm-up
    weights = make_weights(seed, size)
    params = to_program(weights)
    jax.block_until_ready(params)
    t = time.monotonic()
    programmed = program_params(params, cfg, policy, noise_key(seed))
    jax.block_until_ready(programmed)
    program_s = time.monotonic() - t
    t = time.monotonic()
    loop = ServeLoop(params, cfg, ServeConfig(
        policy=policy, slots=int(serving["slots"]),
        max_len=int(serving["max_len"]),
        prefill_chunk=int(serving["prefill_chunk"]),
        block_size=int(serving["block_size"]), compute_dtype=compute,
    ), programmed=programmed)
    # one request compiles every program the window runs: the chunk step
    # (one shape: every chunk is padded to prefill_chunk), the decode
    # step (one shape: all slots), the lane admission and the head
    warm = loop.run([Request(rid=-1, tokens=np.ones(2, np.int32),
                             max_new_tokens=2)])
    if not all(_done(r) for r in warm.results):
        raise RuntimeError("warm-up request did not complete")
    warmup_s = time.monotonic() - t
    dues = traffic.make(cell.mix, seed, seconds, size["vocab_size"])
    requests = _requests(dues)
    if fault is not None:
        fault(loop)
    setup_s = time.monotonic() - t_start

    # ---- the window
    trace_dir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir.name)
    try:
        with jax.profiler.TraceAnnotation(SPAN):
            report = loop.run(requests)
    finally:
        if trace:
            jax.profiler.stop_trace()
    peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used
    )

    # ---- what the window did
    results = report.results
    done = [r for r in results if _done(r)]
    wanted = {r.rid: r.max_new_tokens for r in requests}
    failed = [r for r in results
              if not _done(r) or len(r.tokens) != wanted[r.rid]]
    served = [work.Served(r.prompt_len, len(r.tokens)) for r in done]
    ctx = {
        "cell": cell, "size": size, "report": report, "served": served,
        "requests": requests, "results": results,
        "program_s": program_s, "warmup_s": warmup_s, "setup_s": setup_s,
        "device_kind": used[0].device_kind, "chunk": int(
            serving["prefill_chunk"]),
        "kv_itemsize": jnp.dtype(loop.cache_dtype).itemsize,
    }
    out_metrics, device_extra, breakdown = {}, {}, None
    summary(ctx)
    if trace:
        from bench import trace as trace_mod

        ctx["trace"] = trace_mod.reduce(trace_dir.name, SPAN, len(used))
        trace_dir.cleanup()
        device_extra = {"busy_s": ctx["trace"].busy_s,
                        "window_s": ctx["trace"].window_s}
        breakdown = ctx["trace"].breakdown()
        for m in cell.per_layer:
            value = reader(m["name"])(ctx)
            if value is not None:
                out_metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = END_TO_END[m["name"]](ctx)
            out_metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # ---- free the program's state, then compare with the reference
    del loop, programmed, params, weights, warm
    gc.collect()
    if not verify:
        return {"metrics": out_metrics, "ctx": ctx}
    compared, readings, verdicts = check(cell, seed, done, requests, failed,
                                         controls)
    # the KV cache holds keys and values in the stated compute precision
    # (the program has a bfloat16 cache of its own, which no gap of the
    # served tokens can tell from float32: PERF.md)
    stated = jnp.dtype(cell.conf["precision"]["compute"]).itemsize
    compared["kv_cache_bits_below_stated"] = {
        "value": 8 * max(0, stated - ctx["kv_itemsize"]), "limit": 0}
    correct = passes(compared)
    for name, c in compared.items():
        log(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    line = {
        "correct": bool(correct),
        "attempted": len(requests),
        "failed": len(failed),
        "metrics": out_metrics,
        "device": {
            "platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": int(peak_bytes),
            **device_extra,
        },
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    if controls is not None:
        line["readings"] = readings
        line["controls"] = verdicts
    line["compared"] = compared
    return line


# --------------------------------------------------------------------------
# end-to-end metrics: host clock at request boundaries
# --------------------------------------------------------------------------


def _ttft(ctx) -> list[float]:
    return [r.first_token_time - r.submit_time if _done(r) else math.inf
            for r in ctx["results"]]


def _tpot(ctx) -> list[float]:
    out = []
    for r in ctx["results"]:
        if not _done(r) or len(r.tokens) < 2:
            out.append(math.inf)
        else:
            out.append((r.finish_time - r.first_token_time)
                       / (len(r.tokens) - 1))
    return out


def sim_tokens_per_s(ctx) -> float:
    tokens = sum(s.prompt_len + s.generated for s in ctx["served"])
    return tokens / ctx["report"].wall_s


END_TO_END = {
    "sim_tokens_per_s": sim_tokens_per_s,
    "ttft_p90_s": lambda ctx: traffic.percentile(_ttft(ctx), 90),
    "tpot_p90_ms": lambda ctx: 1e3 * traffic.percentile(_tpot(ctx), 90),
    "setup_s": lambda ctx: ctx["setup_s"],
}


def summary(ctx) -> None:
    """Medians and sample counts, on lines before the result."""
    rep = ctx["report"]
    ttft, tpot = _ttft(ctx), _tpot(ctx)
    log(f"window {rep.wall_s:.3f}s: {len(ctx['served'])}/"
        f"{len(ctx['requests'])} requests, {sum(s.generated for s in ctx['served'])} "
        f"generated, {sum(s.prompt_len for s in ctx['served'])} prompt tokens, "
        f"{rep.decode_steps} decode steps, {rep.prefill_chunks_run} chunks, "
        f"occupancy {rep.occupancy:.3f}")
    log(f"ttft median {traffic.percentile(ttft, 50):.4f}s p90 "
        f"{traffic.percentile(ttft, 90):.4f}s (n={len(ttft)}); tpot median "
        f"{1e3 * traffic.percentile(tpot, 50):.3f}ms p90 "
        f"{1e3 * traffic.percentile(tpot, 90):.3f}ms (n={len(tpot)})")
    log(f"set-up {ctx['setup_s']:.3f}s: programming {ctx['program_s']:.3f}s, "
        f"loop and warm-up {ctx['warmup_s']:.3f}s")


# --------------------------------------------------------------------------
# correctness: the served tokens against the plain reference
# --------------------------------------------------------------------------


def check_sample(done, seed: int, n: int) -> list:
    """``n`` finished requests drawn from the seed, with the longest."""
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 7])
    k = min(len(rest), max(0, n - 1))
    pick = rng.choice(len(rest), size=k, replace=False) if k else []
    return [longest] + [rest[i] for i in sorted(pick)]


# The statistics a cell's limits file may name, over the gaps of every
# sampled token: how far the reference's logit of the served token lies
# below the reference's best.
GAP_STATS = {
    "widest_gap": lambda g: float(np.max(g)),
    "mean_sq_gap": lambda g: float(np.mean(np.square(g))),
}


def passes(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def check(cell: Cell, seed: int, done, requests, failed,
          controls: dict | None = None):
    """The numbers compared, each with its limit (the cell's limits file
    names them); every gap statistic of the program and of each control,
    by name; and each control's numbers under the same limits, with the
    verdict they give (a control has to read not correct)."""
    from bench.reference import Reference

    by_rid = {r.rid: r for r in requests}
    sample = check_sample(done, seed, int(cell.mix["check"]["requests"]))
    ref = Reference(cell.conf, seed, noise_key(seed))
    serving = cell.mix["serving"]
    gaps: dict = {}
    t = time.monotonic()
    for r in sample:
        got = ref.readings(by_rid[r.rid].tokens, r.tokens,
                           int(serving["max_len"]),
                           int(cell.mix["output"]["max"]), controls)
        for name, g in got.items():
            gaps.setdefault(name, []).append(g)
    log(f"reference over {len(sample)} requests, "
        f"{sum(len(r.tokens) for r in sample)} served tokens: "
        f"{time.monotonic() - t:.2f}s")
    gaps = {name: np.concatenate(g) for name, g in gaps.items()}
    stats = {name: {k: f(g) for k, f in GAP_STATS.items()}
             for name, g in gaps.items()}

    def held(read):
        return {name: {"value": read[name], "limit": float(limit)}
                for name, limit in cell.limits["compare"].items()}

    compared = held(stats.get("program", {k: math.inf for k in GAP_STATS}))
    compared["failed_requests"] = {"value": len(failed), "limit": 0}
    verdicts = {}
    for name in controls or {}:
        got = held(stats.get(name, {k: math.inf for k in GAP_STATS}))
        verdicts[name] = {"correct": passes(got), "compared": got}
    if controls is not None:
        stats["per_token"] = {n: [round(float(x), 6) for x in g]
                              for n, g in gaps.items()}
    return compared, stats, verdicts
