"""CPU tests of the benchmark's own code (``bench/``): trace reduction,
percentiles, traffic, work counts, name resolution, the refusal to run
without a TPU, and ``correct`` against a broken timed path."""
from __future__ import annotations

import copy
import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, traffic, work  # noqa: E402
from bench.model import sizes  # noqa: E402

BENCH_JSON = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH_JSON["workloads"]]
QWEN2 = sizes(json.loads(
    (ROOT / "bench/configs/qwen2-0.5b.faithful.json").read_text()))


# ---------------------------------------------------------------- names


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_every_file_by_name(cell):
    c = harness.load_cell(cell)
    assert c.chips == 1
    assert c.conf["device"]["mode"] in ("faithful", "fast")
    assert {"arrivals", "prompt", "output", "serving", "check"} <= set(c.mix)
    assert c.limits["compare"]
    assert set(c.limits["compare"]) <= set(harness.GAP_STATS)
    names = {m["name"] for m in c.per_layer}
    assert names, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(harness.reader(m["name"]))
        assert m["moves"] in {e["name"] for e in c.end_to_end}
    assert "setup_s" in {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2
    for m in c.end_to_end:
        assert m["name"] in harness.END_TO_END


@pytest.mark.parametrize("name", [c["name"] for c in BENCH_JSON["configs"]])
def test_config_file_states_the_published_widths(name):
    conf = next(c for c in BENCH_JSON["configs"] if c["name"] == name)
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["source"] == conf["source"]
    assert data["reduced"] == conf["reduced"]
    s = sizes(data)
    assert (s["num_hidden_layers"], s["hidden_size"], s["intermediate_size"],
            s["num_attention_heads"], s["num_key_value_heads"],
            s["vocab_size"]) == (24, 896, 4864, 14, 2, 151936)
    cfg, policy = harness.program_setup(data)
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab) == (
        24, 896, 4864, 151936)
    assert policy.default.mode == data["device"]["mode"]


def test_program_tree_matches_the_program_init():
    import jax

    from bench.model import _make_weights, to_program
    from repro.models import init_params

    conf = json.loads(
        (ROOT / "bench/configs/qwen2-0.5b.faithful.json").read_text())
    conf.update(num_hidden_layers=2, vocab_size=512)
    cfg, _ = harness.program_setup(conf)
    s = sizes(conf)
    ours = jax.eval_shape(lambda k: to_program(
        _make_weights(k, tuple(sorted(s.items())))), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    shape = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)
    assert shape(ours) == shape(theirs)


# ---------------------------------------------------------------- traffic


def test_percentile_counts_failures_as_missing():
    vals = [1.0] * 18 + [math.inf, math.inf]
    assert traffic.percentile(vals, 50) == 1.0
    assert traffic.percentile(vals, 95) == math.inf
    assert traffic.percentile([3.0, 1.0, 2.0], 95) == 3.0
    assert traffic.percentile(list(range(1, 101)), 95) == 95


@pytest.mark.parametrize("mix", ["decode_batch", "chat", "prefill_batch",
                                 "longctx_batch"])
def test_traffic_is_deterministic_under_a_seed(mix):
    from bench.model import load

    m = load("traffic", mix)
    secs = BENCH_JSON["run_seconds"]
    a = traffic.make(m, 2**33 + 5, secs, 151936)
    b = traffic.make(m, 2**33 + 5, secs, 151936)
    c = traffic.make(m, 7, secs, 151936)
    assert len(a) == len(c) == traffic.count(m, secs)
    for x, y in zip(a, b):
        assert np.array_equal(x.tokens, y.tokens)
        assert (x.max_new_tokens, x.due) == (y.max_new_tokens, y.due)
    # another seed: the same work at the same times, other tokens
    work = lambda rs: [(len(r.tokens), r.max_new_tokens, r.due) for r in rs]
    assert work(a) == work(c)
    assert not np.array_equal(a[0].tokens, c[0].tokens)
    assert len(set(work(a))) > 1
    serving = m["serving"]
    for r in a:
        assert m["prompt"]["min"] <= len(r.tokens) <= m["prompt"]["max"]
        assert len(r.tokens) + r.max_new_tokens <= serving["max_len"]
        assert r.tokens.min() >= 0 and r.tokens.max() < 151936


def test_lognormal_lengths_sit_at_their_median():
    spec = {"dist": "lognormal", "median": 96, "sigma": 0.5, "min": 1,
            "max": 10**6}
    v = traffic.lengths(spec, 1001)
    assert v[500] == 96
    assert list(v) == sorted(v)


# ---------------------------------------------------------------- work


FAITHFUL = json.loads(
    (ROOT / "bench/configs/qwen2-0.5b.faithful.json").read_text())["device"]
FAST = json.loads(
    (ROOT / "bench/configs/qwen2-0.5b.fast.json").read_text())["device"]


def test_gemm_weights_are_qwen2s():
    per_layer = sum(k * n for k, n in work.layer_gemms(QWEN2))
    # q, o: 896x896; k, v: 896x128; up, gate, down: 896x4864
    assert per_layer == 2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864
    assert per_layer == 14_909_440
    k, n = work.head_gemm(QWEN2)
    assert 24 * per_layer + k * n == 493_961_216


def test_dpe_gemm_counts_rows_not_tiles():
    served = [work.Served(prompt_len=100, generated=10)]
    w = work.dpe_gemm(QWEN2, FAITHFUL, served, layer_calls=10, head_calls=10)
    # 109 layer rows (100 prompt + 9 decoded), 10 head rows, 16 pairs
    assert w.ops == 2 * 16 * (24 * 14_909_440 * 109 + 896 * 151936 * 10)
    fast = work.dpe_gemm(QWEN2, FAST, served, layer_calls=10, head_calls=10)
    assert fast.ops * 16 == w.ops
    # faithful state: 4 f32 slices of every padded projection
    q_state = work.state_bytes(896, 896, FAITHFUL)
    assert q_state == 4 * 896 * 896 * 4 + 7 * 7 * 4
    assert work.state_bytes(896, 151936, FAST) == 896 * 151936 * 2
    layer = sum(work.state_bytes(k, n, FAITHFUL)
                for k, n in work.layer_gemms(QWEN2))
    head = work.state_bytes(896, 151936, FAITHFUL)
    act = 4 * (24 * sum(k + n for k, n in work.layer_gemms(QWEN2)) * 109
               + (896 + 151936) * 10)
    assert w.bytes == 10 * 24 * layer + 10 * head + act
    assert 7.8e9 < 24 * layer + head < 8.0e9


def test_attention_counts_the_live_context():
    served = [work.Served(prompt_len=3, generated=3)]
    dec = work.paged_decode(QWEN2, served, kv_itemsize=4)
    # decoded rows at positions 3 and 4 attend over 4 and 5 keys
    assert dec.ops == 24 * 4 * 14 * 64 * (4 + 5)
    kv = lambda ctx: 2 * ctx * 2 * 64 * 4
    qo = 2 * 14 * 64 * 4
    assert dec.bytes == 24 * (kv(4) + kv(5) + 2 * qo)
    ch = work.paged_chunk(QWEN2, [work.Served(5, 1)], chunk=2, kv_itemsize=4)
    # chunks [0,2) [2,4) [4,5): positions 0..4 attend over 1..5 keys
    assert ch.ops == 24 * 4 * 14 * 64 * (1 + 2 + 3 + 4 + 5)
    assert ch.bytes == 24 * (kv(2) + kv(4) + kv(5) + qo * (2 + 2 + 1))


def test_roofline_share_takes_the_binding_bound():
    peak = work.peaks("TPU v5 lite")
    assert peak["flops_bf16"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    w = work.Work(ops=197e12, bytes=819e9 * 2)
    assert work.share(w, 4.0, peak) == pytest.approx(50.0)
    assert work.share(w, 0.0, peak) is None
    with pytest.raises(KeyError):
        work.peaks("TPU v9")


# ---------------------------------------------------------------- trace


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Ev(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name = name
        self.lines = [_Line(n, evs) for n, evs in lines.items()]


def _planes(data):
    return [_Plane(p["name"], p["lines"]) for p in data["planes"]]


def test_trace_reduction_hand_made():
    from bench.trace import reduce_planes

    planes = [
        _Plane("/host:CPU", {"main": [(harness.SPAN, 1000, 10_000)]}),
        _Plane("/device:TPU:0", {
            "XLA Modules": [("jit_step(3)", 2000, 3000),
                            ("jit_chunk_fn(4)", 7000, 2000)],
            "XLA Ops": [("%while.1 = (f32[]) while()", 2000, 3000),
                        ("%fused_sliced_matmul_pallas.2 = f32[8] c()",
                         2000, 2000),
                        ("%fusion.1 = f32[8] fusion()", 4000, 1000),
                        ("%_paged_chunk_call.7 = f32[8] c()", 7000, 2000),
                        ("%outside.1 = f32[] f()", 20_000, 5000)],
        }),
        _Plane("/device:TPU:1", {"XLA Ops": [("other chip", 1000, 10_000)]}),
    ]
    t = reduce_planes(planes, harness.SPAN, chips=1)
    assert t.window_s == pytest.approx(10e-6)
    # union of [2000, 5000) and [7000, 9000)
    assert t.busy_s == pytest.approx(5e-6)
    assert t.op_seconds("^fused_sliced_matmul_pallas$") == pytest.approx(2e-6)
    assert set(t.ops) == {"fused_sliced_matmul_pallas", "fusion",
                          "_paged_chunk_call"}
    assert t.module_durations("^jit_step$") == [pytest.approx(3e-6)]
    gaps = dict((label, s) for label, s in t.breakdown()["idle_gaps"])
    assert gaps["host loop after jit_step"] == pytest.approx(2e-6)
    assert gaps["host loop after jit_chunk_fn"] == pytest.approx(2e-6)
    assert sum(gaps.values()) == pytest.approx(5e-6)


RECORDED = Path(__file__).resolve().parent / "data" / "tiny_serve_trace.json"


def test_trace_reduction_on_a_chip_trace():
    from bench.readers import DECODE_PROGRAM, DPE_KERNEL, PAGED_KERNELS
    from bench.trace import reduce_planes

    data = json.loads(RECORDED.read_text())
    t = reduce_planes(_planes(data), harness.SPAN, chips=1)
    want = data["reduced"]
    assert t.window_s == pytest.approx(want["window_s"])
    assert t.busy_s == pytest.approx(want["busy_s"])
    assert 0 < t.busy_s < t.window_s
    assert len(t.module_durations(DECODE_PROGRAM)) == want["decode_steps"]
    assert t.op_seconds(DPE_KERNEL) == pytest.approx(
        want["fused_sliced_matmul_pallas_s"])
    assert t.op_seconds(PAGED_KERNELS) > 0
    # leaf operations only: the layer loop's while holds the kernels
    assert "while" not in t.ops
    b = t.breakdown()
    assert b["device_ops"][0][0] == "fused_sliced_matmul_pallas"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


# ---------------------------------------------------------------- runs


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def _tiny(name: str):
    """The cell's own files at a size a CPU test can run."""
    c = harness.load_cell(name)
    c.conf = dict(c.conf, num_hidden_layers=2, hidden_size=128,
                  num_attention_heads=2, num_key_value_heads=1,
                  intermediate_size=256, vocab_size=512)
    mix = copy.deepcopy(c.mix)
    mix["arrivals"]["rate"] = 4
    mix["prompt"].update(min=8, max=40, median=20)
    mix["output"] = {"dist": "uniform", "min": 4, "max": 8}
    mix["serving"].update(slots=2, max_len=64, prefill_chunk=16,
                          block_size=8)
    mix["check"]["requests"] = 2
    c.mix = mix
    # at this size sound runs read gaps of 0 to ~4e-3 (CPU) and a token
    # one off the computed one reads tenths
    c.limits = {"compare": {"widest_gap": 0.05}}
    return c


def _alter_a_token(loop):
    """The decode step emits a token other than the one it computed."""
    decode = loop._decode

    def altered(*args):
        logits, toks, cache = decode(*args)
        return logits, (toks + 1) % logits.shape[-1], cache

    loop._decode = altered


@pytest.mark.parametrize("cell", ["qwen2-faithful.decode", "qwen2-fast.chat"])
@pytest.mark.parametrize("fault", [None, _alter_a_token],
                         ids=["sound", "token_altered"])
def test_correct_fails_when_a_token_is_altered(cell, fault):
    import jax

    c = _tiny(cell)
    line = harness.run_cell(c, 2**32 + 3, 1.0, False, jax.devices(),
                            time.monotonic(), fault=fault)
    assert line["attempted"] == 4 and line["failed"] == 0
    assert list(line)[-1] == "compared"
    assert line["correct"] is (fault is None), line["compared"]
    assert line["compared"]["kv_cache_bits_below_stated"]["value"] == 0
    assert {"setup_s"} <= set(line["metrics"])


def test_a_cache_below_the_stated_precision_is_not_correct():
    """The program's own bfloat16 path (``compute_dtype=bfloat16``: a
    bfloat16 KV cache and attention) is not correct under a configuration
    that states float32."""
    import jax

    c = _tiny("qwen2-fast.chat")
    c.conf = dict(c.conf, program=dict(c.conf["program"],
                                       compute_dtype="bfloat16"))
    line = harness.run_cell(c, 2**32 + 3, 1.0, False, jax.devices(),
                            time.monotonic())
    assert line["compared"]["kv_cache_bits_below_stated"] == {
        "value": 16, "limit": 0}
    assert line["correct"] is False


def _agree(ours, theirs, rel):
    """Equal to rounding: the two programs fuse the same float32
    expressions (the log-normal draw's exp and log) differently, so some
    elements lie one or two roundings apart."""
    a = np.asarray(ours, np.float32)
    b = np.asarray(theirs, np.float32)
    assert a.shape == b.shape
    assert np.mean(a != b) < 0.25
    assert np.max(np.abs(a - b)) <= rel * np.max(np.abs(b))


@pytest.mark.parametrize("config", ["faithful", "fast"])
def test_reference_programs_the_same_noisy_state(config):
    """The reference's own programming of a layer, from the seed and the
    stated key chain, is the program's state for that layer."""
    import jax

    from bench import reference
    from bench.model import make_weights, noise_key, to_program
    from repro.models import program_params

    conf = json.loads(
        (ROOT / f"bench/configs/qwen2-0.5b.{config}.json").read_text())
    conf.update(num_hidden_layers=2, hidden_size=128, num_attention_heads=2,
                num_key_value_heads=1, intermediate_size=256, vocab_size=384)
    cfg, policy = harness.program_setup(conf)
    s, dev = sizes(conf), conf["device"]
    w = make_weights(5, s)
    prog = program_params(to_program(w), cfg, policy, noise_key(5))
    lk = jax.random.fold_in(jax.random.fold_in(noise_key(5), 0), 1)
    ours = reference._program(
        w["wg"][1], reference._layer_key(lk, "L.attn.mlp.wg"), dev)
    head = reference._program(
        w["head"], reference._layer_key(noise_key(5), "lm_head"), dev)
    theirs = jax.tree_util.tree_map(
        lambda a: a[1], prog["blocks"]["seg0"]["mlp"]["wg"])
    if config == "faithful":
        for (ws, sw), st in ((ours, theirs), (head, prog["lm_head"])):
            nsw, nk, bk, nn, bn = ws.shape
            _agree(ws.reshape(nsw, nk * bk, nn * bn), st.slices, 1e-6)
            np.testing.assert_allclose(sw, st.scale, rtol=1e-6)
    else:
        # one bfloat16 rounding apart where the float32 sums differ
        _agree(reference._fold(ours, dev), theirs.w_eff, 2.0 ** -7)
        _agree(reference._fold(head, dev), prog["lm_head"].w_eff, 2.0 ** -7)


@pytest.mark.parametrize("mode,err", [("f32", 1e-6), ("bf16x3", 1e-4),
                                      ("bf16", 2e-2)])
def test_control_contractions_run_in_their_stated_precision(mode, err):
    """The controls' contractions round as ``Precision.HIGH`` (three
    bfloat16 passes) and one bfloat16 pass do, on any backend."""
    import jax

    from bench.reference import _dot

    a = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (64, 256)))
    b = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (256, 32)))
    exact = a.astype(np.float64) @ b.astype(np.float64)
    got = np.asarray(_dot(a, b, (((1,), (0,)), ((), ())), mode))
    rel = np.max(np.abs(got - exact)) / np.max(np.abs(exact))
    assert rel <= err
    if mode != "f32":
        assert rel > 1e-7  # it is not the full-precision product


def test_controls_read_beside_the_program():
    """``bench/calibrate.py limits`` reads every control of the
    configuration on the program's own sample, teacher-forced on the
    program's tokens: a control that is the reference itself reads what
    an exact program reads, 0.  Each control's numbers are held to the
    cell's own limits, and the verdict is theirs."""
    import jax

    from bench.calibrate import CONTROLS

    c = _tiny("qwen2-faithful.decode")
    controls = dict(CONTROLS["faithful"], same={"dot": "f32"})
    line = harness.run_cell(c, 11, 1.0, False, jax.devices(),
                            time.monotonic(), controls=controls)
    readings = dict(line["readings"])
    per_token = readings.pop("per_token")
    assert set(readings) == set(per_token) == {"program", *controls}
    assert readings["same"]["widest_gap"] == 0.0
    assert max(per_token["program"]) == pytest.approx(
        readings["program"]["widest_gap"], abs=1e-6)
    for stats in readings.values():
        assert set(stats) == set(harness.GAP_STATS)
        assert all(np.isfinite(v) and v >= 0 for v in stats.values())
    verdicts = line["controls"]
    assert set(verdicts) == set(controls)
    for name, v in verdicts.items():
        assert set(v["compared"]) == set(c.limits["compare"])
        for stat, held in v["compared"].items():
            assert held == {"value": readings[name][stat],
                            "limit": c.limits["compare"][stat]}
        assert v["correct"] is harness.passes(v["compared"])
    assert verdicts["same"]["correct"] is True


def test_a_control_that_reads_far_off_is_not_correct():
    """The folded weight kept in fp8 puts other tokens first: its verdict
    under the cell's limits is not correct, while the program's run is."""
    import jax

    from bench.calibrate import CONTROLS

    c = _tiny("qwen2-fast.chat")
    line = harness.run_cell(c, 2**32 + 5, 1.0, False, jax.devices(),
                            time.monotonic(), controls=CONTROLS["fast"])
    assert line["correct"] is True
    fp8 = line["controls"]["fp8_state"]
    assert fp8["correct"] is False, fp8
    assert fp8["compared"]["widest_gap"]["value"] > 0.05


def test_run_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    directories exits non-zero and prints no result."""
    import shutil

    for p in BENCH_JSON["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, *BENCH_JSON["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
