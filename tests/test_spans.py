"""The serve loop's host spans (``serve/spans.py``) and the names the
benchmark reads in the device trace.

* The recorder keeps per-span totals and the host gap: from the first
  device sync after a dispatch to the next dispatch, less ``serve.wait``;
  it lists compiles only while it listens.
* ``ServeLoop.run`` reports every documented span, one ``serve.iteration``
  per iteration record, a host gap inside the run's wall time, and the
  programs compiled while it ran (none on an identical second run).
* Under the profiler the spans, and the programming pass's, land on a
  host plane with their ``rid``.
* The step programs and the programming pass lower to the modules
  ``jit_step``, ``jit_chunk_fn`` and ``jit__program_params_body``.
"""
import glob
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import DPEConfig, spec
from repro.core.layers import MemPolicy
from repro.models import init_params, program_params
from repro.models.model import init_paged_cache
from repro.models.programmed import _program_params_impl
from repro.serve import Request, ServeConfig, ServeLoop, spans
from repro.serve.batching import _jit_decode

INT8 = spec("int8")
FAST = MemPolicy(
    default=DPEConfig(input_spec=INT8, weight_spec=INT8, mode="fast")
)
MAX_LEN = 32
# the spans every run records (serve.wait and serve.refresh only where the
# loop waits for an arrival or refreshes)
SERVE_SPANS = {"serve.iteration", "serve.admit", "serve.chunk",
               "serve.decode", "serve.sync", "serve.emit"}


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke("qwen2-0.5b").replace(vocab=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prog = program_params(params, cfg, FAST, jax.random.PRNGKey(0))
    return cfg, params, prog


def _requests(cfg, n=4, spread=0.0, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(rid=i, tokens=rng.integers(0, cfg.vocab, size=3 + 2 * i)
                .astype(np.int32), max_new_tokens=3 + i,
                submit_time=spread * i)
        for i in range(n)
    ]


# ------------------------------------------------------------ the recorder


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _spend(rec, name, seconds):
    with rec.span(name):
        rec.clock.t += seconds


def test_gap_runs_from_a_sync_to_the_next_dispatch():
    rec = spans.Recorder(_Clock())
    clock = rec.clock
    rec.dispatch()  # no sync yet: no gap
    _spend(rec, "serve.decode", 1.0)
    _spend(rec, "serve.sync", 2.0)  # the gap opens at its return
    clock.t += 0.5
    _spend(rec, "serve.sync", 0.25)  # already drained: still open
    clock.t += 0.25
    _spend(rec, "serve.wait", 4.0)  # nothing to run: not a gap
    clock.t += 0.125
    rec.dispatch()  # closes it: 0.5 + 0.25 + 0.25 + 0.125
    clock.t += 8.0
    rec.dispatch()  # no sync since: nothing more
    _spend(rec, "serve.sync", 1.0)
    clock.t += 16.0  # open at the end: no dispatch closed it
    assert rec.gap_s == pytest.approx(1.125)
    assert rec.totals == {
        "serve.decode": [1, 1.0], "serve.sync": [3, 3.25],
        "serve.wait": [1, 4.0],
    }


def test_a_wait_before_any_sync_leaves_no_gap():
    rec = spans.Recorder(_Clock())
    _spend(rec, "serve.wait", 1.0)
    rec.clock.t += 1.0
    rec.dispatch()
    assert rec.gap_s == 0.0


def test_recorder_counts_compiles_and_lets_go_of_its_listener():
    rec = spans.Recorder()
    with rec.listening():
        # a shape no other test compiles
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((7, 3, 5))).block_until_ready()
    assert len(rec.compiled) >= 1
    assert all(rec.compiled)
    n = len(rec.compiled)
    jax.jit(lambda x: x * 5)(jnp.ones((3, 7, 5))).block_until_ready()
    assert len(rec.compiled) == n, "the listener outlived its recording"


# ---------------------------------------------------------------- the loop


def _loop(model, **kw):
    cfg, params, prog = model
    kw = {"slots": 2, "prefill_chunk": 4, **kw}
    return ServeLoop(params, cfg, ServeConfig(
        policy=FAST, max_len=MAX_LEN, compute_dtype=jnp.float32, **kw,
    ), programmed=prog)


def test_run_reports_every_span(model):
    cfg = model[0]
    loop = _loop(model, collect_trace=True)
    loop.run(_requests(cfg))  # compile first
    # arrivals spread out: the loop waits for some
    report = loop.run(_requests(cfg, spread=0.05))
    assert SERVE_SPANS | {"serve.wait"} <= set(report.host_spans)
    assert report.host_spans["serve.iteration"][0] == len(report.trace)
    admitted = sum(len(t["admitted"]) for t in report.trace)
    assert report.host_spans["serve.admit"][0] >= admitted == 4
    assert report.host_spans["serve.chunk"][0] == report.prefill_chunks_run
    assert report.host_spans["serve.decode"][0] == report.decode_steps
    for calls, seconds in report.host_spans.values():
        assert calls >= 1 and 0.0 <= seconds <= report.wall_s
    assert 0.0 <= report.host_gap_s <= report.wall_s
    assert report.window_compiles == 0 and report.compiled == []
    # the host records stay off the stable counter surface
    assert not {"host_spans", "host_gap_s", "window_compiles"} \
        & set(report.counters())


def test_refresh_and_speculative_paths_record_their_spans(model):
    cfg, params, prog = model
    loop = ServeLoop(params, cfg, ServeConfig(
        policy=FAST, slots=2, max_len=MAX_LEN, compute_dtype=jnp.float32,
        refresh_every=2.0, spec_k=2,
        clock=lambda c=itertools.count(1): float(next(c)),
    ), programmed=prog)
    report = loop.run(_requests(cfg, n=3))
    assert report.reprogram_swaps >= 1
    assert SERVE_SPANS | {"serve.refresh"} <= set(report.host_spans)
    assert report.host_spans["serve.refresh"][0] == report.reprogram_swaps
    assert report.host_spans["serve.decode"][0] == report.decode_steps
    # the draft's chunks are chunks too
    assert report.host_spans["serve.chunk"][0] > report.prefill_chunks_run
    assert 0.0 <= report.host_gap_s <= report.wall_s


def test_a_fresh_shape_compiles_in_the_run_and_not_again(model):
    cfg = model[0]
    # slots and chunk no other test uses: the first run compiles
    loop = _loop(model, slots=3, prefill_chunk=5)
    first = loop.run(_requests(cfg))
    second = loop.run(_requests(cfg))
    assert first.window_compiles == len(first.compiled) > 0
    assert {"jit(step)", "jit(chunk_fn)"} <= set(first.compiled)
    assert second.window_compiles == 0, second.compiled
    assert [r.tokens for r in first.results] == \
        [r.tokens for r in second.results]


def test_spans_land_on_a_host_plane_with_their_rid(model, tmp_path):
    cfg, params, _ = model
    loop = _loop(model)
    loop.run(_requests(cfg, n=2))
    jax.profiler.start_trace(str(tmp_path))
    try:
        loop.run(_requests(cfg, n=2))
        program_params(params, cfg, FAST, jax.random.PRNGKey(1))
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path[0])
    seen = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve.", "program.")):
                    seen.setdefault(ev.name, []).append(dict(ev.stats))
    assert {"serve.chunk", "serve.sync", "serve.iteration",
            "program.pass"} <= set(seen)
    assert {s["rid"] for s in seen["serve.chunk"]} == {0, 1}
    assert all("start" in s for s in seen["serve.chunk"])
    firsts = [s for s in seen["serve.sync"] if s["what"] == "first_token"]
    assert {s["rid"] for s in firsts} == {0, 1}
    assert {s["what"] for s in seen["serve.sync"]} >= {"tokens"}
    assert all({"chunks", "decoded", "admitted", "deferred"} <= set(s)
               for s in seen["serve.iteration"])


# ------------------------------------------------ names the benchmark reads


def _module(lowered) -> str:
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_decode_step_is_the_module_jit_step(model, sampled):
    cfg, params, prog = model
    loop = _loop(model)
    k = loop.slots
    cache = init_paged_cache(cfg, k, loop.max_len, loop.block_size,
                             loop.kv_blocks, loop.cache_dtype)
    step = _jit_decode(cfg, loop.policy, loop.compute_dtype, None,
                       sampled=sampled)
    extra = ((jnp.zeros((k, 2), jnp.uint32), jnp.zeros((k,)),
              jnp.zeros((k,), jnp.int32), jnp.ones((k,)))
             if sampled else ())
    lowered = step.lower(params, cache, jnp.zeros((k,), jnp.int32), prog,
                         jnp.ones((k,), bool), None, *extra)
    assert _module(lowered) == "jit_step"


def test_chunk_step_is_the_module_jit_chunk_fn(model):
    cfg, params, prog = model
    loop = _loop(model)
    cache = init_paged_cache(cfg, loop.slots, loop.max_len, loop.block_size,
                             loop.kv_blocks, loop.cache_dtype)
    i32 = lambda v: jnp.int32(v)
    lowered = loop._chunk.lower(
        params, cache, jnp.zeros((loop.prefill_chunk,), jnp.int32), i32(0),
        i32(0), i32(4), jnp.bool_(True), prog, None,
    )
    assert _module(lowered) == "jit_chunk_fn"


def test_programming_pass_is_the_module_jit__program_params_body(model):
    cfg, params, _ = model
    lowered = _program_params_impl.lower(
        params, cfg, FAST, jax.random.PRNGKey(0), jnp.float32(0.0))
    assert _module(lowered) == "jit__program_params_body"
