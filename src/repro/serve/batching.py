"""Continuous-batching serving engine over program-once crossbar state.

MemIntelli's inference semantics are weight-stationary: crossbars are
programmed once and reused for many analog matmuls.  ``greedy_generate``
amortises the programmed state over ONE fixed batch decoded in lockstep;
this module amortises it over a *stream* of requests (DESIGN.md §7):

* :class:`RequestQueue` holds submitted :class:`Request`\\ s and is the
  admission **scheduler**: requests carry a priority class
  (``"interactive"`` | ``"batch"``), each class is an arrival-ordered
  queue, and selection is weighted toward interactive traffic
  (``ServeConfig.interactive_weight``) with bounded skip-ahead past
  pool-starved heads and a cache-aware tie-break — all under a global
  aging bound (``ServeConfig.max_queue_skip``) that caps how many
  later-submitted requests may ever be admitted ahead of a waiting one
  (``max_queue_skip=0`` degenerates to strict submit-order FIFO).
* :class:`ServeLoop` owns a fixed table of ``slots`` decode lanes backed
  by a PAGED KV arena — one block pool per attention layer
  (``kv_blocks x block_size`` token rows, donated across steps) indexed
  through per-slot block tables — and ONE shared programmed pytree
  (replicated or mesh-sharded).  Each iteration (1) admits ready
  requests into free lanes through the refcounted
  :class:`~repro.serve.prefix_cache.PrefixCache` — block-aligned prompt
  prefixes already resident in the arena are MAPPED (refcount bump, no
  prefill) and only the cold tail allocates fresh blocks, with a jitted
  copy-on-write block copy when the first written position lands in a
  shared block — (2) advances every still-prefilling lane by exactly
  ONE prompt chunk starting at its first uncached position (chunked
  prefill: a long prompt never monopolises an iteration; a fully cached
  prompt recomputes exactly one token), and (3) runs one jitted
  slot-parallel decode step for the active lanes, retiring finished
  sequences (EOS / max-token), releasing their block references, and
  refilling from the queue next iteration.

Equivalence contract (tests/test_batching.py, tests/test_scheduler.py,
DESIGN.md §7): a request decoded through this engine emits exactly the
tokens ``greedy_generate`` emits for it alone — for ANY priority
assignment and admission schedule, because scheduling only reorders
*admissions*; it never touches per-lane numerics.  Every per-row
computation in the graph is row-independent and both the paged layout
and the prefill chunking are pure data movement — blocks are gathered into logical order before the
attention math, and masked tail keys contribute exactly 0.0 after
``exp``.  On the fast engine the per-step logits are BITWISE identical
across packings, chunk sizes, and block-table layouts; the faithful
row-independent engine (``adc_mode="dynamic_row"``/``fullscale``) agrees
to GEMM-kernel rounding with tokens equal.  Batch-coupled numerics
(faithful ``adc_mode="dynamic"``) are rejected at construction.
"""
from __future__ import annotations

import contextlib
import heapq
import time
import warnings
from dataclasses import dataclass, field
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.layers import MemPolicy
from repro.distributed.sharding import rules_context
from repro.kernels import ops as _kops
from repro.models import program_params
from repro.models.model import copy_paged_block, init_paged_cache

from .config import ReproDeprecationWarning, ServeConfig
from .engine import make_chunk_prefill, make_decode_step, make_verify_step
from .prefix_cache import PrefixCache
from .sampling import SamplingParams, request_keys, sample_row, sample_rows
from .spans import Recorder

__all__ = [
    "Request",
    "RequestResult",
    "RequestQueue",
    "SamplingParams",
    "ServeConfig",
    "ServeLoop",
    "ServeReport",
    "default_buckets",
]


# ---------------------------------------------------------------------------
# requests and results
# ---------------------------------------------------------------------------


#: the scheduler's priority classes, in selection-preference order
PRIORITY_CLASSES = ("interactive", "batch")


@dataclass
class Request:
    """One generation request.

    ``max_new_tokens`` counts every emitted token including the one
    derived from the prefill logits (so it matches
    ``greedy_generate(..., n_steps=max_new_tokens - 1)``).
    ``submit_time`` is seconds relative to ``ServeLoop.run`` start; the
    request is not admitted before it (Poisson replay in launch.serve).
    ``priority`` is the admission class (DESIGN.md §7 scheduling rules):
    ``"interactive"`` requests are admitted ahead of ``"batch"`` ones
    (default) under the weighted, aging-bounded scheduler — priority
    changes WHEN a request is admitted, never what it decodes to.
    ``sampling`` (a :class:`~repro.serve.sampling.SamplingParams`, or
    None for greedy) selects stochastic decoding with a PER-REQUEST
    seed: token ``i`` draws with ``fold_in(PRNGKey(seed), i)`` whatever
    slot/packing the request lands in, so sampled tokens satisfy the
    same batched==solo contract greedy tokens do
    (``greedy_generate(..., sampling=...)`` is the solo oracle).
    """

    rid: int
    tokens: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    eos_id: int | None = None
    submit_time: float = 0.0
    priority: str = "batch"  # "interactive" | "batch"
    sampling: SamplingParams | None = None  # None = greedy


@dataclass
class RequestResult:
    """Per-request outcome.  ``tokens`` are exactly the tokens solo
    ``greedy_generate`` would emit for this prompt (the batched==solo
    contract); timing fields are host wall-clock seconds relative to
    ``ServeLoop.run`` start.  ``cached_prompt_tokens`` counts prompt
    positions served from the prefix cache (KV mapped, prefill skipped)
    and ``prefill_chunks`` the chunks actually run — a fully cached
    prompt runs exactly one (the single-token logit recompute).
    Requests refused at submission (prompt longer than the largest pad
    bucket) come back with ``finish_reason="refused"``, empty
    ``tokens``, ``None`` for every admission/finish timestamp — the
    derived ``latency_s``/``ttft_s``/``itl_s`` are then ``None`` too,
    never garbage — and the reason in ``error``."""

    rid: int
    prompt_len: int
    tokens: list[int]
    finish_reason: str  # "eos" | "length" | "refused"
    submit_time: float
    admit_time: float | None
    first_token_time: float | None
    finish_time: float | None
    decode_steps: int
    logits: list[np.ndarray] | None = None  # only when collect_logits
    cached_prompt_tokens: int = 0
    prefill_chunks: int = 0
    priority: str = "batch"
    error: str | None = None  # only when finish_reason == "refused"
    tokens_drafted: int = 0  # draft proposals the target examined
    tokens_accepted: int = 0  # of those, accepted (== the target's token)

    @property
    def acceptance(self) -> float | None:
        """Per-request draft acceptance rate (speculative decoding):
        accepted / examined draft proposals, ``None`` when the request
        never ran a speculative round (spec off, or it finished at its
        first token)."""
        if self.tokens_drafted == 0:
            return None
        return self.tokens_accepted / self.tokens_drafted

    @property
    def latency_s(self) -> float | None:
        """End-to-end latency: submit → last token (``None`` for a
        refused request — it never finished)."""
        if self.finish_time is None:
            return None
        return self.finish_time - self.submit_time

    @property
    def ttft_s(self) -> float | None:
        """Time to first token: submit → first emitted token (includes
        queueing and the chunked prefill of the prompt; ``None`` for a
        refused request — it never emitted one)."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    @property
    def itl_s(self) -> float | None:
        """Mean inter-token latency over the decode phase (0.0 for
        single-token results, ``None`` for refused requests)."""
        if self.first_token_time is None or self.finish_time is None:
            return None
        n = len(self.tokens) - 1
        if n <= 0:
            return 0.0
        return (self.finish_time - self.first_token_time) / n


def _percentiles(vals) -> dict:
    # None timings (refused requests) never reach a percentile — the
    # report methods filter by completed(), this guards direct callers
    vals = sorted(v for v in vals if v is not None)
    if not vals:
        return {}
    pick = lambda q: vals[min(len(vals) - 1, int(q * len(vals)))]
    return {
        "mean": sum(vals) / len(vals),
        "p50": pick(0.50),
        "p95": pick(0.95),
        "max": vals[-1],
    }


@dataclass
class ServeReport:
    """Aggregate outcome of one ``ServeLoop.run``.

    ``results`` are in submission order.  ``kv_blocks_reused`` counts
    pool blocks that were freed by a retired request and re-allocated to
    a later one (the paged-arena reclaim at work).  The prefix-cache
    counters (DESIGN.md §7): ``prefix_cache_hits`` / ``_misses`` count
    hashed prompt blocks that were / were not already resident at
    admission, ``prefix_cache_evictions`` LRU-parked blocks reclaimed
    under allocation pressure, ``prefix_cache_cow_copies`` the jitted
    copy-on-write block copies that kept shared blocks immutable.
    ``admission_deferrals`` counts deferral EVENTS, not requests: one
    event per admission attempt (a free lane, ready request(s) waiting)
    in which no ready request could be admitted under pool pressure.
    The same pool-starved request re-checked across N iterations counts
    N events — the counter measures how often the scheduler hit the
    wall, not how many requests did (pinned by the trace-based test in
    tests/test_batching.py).  ``prefill_chunks_run`` totals prefill
    chunk steps actually executed, the device work prefix caching
    removes.

    Scheduler counters (DESIGN.md §7 scheduling rules):
    ``scheduler_skips`` counts skip events — a ready request seeing one
    later-submitted request admitted ahead of it, whether by class
    preference, pool-feasibility skip-ahead, or the cache-aware
    tie-break; ``aged_admissions`` counts requests admitted via the
    aging bound (their skip count reached ``max_queue_skip``, so they
    became the strict head until admitted — the no-starvation
    mechanism).

    ``trace`` (only with ``collect_trace=True``) records per-iteration
    scheduler activity — ``{"chunks": prefill chunks run, "decoded":
    lanes decoded, "admitted": [rid, ...] in admission order,
    "deferred": deferral events this iteration}`` — for starvation and
    deferral-semantics analysis.

    Host records of the run (``serve/spans.py``; kept out of
    ``COUNTER_FIELDS``, since a compile count depends on the process's
    cache state): ``host_spans`` maps each span name to ``[calls,
    seconds]`` of host time; ``host_gap_s`` sums the host time from the
    first device sync after a dispatch to the next jitted call, outside
    ``serve.wait`` — the idle gaps the host causes after the device
    drains; ``compiled`` names every program XLA compiled or loaded from
    the persistent cache while ``run()`` was in flight, and
    ``window_compiles`` counts them.  A request's phases are its own
    stamps on the loop's clock: queue from ``submit_time`` to
    ``admit_time``, prefill (``prefill_chunks`` chunks) from there to
    ``first_token_time``, decode from there to ``finish_time``."""

    results: list[RequestResult]
    wall_s: float
    decode_steps: int
    generated_tokens: int
    occupancy: float  # mean active slots per decode step / total slots
    kv_blocks: int = 0
    kv_blocks_reused: int = 0
    prefix_cache_hits: int = 0
    prefix_cache_misses: int = 0
    prefix_cache_evictions: int = 0
    prefix_cache_cow_copies: int = 0
    admission_deferrals: int = 0
    scheduler_skips: int = 0
    aged_admissions: int = 0
    prefill_chunks_run: int = 0
    reprogram_swaps: int = 0
    tokens_drafted: int = 0
    tokens_accepted: int = 0
    trace: list | None = None
    host_spans: dict = field(default_factory=dict)
    host_gap_s: float = 0.0
    window_compiles: int = 0
    compiled: list = field(default_factory=list)

    #: the stable counter surface — ``counters()`` keys, in order.  New
    #: counters are added HERE (and to the dataclass), so callers consume
    #: one documented mapping instead of importing ad-hoc fields.
    COUNTER_FIELDS = (
        "decode_steps",
        "generated_tokens",
        "kv_blocks",
        "kv_blocks_reused",
        "prefix_cache_hits",
        "prefix_cache_misses",
        "prefix_cache_evictions",
        "prefix_cache_cow_copies",
        "admission_deferrals",
        "scheduler_skips",
        "aged_admissions",
        "prefill_chunks_run",
        "reprogram_swaps",
        "tokens_drafted",
        "tokens_accepted",
    )

    @property
    def acceptance_rate(self) -> float | None:
        """Aggregate draft acceptance rate across the run
        (speculative decoding): ``tokens_accepted / tokens_drafted``,
        ``None`` when no speculative round ran.  With a greedy draft
        whose policy equals the target's this is exactly 1.0 — the two
        engines compute bitwise-identical trajectories — and it decays
        as crossbar non-idealities (write noise, ADC mode, drift age)
        pull the target away from the draft (the BENCH
        ``serve_speculative`` sweep)."""
        if self.tokens_drafted == 0:
            return None
        return self.tokens_accepted / self.tokens_drafted

    def counters(self) -> dict:
        """Stable name → int mapping of every scheduler counter
        (``COUNTER_FIELDS`` order).  ``reprogram_swaps`` counts completed
        generation swaps: background re-programs whose fresh state new
        admissions picked up (DESIGN.md §5)."""
        return {k: int(getattr(self, k)) for k in self.COUNTER_FIELDS}

    @property
    def tok_per_s(self) -> float:
        return self.generated_tokens / max(self.wall_s, 1e-9)

    def completed(self, priority: str | None = None) -> list[RequestResult]:
        """Results that actually ran (refused requests excluded — their
        timing fields are ``None`` and must stay out of every percentile
        aggregate), optionally filtered to one priority class."""
        return [
            r for r in self.results
            if r.finish_reason != "refused"
            and (priority is None or r.priority == priority)
        ]

    def latency_percentiles(self, priority: str | None = None) -> dict:
        """End-to-end (submit → last token) latency percentiles,
        optionally per priority class."""
        return _percentiles(r.latency_s for r in self.completed(priority))

    def ttft_percentiles(self, priority: str | None = None) -> dict:
        """Time-to-first-token percentiles — the responsiveness metric
        chunked prefill, prefix caching, and the priority-class
        scheduler target.  ``priority="interactive"`` isolates the
        latency class the scheduler protects from batch floods."""
        return _percentiles(r.ttft_s for r in self.completed(priority))

    def itl_percentiles(self, priority: str | None = None) -> dict:
        """Per-request mean inter-token-latency percentiles (decode-phase
        smoothness; requests with a single token are excluded),
        optionally per priority class."""
        return _percentiles(
            r.itl_s for r in self.completed(priority) if len(r.tokens) > 1
        )


@dataclass
class _QueueEntry:
    """One ready request plus its scheduler age.  ``order`` is the
    global submission order key ``(submit_time, seq)`` — "earlier" means
    an earlier arrival, ties broken by submission sequence.  ``skips``
    counts admissions of later-submitted requests that happened while
    this one was ready (the quantity the aging bound caps)."""

    order: tuple
    request: Request
    skips: int = 0


class RequestQueue:
    """Priority-class admission scheduler (DESIGN.md §7).

    Each :class:`Request` carries a ``priority`` class —
    ``"interactive"`` (latency-sensitive) or ``"batch"`` (throughput
    traffic, the default).  Classes are arrival-ordered queues;
    :meth:`select` picks the next admission by three rules, in order:

    1. **Aging bound — no permanent starvation.**  A *skip* is one
       admission of a later-submitted request while a ready request
       waits; ``max_queue_skip`` caps each request's lifetime skips.  A
       request at the cap is *aged*: until it admits, only it and
       requests submitted before it are candidates (admitting an older
       request cannot skip it further).  So for EVERY request, at most
       ``max_queue_skip`` later-submitted requests are ever admitted
       ahead of it — ``max_queue_skip=0`` is strict submit-order FIFO
       (priority classes and skip-ahead disabled).
    2. **Weighted class selection.**  While both classes hold ready
       requests, interactive is preferred for at most
       ``interactive_weight`` consecutive admissions, then one batch
       request goes first — a batch flood cannot starve interactive
       TTFT, and interactive floods cannot starve batch beyond the
       weight (plus rule 1's hard cap).
    3. **Cache-aware, pool-feasible pick within the class.**  Among the
       first ``max_queue_skip + 1`` ready requests of the class, prefer
       the longest resident prefix (the ``probe`` — parked blocks
       become hits before eviction drains them; stable FIFO tie-break),
       and admit the first candidate whose block need the allocator
       covers (``try_admit``), skipping pool-starved or cache-cold
       entries ahead of it.

    Scheduling decides only WHEN a request is admitted; per-lane
    numerics are untouched, so every request still decodes to exactly
    its solo tokens (tests/test_scheduler.py).

    Counters: ``skips`` totals skip events, ``aged_admissions`` counts
    requests admitted via rule 1's cap, ``deferrals`` counts deferral
    events — :meth:`select` calls that found ready request(s) but could
    admit none under pool pressure (re-checking the same request next
    iteration counts again)."""

    def __init__(
        self, interactive_weight: int = 4, max_queue_skip: int = 8
    ):
        if interactive_weight < 1:
            raise ValueError("interactive_weight must be >= 1")
        if max_queue_skip < 0:
            raise ValueError("max_queue_skip must be >= 0")
        self.interactive_weight = int(interactive_weight)
        self.max_queue_skip = int(max_queue_skip)
        # not-yet-arrived requests: per-class (submit_time, seq, r) heaps
        self._pending: dict[str, list] = {c: [] for c in PRIORITY_CLASSES}
        # arrived requests: per-class FIFO lists of _QueueEntry
        self._ready: dict[str, list] = {c: [] for c in PRIORITY_CLASSES}
        self._seq = 0
        # consecutive interactive admissions while batch was waiting
        self._credit = 0
        self.skips = 0
        self.aged_admissions = 0
        self.deferrals = 0

    def submit(self, request: Request) -> None:
        if request.priority not in PRIORITY_CLASSES:
            raise ValueError(
                f"request {request.rid}: priority must be one of "
                f"{PRIORITY_CLASSES} (got {request.priority!r})"
            )
        heapq.heappush(
            self._pending[request.priority],
            (request.submit_time, self._seq, request),
        )
        self._seq += 1

    def __len__(self) -> int:
        return sum(len(h) for h in self._pending.values()) + sum(
            len(d) for d in self._ready.values()
        )

    def next_arrival(self) -> float | None:
        """Earliest submit_time among not-yet-arrived requests (ready
        ones have, by definition, already arrived)."""
        ts = [h[0][0] for h in self._pending.values() if h]
        return min(ts) if ts else None

    def _release(self, now: float) -> None:
        for c in PRIORITY_CLASSES:
            h = self._pending[c]
            while h and h[0][0] <= now:
                t, seq, r = heapq.heappop(h)
                self._ready[c].append(_QueueEntry(order=(t, seq), request=r))

    def has_ready(self, now: float) -> bool:
        self._release(now)
        return any(self._ready.values())

    def pop_ready(self, now: float) -> Request | None:
        """Plain submit-order FIFO pop across classes — the legacy
        surface for callers that do their own admission.  Bypasses the
        scheduler (no skip accounting)."""
        self._release(now)
        heads = [d[0] for d in self._ready.values() if d]
        if not heads:
            return None
        e = min(heads, key=lambda e: e.order)
        self._ready[e.request.priority].remove(e)
        return e.request

    # -- the scheduler ------------------------------------------------------

    def select(self, now: float, try_admit, probe=None):
        """One admission attempt.  ``try_admit(request)`` must return a
        non-None admission handle on success (committing the request's
        resources) or None when the pool cannot cover it; ``probe``
        optionally maps a request to its resident-prefix length for the
        cache-aware tie-break.  Returns ``(request, handle)`` or None —
        when ready requests existed but none could admit, that is ONE
        deferral event."""
        self._release(now)
        ready_all = [e for c in PRIORITY_CLASSES for e in self._ready[c]]
        if not ready_all:
            return None
        contended = all(self._ready[c] for c in PRIORITY_CLASSES)
        aged = [e for e in ready_all if e.skips >= self.max_queue_skip]
        if aged:
            head = min(aged, key=lambda e: e.order)
            # only candidates whose admission cannot age ``head`` (or
            # any older entry) past the bound: itself and anything
            # submitted before it, oldest first
            cands = sorted(
                (e for e in ready_all if e.order <= head.order),
                key=lambda e: e.order,
            )
        else:
            cands = []
            for cls in self._class_order(contended):
                window = self._ready[cls][: self.max_queue_skip + 1]
                if probe is not None and len(window) > 1:
                    # stable sort: FIFO order breaks residency ties
                    window = sorted(
                        window, key=lambda e: -probe(e.request)
                    )
                cands.extend(window)
        for e in cands:
            handle = try_admit(e.request)
            if handle is not None:
                return self._admit(e, contended), handle
        self.deferrals += 1
        return None

    def _class_order(self, contended: bool) -> tuple:
        if contended:
            if self._credit < self.interactive_weight:
                return ("interactive", "batch")
            return ("batch", "interactive")
        return tuple(c for c in PRIORITY_CLASSES if self._ready[c])

    def _admit(self, e: _QueueEntry, contended: bool) -> Request:
        cls = e.request.priority
        self._ready[cls].remove(e)
        if self.max_queue_skip > 0 and e.skips >= self.max_queue_skip:
            self.aged_admissions += 1
        # every still-waiting earlier-submitted request was just skipped
        for c in PRIORITY_CLASSES:
            for e2 in self._ready[c]:
                if e2.order < e.order:
                    e2.skips += 1
                    self.skips += 1
        if contended:
            if cls == "interactive":
                self._credit = min(self._credit + 1, self.interactive_weight)
            else:
                self._credit = 0
        return e.request


# ---------------------------------------------------------------------------
# jitted step cache — shared across ServeLoop instances so repeated
# construction (tests, sweeps over slot counts) never re-jits; shape
# specialisation per (slots, chunk_len, pool geometry) is jax's own cache.
# ---------------------------------------------------------------------------


def _kernel_state():
    """Kernel-selection state the serving traces bake in at trace time.

    ``resolve_attention_backend`` / ``kernel_interpret`` are consulted
    while TRACING (models/attention.py), so a flipped backend or
    interpret override must miss this cache — otherwise a test that
    forces the Pallas path would silently reuse an XLA-path trace."""
    return (
        _kops.resolve_attention_backend(),
        _kops.kernels_enabled(),
        _kops.kernel_interpret(),
    )


@lru_cache(maxsize=None)
def _jit_chunk_cached(cfg, policy, compute_dtype, mesh, kernel_state):
    fn = make_chunk_prefill(cfg, policy, compute_dtype=compute_dtype)
    # donate the arena: chunk KV writes alias the previous buffer.
    # t_now (trailing arg) is the traced drift-clock scalar — None when
    # drift is off, which traces the identical pre-drift graph.
    return jax.jit(fn, donate_argnums=(1,))


def _jit_chunk(cfg, policy, compute_dtype, mesh):
    return _jit_chunk_cached(cfg, policy, compute_dtype, mesh, _kernel_state())


@lru_cache(maxsize=None)
def _jit_decode_cached(cfg, policy, compute_dtype, mesh, kernel_state,
                       sampled):
    fn = make_decode_step(cfg, policy, compute_dtype=compute_dtype)

    # ``sampled`` is part of the cache key (like ``kernel_state``): the
    # two step functions trace DIFFERENT graphs over the same leading
    # arguments, so a loop flipped from greedy to sampled (or back)
    # between constructions must never reuse the other mode's trace.
    # Both are named ``step``, so both run as the module ``jit_step``:
    # the benchmark reads that name in the device trace.
    if sampled:
        def step(params, cache, tokens, programmed, active, t_now,
                 keys, temps, top_ks, top_ps):
            logits, cache = fn(
                params, cache, tokens, programmed, active, t_now
            )
            toks = sample_rows(keys, logits, temps, top_ks, top_ps)
            return logits, toks, cache
    else:
        def step(params, cache, tokens, programmed, active, t_now):
            logits, cache = fn(
                params, cache, tokens, programmed, active, t_now
            )
            return logits, jnp.argmax(logits, axis=-1), cache

    # donate the arena: each step's KV writes alias the previous buffer
    return jax.jit(step, donate_argnums=(1,))


def _jit_decode(cfg, policy, compute_dtype, mesh, sampled=False):
    return _jit_decode_cached(
        cfg, policy, compute_dtype, mesh, _kernel_state(), bool(sampled)
    )


@lru_cache(maxsize=None)
def _jit_spec_round_cached(cfg, policy, draft_policy, compute_dtype,
                           mesh, kernel_state, n_draft):
    """One FUSED speculative round: frontier commit on both caches,
    the scanned draft chain, and the target's batched multi-token
    verify — a single dispatch per round where a staged version pays
    four plus two host round-trips (the draft tokens never leave the
    device between proposal and verification)."""
    draft_fn = make_decode_step(
        cfg, draft_policy, compute_dtype=compute_dtype
    )
    verify_fn = make_verify_step(cfg, policy, compute_dtype=compute_dtype)

    def round_(params, cache, draft_cache, tokens, pos_t, pos_d,
               programmed, draft_programmed, active, t_now,
               keys_d, keys_v, temps, top_ks, top_ps):
        """tokens (K,): last emitted token per slot.  pos_t/pos_d (K,):
        the accepted frontier from the previous round (accept =
        advance past the matched drafts, rollback = rewind over the
        rejected tail) — pure bookkeeping: rejected positions' KV
        stays in the arena but is dead by the ``ki <= pos`` length
        mask until this round's writes re-cover it.  keys_d
        (n_draft, K, 2) / keys_v (K, C, 2): draft step j and verify
        column j draw emission index e0+j of their slot with the SAME
        key on (numerically different) logits — a matching draw is
        exactly an accepted draft.  Returns per-position target logits
        (K, C, V), the token the TARGET emits at each position —
        sampled with exactly the keys the non-speculative path would
        use, so the accept rule (draft == target token) preserves the
        trajectory token for token — the proposed token matrix
        tokens_c (K, C), and both caches (target pos NOT advanced)."""
        cache = {**cache, "pos": pos_t}
        draft_cache = {**draft_cache, "pos": pos_d}

        def step(carry, step_keys):
            dcache, toks = carry
            logits, dcache = draft_fn(
                params, dcache, toks, draft_programmed, active, t_now
            )
            toks = sample_rows(step_keys, logits, temps, top_ks, top_ps)
            return (dcache, toks), toks

        (draft_cache, last), drafts = lax.scan(
            step, (draft_cache, tokens), keys_d
        )
        # one extra draft decode feeding the LAST proposal so its KV
        # lands in the draft cache too: a fully-accepted round advances
        # the frontier one past the scan's last write, and without this
        # the next round's draft attention would read a never-written
        # position (stale KV → spurious rejections).  Logits discarded.
        _, draft_cache = draft_fn(
            params, draft_cache, last, draft_programmed, active, t_now
        )
        # column 0 = the last emitted token, columns 1..n_draft = the
        # draft chain; column c's verify logits are the target's
        # logits for emission index e0+c
        tokens_c = jnp.concatenate(
            [tokens[:, None], jnp.moveaxis(drafts, 0, 1)], axis=1
        )
        k_sl, c = tokens_c.shape
        logits, cache = verify_fn(
            params, cache, tokens_c, programmed, active, t_now
        )
        bc = lambda a: jnp.broadcast_to(a[:, None], (k_sl, c)).reshape(-1)
        toks = sample_rows(
            keys_v.reshape(k_sl * c, -1), logits.reshape(k_sl * c, -1),
            bc(temps), bc(top_ks), bc(top_ps),
        ).reshape(k_sl, c)
        return logits, toks, tokens_c, cache, draft_cache

    return jax.jit(round_, donate_argnums=(1, 2))


def _jit_spec_round(cfg, policy, draft_policy, compute_dtype, mesh,
                    n_draft):
    return _jit_spec_round_cached(
        cfg, policy, draft_policy, compute_dtype, mesh, _kernel_state(),
        int(n_draft),
    )


@lru_cache(maxsize=None)
def _jit_sample1():
    """Single-row sampler for the first token (prefill logits): the
    same ``sample_row`` the batched steps vmap, so the draw is bitwise
    the solo oracle's."""
    return jax.jit(sample_row)


@lru_cache(maxsize=None)
def _jit_admit():
    def admit(cache, slot, bt_row):
        """Bind a slot to a fresh block-table row and reset its pos —
        pure bookkeeping, no KV bytes move."""
        tables = lax.dynamic_update_slice(
            cache["block_tables"], bt_row[None], (slot, 0)
        )
        pos = lax.dynamic_update_slice(
            cache["pos"], jnp.zeros((1,), jnp.int32), (slot,)
        )
        return {**cache, "block_tables": tables, "pos": pos}

    return jax.jit(admit, donate_argnums=(0,))


@lru_cache(maxsize=None)
def _jit_copy():
    """Copy-on-write block copy (jitted, arena donated): run at
    admission when a request's first written position lands in a block
    another live request still references — the sharer keeps reading
    ``src``, this lane's table points at the ``dst`` clone before any
    write happens, so a block is never mutated while refcount > 1."""
    return jax.jit(copy_paged_block, donate_argnums=(0,))


def default_buckets(max_len: int) -> tuple[int, ...]:
    """Prompt-length pad buckets: powers of two capped at ``max_len``.
    With ``prefill_chunk=None`` these are the single-chunk lengths (one
    compile per bucket, no recompile per prompt length)."""
    out = []
    b = 8
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


# ---------------------------------------------------------------------------
# the serving loop
# ---------------------------------------------------------------------------


@dataclass
class _SlotState:
    request: Request
    admit_time: float
    plan: object  # prefix_cache.AdmitPlan — owns the block references
    # the programmed generation this request was admitted on: the lane
    # runs EVERY chunk and decode step against this exact pytree until it
    # retires (the no-mid-request-swap rule, DESIGN.md §5)
    programmed: object = None
    gen: int = 0
    prefill_pos: int = 0
    first_token_time: float = 0.0
    out: list = field(default_factory=list)
    logits: list | None = None
    decode_steps: int = 0
    prefill_chunks: int = 0
    finish_reason: str | None = None
    # sampling: keys[i] is the per-request key of emission index i (a
    # pure function of the request's seed — slot, packing, and mesh
    # never enter it, the batched==solo anchor for sampled tokens);
    # temp == 0.0 rows collapse to exact argmax inside sample_row
    keys: np.ndarray | None = None
    temp: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    # speculative decoding: the draft engine's prefill frontier on its
    # own paged cache, plus the per-request acceptance counters
    # (drafts EXAMINED by the accept rule / drafts that matched)
    draft_pos: int = 0
    tokens_drafted: int = 0
    tokens_accepted: int = 0

    @property
    def blocks(self) -> list:
        return self.plan.blocks


#: the one-release-deprecated loose keywords of ServeLoop.__init__ —
#: exactly the ServeConfig fields (programmed is a direct argument).
_LEGACY_KWARGS = frozenset(
    f.name for f in __import__("dataclasses").fields(ServeConfig)
)


class ServeLoop:
    """Continuous-batching greedy decoding against shared programmed state.

    Scheduler (DESIGN.md §7) — per iteration, in order:

    1. **Admit**: every free lane takes the request the priority-class
       scheduler selects (:meth:`RequestQueue.select` — weighted
       interactive-over-batch preference, bounded skip-ahead past
       pool-starved heads, cache-aware tie-break, all under the
       ``max_queue_skip`` aging bound), if the block pool can cover its
       full KV need (``ceil((prompt_len + max_new - 1) / block_size)``
       blocks, eager so decode never stalls mid-stream); when no ready
       request fits, admission defers (one deferral event) until a
       retirement frees blocks.  With ``prefix_cache``
       (default on), block-aligned prompt prefixes already resident in
       the arena are MAPPED instead of allocated (refcount bump), only
       the cold tail takes fresh blocks, and a fully cached prompt's
       last hit block is cloned first when it is shared (jitted
       copy-on-write) — shared blocks are immutable while refcount > 1.
    2. **Prefill one chunk per lane**: each still-prefilling lane
       advances by exactly ONE chunk of ``prefill_chunk`` tokens
       (``None`` = the remaining prompt in one bucket-padded chunk),
       starting at its first uncached position.  A long prompt therefore
       spreads over many iterations and can never monopolise one — and
       a cached prefix skips its chunks entirely (a fully cached prompt
       recomputes exactly one token for its first-token logits).
    3. **Decode**: one jitted slot-parallel step over the active lanes;
       finished sequences (EOS / max-token) retire, each of their block
       references drops, zero-reference blocks park in the LRU pool
       (drained only under allocation pressure) or return to the free
       list, and the lane re-enters admission next iteration.

    Numerics contract: per-request tokens are identical to solo
    ``greedy_generate``; fast-path logits are bitwise invariant to
    packing, chunk size, and block placement (module docstring).
    Policies that couple batch rows (faithful ``adc_mode="dynamic"``)
    are rejected.

    Supports every all-attention decoder family (dense / MoE — per-row
    routing keeps MoE dispatch request-local).  Recurrent-state families
    (ssm / hybrid) need exact-length prefill (right-padding would pollute
    the carried state) and encoder-decoder / VLM families need per-request
    side inputs — both raise ``NotImplementedError`` for now.
    """

    def __init__(
        self,
        params,
        cfg,
        config: ServeConfig | None = None,
        *,
        programmed=None,
        **legacy,
    ):
        """``ServeLoop(params, cfg, ServeConfig(...))`` is the supported
        construction; ``programmed`` optionally injects a pre-built
        generation-0 programmed pytree (an artifact, not a knob — it
        stays a direct argument).

        The legacy loose-keyword form ``ServeLoop(params, cfg,
        policy=…, slots=…, …)`` still works for one release: the kwargs
        are folded into a ServeConfig behind a single
        :class:`ReproDeprecationWarning` per construction.  Mixing
        ``config`` with legacy kwargs is an error."""
        if legacy:
            unknown = set(legacy) - _LEGACY_KWARGS
            if unknown:
                raise TypeError(
                    f"ServeLoop got unexpected keyword(s) {sorted(unknown)}"
                )
            if config is not None:
                raise TypeError(
                    "pass EITHER a ServeConfig or legacy keywords, not "
                    f"both (got config= and {sorted(legacy)})"
                )
            warnings.warn(
                "ServeLoop(policy=..., slots=..., ...) loose keywords are "
                "deprecated; pass ServeLoop(params, cfg, ServeConfig(...))",
                ReproDeprecationWarning,
                stacklevel=2,
            )
            config = ServeConfig(**legacy)
        elif config is None:
            config = ServeConfig()
        self.config = config
        policy = config.policy
        slots = config.slots
        max_len = config.max_len
        prefill_chunk = config.prefill_chunk
        block_size = config.block_size
        kv_blocks = config.kv_blocks
        buckets = config.buckets
        compute_dtype = config.compute_dtype
        weight_stationary = config.weight_stationary
        mesh = config.mesh
        collect_logits = config.collect_logits
        collect_trace = config.collect_trace
        allow_coupled_numerics = config.allow_coupled_numerics
        prefix_cache = config.prefix_cache
        if cfg.encoder is not None or cfg.vision_prefix:
            raise NotImplementedError(
                "continuous batching needs per-request side inputs for "
                f"{cfg.family} models"
            )
        kinds = {cfg.layer_kind(i)[0] for i in range(cfg.n_layers)}
        if kinds != {"attn"}:
            raise NotImplementedError(
                "continuous batching requires all-attention layers "
                f"(got {sorted(kinds)}): recurrent state cannot be "
                "prefilled with right-padded prompts"
            )
        self.policy = policy or MemPolicy(default=None)
        if not allow_coupled_numerics:
            coupled = [
                pat
                for pat, c in (("default", self.policy.default),)
                + tuple(self.policy.overrides)
                if c is not None and not c.row_independent
            ]
            if coupled:
                raise ValueError(
                    "policy couples batch rows through the ADC range "
                    f"(faithful adc_mode='dynamic' at {coupled}): a "
                    "request would decode differently next to strangers. "
                    "Use adc_mode='dynamic_row' (per-read ranging) or "
                    "'fullscale', or pass allow_coupled_numerics=True."
                )
        # --- speculative decoding (DESIGN.md §7): the draft engine is
        # folded from the SAME params under its own (usually cheaper)
        # policy; it proposes spec_k tokens per slot per round and the
        # programmed target verifies them in one batched multi-token
        # forward, so speculation changes throughput, never output
        self.spec_k = int(config.spec_k)
        self.draft_policy = config.draft_policy or MemPolicy(default=None)
        if self.spec_k and not allow_coupled_numerics:
            coupled = [
                pat
                for pat, c in (("default", self.draft_policy.default),)
                + tuple(self.draft_policy.overrides)
                if c is not None and not c.row_independent
            ]
            if coupled:
                raise ValueError(
                    "draft_policy couples batch rows through the ADC "
                    f"range (faithful adc_mode='dynamic' at {coupled}): "
                    "draft proposals (hence acceptance) would depend on "
                    "slot neighbours.  Use adc_mode='dynamic_row' or "
                    "'fullscale', or pass allow_coupled_numerics=True."
                )
        self.params = params
        self.cfg = cfg
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.block_size = int(block_size)
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.blocks_per_slot = -(-self.max_len // self.block_size)
        # +1: physical block 0 is the reserved trash block
        self.kv_blocks = int(
            kv_blocks
            if kv_blocks is not None
            else self.slots * self.blocks_per_slot + 1
        )
        if self.kv_blocks < 2:
            raise ValueError("kv_blocks must be >= 2 (block 0 is trash)")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1 (or None)")
        self.prefill_chunk = prefill_chunk
        self.buckets = tuple(sorted(buckets or default_buckets(max_len)))
        if self.buckets[-1] > self.max_len:
            raise ValueError("buckets must not exceed max_len")
        self.compute_dtype = compute_dtype
        self.cache_dtype = (
            jnp.float32 if compute_dtype == jnp.float32 else jnp.bfloat16
        )
        self.mesh = mesh
        self.collect_logits = collect_logits
        self.collect_trace = collect_trace
        ctx = (
            rules_context(mesh) if mesh is not None
            else contextlib.nullcontext()
        )
        with ctx:
            if (
                programmed is None
                and weight_stationary
                and self.policy.enabled
            ):
                # PRNGKey(0) = the static serving key of the step makers
                programmed = program_params(
                    params, cfg, self.policy, jax.random.PRNGKey(0),
                    mesh=mesh,
                )
            # the draft's programmed state is pinned at generation 0 —
            # drafts only steer throughput, so the refresh machinery
            # never re-programs the draft (acceptance may sag as the
            # TARGET ages/refreshes; that is the measured quantity)
            draft_programmed = None
            if (
                self.spec_k
                and weight_stationary
                and self.draft_policy.enabled
            ):
                draft_programmed = program_params(
                    params, cfg, self.draft_policy, jax.random.PRNGKey(0),
                    mesh=mesh,
                )
        self.programmed = programmed
        self.draft_programmed = draft_programmed
        self._chunk = _jit_chunk(cfg, self.policy, compute_dtype, mesh)
        self._decode = _jit_decode(cfg, self.policy, compute_dtype, mesh)
        self._admit = _jit_admit()
        self._copy = _jit_copy()
        if self.spec_k:
            self._draft_chunk = _jit_chunk(
                cfg, self.draft_policy, compute_dtype, mesh
            )
            self._spec_round = _jit_spec_round(
                cfg, self.policy, self.draft_policy, compute_dtype,
                mesh, self.spec_k,
            )
        # host-side refcounted block allocator (block 0 = trash, never
        # handed out); prefix_cache=False degrades it to the plain
        # free list with identical allocation order
        self.prefix_cache = bool(prefix_cache)
        self._blocks = PrefixCache(
            self.kv_blocks, self.block_size, enabled=self.prefix_cache
        )
        # --- priority-class scheduler knobs (DESIGN.md §7)
        self.interactive_weight = int(config.interactive_weight)
        self.max_queue_skip = int(config.max_queue_skip)
        # --- programmed-state generations (drift / refresh, DESIGN.md §5)
        # ``self.programmed`` is always the CURRENT generation; lanes pin
        # the pytree they were admitted on, so a swap never touches an
        # in-flight request.  The generation counter persists across
        # run() calls — re-programming is physical device state, not
        # per-stream bookkeeping.
        self.weight_stationary = bool(weight_stationary)
        self.refresh_every = config.refresh_every
        self.clock = config.clock
        self.generation = 0
        if self.refresh_every is not None and self.programmed is None:
            raise ValueError(
                "refresh_every needs weight-stationary programmed state "
                "(a hardware policy with weight_stationary=True): there "
                "is nothing to re-program"
            )
        # drift is evaluated only when some layer config carries a model:
        # otherwise t_now stays None and the steps trace the identical
        # drift-free graph (the bitwise-off contract)
        self._drift_on = any(
            c is not None and c.drift is not None
            for _, c in (("default", self.policy.default),)
            + tuple(self.policy.overrides)
        )
        if self.spec_k:
            # a drifting DRAFT also needs the per-iteration clock (its
            # proposals age even while the target stays drift-free)
            self._drift_on = self._drift_on or any(
                c is not None and c.drift is not None
                for _, c in (("default", self.draft_policy.default),)
                + tuple(self.draft_policy.overrides)
            )

    # -- block allocator ----------------------------------------------------

    def _blocks_needed(self, r: Request) -> int:
        # KV positions written: prompt 0..plen-1, decode up to
        # plen+max_new-2 (the final emitted token's KV is never stored)
        return -(-(len(r.tokens) + r.max_new_tokens - 1) // self.block_size)

    # -- helpers ------------------------------------------------------------

    def _bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if b >= prompt_len:
                return b
        # unreachable from the loop: prompts longer than the largest
        # bucket are refused per-request in run() before admission
        raise ValueError(f"prompt_len {prompt_len} > max bucket")

    def _refusal(self, r: Request) -> str | None:
        """Per-request refusal reason, or None when servable.  Prompts
        longer than the largest pad bucket used to raise out of
        ``_bucket_for`` MID-RUN, killing every other in-flight request;
        they are refused up front instead (result with
        ``finish_reason="refused"``)."""
        if len(r.tokens) > self.buckets[-1]:
            return (
                f"prompt_len({len(r.tokens)}) exceeds the largest "
                f"prefill bucket ({self.buckets[-1]})"
            )
        return None

    def _validate(self, r: Request) -> None:
        n = len(r.tokens)
        if r.priority not in PRIORITY_CLASSES:
            raise ValueError(
                f"request {r.rid}: priority must be one of "
                f"{PRIORITY_CLASSES} (got {r.priority!r})"
            )
        if n < 1:
            raise ValueError(f"request {r.rid}: empty prompt")
        if r.max_new_tokens < 1:
            raise ValueError(f"request {r.rid}: max_new_tokens < 1")
        if n + r.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {r.rid}: prompt_len({n}) + max_new"
                f"({r.max_new_tokens}) exceeds max_len({self.max_len})"
            )
        if self._blocks_needed(r) > self.kv_blocks - 1:
            raise ValueError(
                f"request {r.rid}: needs {self._blocks_needed(r)} KV "
                f"blocks but the pool holds {self.kv_blocks - 1}"
            )

    def _emit(self, st: _SlotState, tok: int, logit_row) -> bool:
        """Record one token; returns True when the request just finished —
        nothing is ever emitted past EOS / max-token (the stop contract)."""
        st.out.append(tok)
        if st.logits is not None:
            st.logits.append(np.asarray(logit_row))
        r = st.request
        if r.eos_id is not None and tok == r.eos_id:
            st.finish_reason = "eos"
        elif len(st.out) >= r.max_new_tokens:
            st.finish_reason = "length"
        return st.finish_reason is not None

    def _result(self, st: _SlotState, now: float) -> RequestResult:
        return RequestResult(
            rid=st.request.rid,
            prompt_len=len(st.request.tokens),
            tokens=st.out,
            finish_reason=st.finish_reason,
            submit_time=st.request.submit_time,
            admit_time=st.admit_time,
            first_token_time=st.first_token_time,
            finish_time=now,
            decode_steps=st.decode_steps,
            logits=st.logits,
            cached_prompt_tokens=st.plan.cached_len,
            prefill_chunks=st.prefill_chunks,
            priority=st.request.priority,
            tokens_drafted=st.tokens_drafted,
            tokens_accepted=st.tokens_accepted,
        )

    def _refused_result(self, r: Request, msg: str) -> RequestResult:
        # a refused request was never admitted and never emitted a
        # token: its admit/first-token/finish timestamps are None, so
        # the derived latencies are None (not garbage) and completed()
        # keeps them out of every percentile aggregate
        return RequestResult(
            rid=r.rid,
            prompt_len=len(r.tokens),
            tokens=[],
            finish_reason="refused",
            submit_time=r.submit_time,
            admit_time=None,
            first_token_time=None,
            finish_time=None,
            decode_steps=0,
            priority=r.priority,
            error=msg,
        )

    # -- the loop -----------------------------------------------------------

    def run(self, requests) -> ServeReport:
        """Serve ``requests`` to completion; returns per-request results
        (same order as submitted) plus aggregate throughput/latency.
        Tokens per request satisfy the batched==solo contract (module
        docstring); requests whose prompt + budget exceed ``max_len`` or
        the whole block pool raise, not clamp.  A prompt longer than the
        largest pad bucket is refused PER-REQUEST (result with
        ``finish_reason="refused"`` and the reason in ``error``) so one
        oversized prompt never kills the rest of the stream."""
        requests = list(requests)
        rids = [r.rid for r in requests]
        if len(set(rids)) != len(rids):
            raise ValueError("request rids must be unique")
        refused: dict[int, RequestResult] = {}
        live = []
        for r in requests:
            msg = self._refusal(r)
            if msg is not None:
                refused[r.rid] = self._refused_result(r, msg)
                continue
            self._validate(r)
            live.append(r)
        ctx = (
            rules_context(self.mesh) if self.mesh is not None
            else contextlib.nullcontext()
        )
        rec = Recorder()
        with rec.listening(), ctx:
            report = self._run(live, rec)
        report.host_spans = rec.totals
        report.host_gap_s = rec.gap_s
        report.compiled = rec.compiled
        report.window_compiles = len(rec.compiled)
        if refused:
            by_rid = {res.rid: res for res in report.results}
            by_rid.update(refused)
            report.results = [by_rid[r.rid] for r in requests]
        return report

    def _run(self, requests, rec: Recorder) -> ServeReport:
        span, dispatch = rec.span, rec.dispatch
        queue = RequestQueue(
            interactive_weight=self.interactive_weight,
            max_queue_skip=self.max_queue_skip,
        )
        for r in requests:
            queue.submit(r)
        # fresh allocator per run — cache contents and stats are
        # per-run, and a run that raised mid-flight must not leak
        # blocks (or stale hashes) into the next one
        self._blocks.reset()
        K = self.slots
        cache = init_paged_cache(
            self.cfg, K, self.max_len, self.block_size, self.kv_blocks,
            self.cache_dtype,
        )
        # per-RUN mode selection: the sampled and greedy step functions
        # are distinct lru-cached jits (``sampled`` is in the cache
        # key), so an all-greedy run keeps the exact pre-sampling trace
        # and back-to-back runs that flip modes never share a trace
        run_sampled = any(r.sampling is not None for r in requests)
        decode = (
            _jit_decode(
                self.cfg, self.policy, self.compute_dtype, self.mesh,
                sampled=True,
            )
            if run_sampled else self._decode
        )
        spec = self.spec_k > 0
        C = self.spec_k + 1
        draft_cache = None
        if spec:
            # the draft's own arena: statically partitioned (slot k owns
            # blocks 1+k*nbps .. 1+(k+1)*nbps-1; block 0 stays trash) —
            # no prefix cache, no allocator, nothing to leak
            draft_cache = init_paged_cache(
                self.cfg, K, self.max_len, self.block_size,
                K * self.blocks_per_slot + 1, self.cache_dtype,
            )
        slot_state: list[_SlotState | None] = [None] * K
        next_tok = np.zeros((K,), np.int32)
        active = np.zeros((K,), bool)
        # per-slot sampling surface of the CURRENT occupant (temp 0.0 =
        # exact argmax inside sample_row, so greedy requests mixed into
        # a sampled batch stay greedy)
        slot_temp = np.zeros((K,), np.float32)
        slot_topk = np.zeros((K,), np.int32)
        slot_topp = np.ones((K,), np.float32)
        results: dict[int, RequestResult] = {}
        total_chunks = 0
        swaps = 0
        trace: list | None = [] if self.collect_trace else None
        t0 = time.monotonic()
        decode_steps = 0
        generated = 0
        occupancy = 0

        def now() -> float:
            return time.monotonic() - t0

        # The DEVICE clock: drives drift aging and the refresh schedule.
        # Injectable (ServeConfig.clock) so drift/refresh timing is
        # deterministic under test; defaults to the run-relative wall
        # clock.  Latency metrics always use the wall clock above.
        dev_clock = self.clock or now
        # one start-of-run sample regardless of refresh arming, so the
        # per-iteration clock sequence (and with it drift aging) is
        # identical whether or not background refresh is enabled
        t_start = dev_clock()
        next_refresh = (
            None if self.refresh_every is None
            else t_start + self.refresh_every
        )

        while len(results) < len(requests):
            with span("serve.iteration") as it:
                # 0. one device-clock sample per iteration: every chunk and
                # decode call of this iteration evaluates drift at the same
                # instant, and the refresh trigger compares against it
                t_dev = dev_clock()
                t_arg = jnp.float32(t_dev) if self._drift_on else None
                if next_refresh is not None and t_dev >= next_refresh:
                    draining = any(
                        st is not None and st.gen != self.generation
                        for st in slot_state
                    )
                    if not draining:
                        # generation N+1: fresh programming noise
                        # (fold_in(key0, gen)) and a fresh t_prog stamp,
                        # built SHARDED like generation 0.  JAX dispatches
                        # the programming pass asynchronously — generation N
                        # keeps decoding below while it materialises; only a
                        # lane that later pins gen N+1 ever blocks on it.
                        # At most two generations are live: while old-gen
                        # lanes drain, the next refresh waits (the
                        # double-buffer bound on transient memory).
                        self.generation += 1
                        key = jax.random.fold_in(
                            jax.random.PRNGKey(0), self.generation
                        )
                        with span("serve.refresh", gen=self.generation):
                            dispatch()
                            self.programmed = program_params(
                                self.params, self.cfg, self.policy, key,
                                mesh=self.mesh, t_prog=t_dev,
                            )
                        swaps += 1
                        next_refresh = t_dev + self.refresh_every
                # 1. admit: the scheduler binds ready requests to free
                # lanes per the DESIGN.md §7 rules (aging bound first, then
                # weighted round-robin over classes, then the cache-aware
                # pool-feasible pick), eagerly allocating each pick's full
                # KV block need; a pool-starved request waits for a
                # retirement unless a bounded skip-ahead can fill the lane
                def_before = queue.deferrals
                admitted_now: list[int] = []
                probe = (
                    (lambda rq: self._blocks.resident_prefix_len(rq.tokens))
                    if self.prefix_cache else None
                )
                for k in range(K):
                    if slot_state[k] is not None:
                        continue
                    # one span per pick: every admitted lane (with its rid),
                    # and the iteration's last pick that found none
                    with span("serve.admit") as adm:
                        sel = queue.select(
                            now(),
                            lambda rq: self._blocks.admit(
                                rq.tokens, self._blocks_needed(rq)
                            ),
                            probe=probe,
                        )
                        if sel is None:
                            break
                        r, plan = sel
                        adm.set_metadata(rid=r.rid)
                        admitted_now.append(r.rid)
                        bt_row = np.zeros((self.blocks_per_slot,), np.int32)
                        bt_row[: len(plan.blocks)] = plan.blocks
                        dispatch()
                        cache = self._admit(
                            cache, jnp.int32(k), jnp.asarray(bt_row)
                        )
                        if plan.cow is not None:
                            # the one device cost of sharing: clone the shared
                            # block this lane is about to write into
                            src, dst = plan.cow
                            dispatch()
                            cache = self._copy(
                                cache, jnp.int32(src), jnp.int32(dst)
                            )
                        sp = r.sampling
                        n_keys = r.max_new_tokens + self.spec_k + 1
                        if sp is not None and sp.temperature > 0:
                            with span("serve.sync", what="keys", rid=r.rid):
                                keys = np.asarray(
                                    request_keys(sp.seed, n_keys)
                                )
                            temp = float(sp.temperature)
                            tk, tp = int(sp.top_k), float(sp.top_p)
                        else:
                            # greedy (or temperature=0 sampling, the same
                            # thing): keys never reach a draw
                            keys = np.zeros((n_keys, 2), np.uint32)
                            temp, tk, tp = 0.0, 0, 1.0
                        slot_temp[k], slot_topk[k], slot_topp[k] = temp, tk, tp
                        slot_state[k] = _SlotState(
                            request=r,
                            admit_time=now(),
                            plan=plan,
                            # swap boundary: a request takes the generation
                            # that is current AT ADMISSION and keeps it to
                            # retirement
                            programmed=self.programmed,
                            gen=self.generation,
                            prefill_pos=plan.resume_pos,
                            logits=[] if self.collect_logits else None,
                            keys=keys,
                            temp=temp,
                            top_k=tk,
                            top_p=tp,
                        )
                        active[k] = False
                        if spec:
                            # bind the draft lane to its static block range
                            # and reset its pos; the draft prefills the FULL
                            # prompt from 0 (its arena shares nothing with
                            # the target's prefix cache)
                            draft_bt = np.arange(
                                1 + k * self.blocks_per_slot,
                                1 + (k + 1) * self.blocks_per_slot,
                                dtype=np.int32,
                            )
                            dispatch()
                            draft_cache = self._admit(
                                draft_cache, jnp.int32(k),
                                jnp.asarray(draft_bt),
                            )

                # 2. one prefill chunk per still-prefilling lane — admission
                # work is spread so it never stalls the decode step below.
                # With speculation each lane ALSO advances its draft-engine
                # prefill by one chunk per iteration (own cache, full
                # prompt); the lane only starts decoding once both engines
                # hold the prompt, but the first token always comes from the
                # target's final chunk.
                chunks_run = 0
                draft_chunks = 0
                for k in range(K):
                    st = slot_state[k]
                    if st is None or active[k]:
                        continue
                    r = st.request
                    plen = len(r.tokens)
                    if st.prefill_pos < plen:
                        start = st.prefill_pos
                        # a cached prefix shrinks the remaining prompt — the
                        # unchunked bucket covers only what is left to run
                        clen = (
                            self.prefill_chunk
                            or self._bucket_for(plen - start)
                        )
                        nv = min(clen, plen - start)
                        toks = np.zeros((clen,), np.int32)
                        toks[:nv] = np.asarray(
                            r.tokens[start:start + nv], np.int32
                        )
                        with span("serve.chunk", rid=r.rid, start=start):
                            dispatch()
                            logits, cache = self._chunk(
                                self.params, cache, jnp.asarray(toks),
                                jnp.int32(k), jnp.int32(start), jnp.int32(nv),
                                jnp.bool_(start + nv >= plen), st.programmed,
                                t_arg,
                            )
                        st.prefill_pos = start + nv
                        st.prefill_chunks += 1
                        chunks_run += 1
                        self._blocks.register_progress(st.plan, st.prefill_pos)
                        if st.prefill_pos >= plen:  # final chunk → 1st token
                            with span("serve.sync", what="first_token",
                                      rid=r.rid):
                                if st.temp > 0:
                                    # emission index 0 draws with keys[0] —
                                    # the same single-row sampler the solo
                                    # oracle vmaps, so the draw is bitwise
                                    # theirs
                                    t_first = int(
                                        _jit_sample1()(
                                            jnp.asarray(st.keys[0]), logits[0],
                                            st.temp, st.top_k, st.top_p,
                                        )
                                    )
                                else:
                                    t_first = int(jnp.argmax(logits[0]))
                            st.first_token_time = now()
                            generated += 1
                            if self._emit(st, t_first, logits[0]):
                                results[r.rid] = self._result(st, now())
                                self._blocks.release(st.plan)
                                slot_state[k] = None
                                continue
                            next_tok[k] = t_first
                    if spec and st.draft_pos < plen:
                        start = st.draft_pos
                        clen = (
                            self.prefill_chunk
                            or self._bucket_for(plen - start)
                        )
                        nv = min(clen, plen - start)
                        toks = np.zeros((clen,), np.int32)
                        toks[:nv] = np.asarray(
                            r.tokens[start:start + nv], np.int32
                        )
                        # final=False: the draft never needs prefill logits
                        # (its first proposal samples AFTER consuming the
                        # target's first token), so the vocab projection is
                        # skipped while pos still advances to plen
                        with span("serve.chunk", rid=r.rid, start=start):
                            dispatch()
                            _, draft_cache = self._draft_chunk(
                                self.params, draft_cache, jnp.asarray(toks),
                                jnp.int32(k), jnp.int32(start), jnp.int32(nv),
                                jnp.bool_(False), self.draft_programmed, t_arg,
                            )
                        st.draft_pos = start + nv
                        draft_chunks += 1
                    if (
                        st.prefill_pos >= plen
                        and (not spec or st.draft_pos >= plen)
                        and st.out
                    ):
                        active[k] = True

                # 3. slot-parallel decode over the active lanes — one jitted
                # call per LIVE GENERATION (normally exactly one; during a
                # post-refresh drain, one for the old-gen lanes and one for
                # the new, with complementary active masks — inactive lanes
                # write only the trash block, so the calls compose)
                decoded = int(active.sum())
                if decoded and not spec:
                    gens = sorted(
                        {slot_state[k].gen for k in range(K) if active[k]}
                    )
                    toks_np = np.zeros((K,), np.int32)
                    logits_np = None
                    extra = ()
                    if run_sampled:
                        # emission index of the token this step draws =
                        # len(out); the key is a pure function of (seed,
                        # index), so the packing never enters the draw
                        keys_now = np.zeros((K, 2), np.uint32)
                        for k in range(K):
                            if active[k]:
                                st = slot_state[k]
                                keys_now[k] = st.keys[len(st.out)]
                        extra = (
                            jnp.asarray(keys_now), jnp.asarray(slot_temp),
                            jnp.asarray(slot_topk), jnp.asarray(slot_topp),
                        )
                    for g in gens:
                        mask = np.array(
                            [
                                bool(active[k]) and slot_state[k].gen == g
                                for k in range(K)
                            ]
                        )
                        prog = next(
                            slot_state[k].programmed
                            for k in range(K)
                            if mask[k]
                        )
                        with span("serve.decode"):
                            dispatch()
                            logits, toks, cache = decode(
                                self.params, cache, jnp.asarray(next_tok),
                                prog, jnp.asarray(mask), t_arg, *extra,
                            )
                        decode_steps += 1
                        occupancy += int(mask.sum())
                        with span("serve.sync", what="tokens"):
                            toks_np[mask] = np.asarray(toks)[mask]
                        if self.collect_logits:
                            with span("serve.sync", what="logits"):
                                l_np = np.asarray(logits)
                            if logits_np is None:
                                logits_np = np.zeros_like(l_np)
                            logits_np[mask] = l_np[mask]
                    with span("serve.emit"):
                        for k in range(K):
                            if not active[k]:
                                continue
                            st = slot_state[k]
                            st.decode_steps += 1
                            generated += 1
                            t = int(toks_np[k])
                            row = (
                                logits_np[k] if logits_np is not None
                                else None
                            )
                            if self._emit(st, t, row):
                                results[st.request.rid] = self._result(
                                    st, now()
                                )
                                self._blocks.release(st.plan)
                                slot_state[k] = None
                                active[k] = False
                            else:
                                next_tok[k] = t
                elif decoded:
                    # speculative round, one per live generation: draft
                    # proposes spec_k tokens on its own cache, the target
                    # verifies all C = spec_k+1 positions in ONE batched
                    # multi-token forward, and the host accepts the longest
                    # prefix of drafts that match what the target itself
                    # emits — so the emitted tokens are EXACTLY the
                    # non-speculative trajectory and only throughput moves
                    gens = sorted(
                        {slot_state[k].gen for k in range(K) if active[k]}
                    )
                    temps = jnp.asarray(slot_temp)
                    tks_a = jnp.asarray(slot_topk)
                    tps_a = jnp.asarray(slot_topp)
                    for g in gens:
                        mask = np.array(
                            [
                                bool(active[k]) and slot_state[k].gen == g
                                for k in range(K)
                            ]
                        )
                        prog = next(
                            slot_state[k].programmed
                            for k in range(K)
                            if mask[k]
                        )
                        # keys: draft step j and verify column c both draw
                        # emission index e0+j / e0+c of their slot — the
                        # SAME key on (numerically different) logits; a
                        # matching draw is exactly an accepted draft
                        keys_d = np.zeros((self.spec_k, K, 2), np.uint32)
                        keys_v = np.zeros((K, C, 2), np.uint32)
                        for k in range(K):
                            if not mask[k]:
                                continue
                            st = slot_state[k]
                            e0 = len(st.out)
                            keys_d[:, k] = st.keys[e0:e0 + self.spec_k]
                            keys_v[k] = st.keys[e0:e0 + C]
                        # the accepted frontier going INTO this round, for
                        # every slot (a previous round's draft scan left
                        # pos past it; verify never advances it): active =
                        # one past the last emitted token's KV, prefilling
                        # = the chunk frontier, free = parked at 0
                        pos_t = np.zeros((K,), np.int32)
                        pos_d = np.zeros((K,), np.int32)
                        for k in range(K):
                            st = slot_state[k]
                            if st is None:
                                continue
                            if active[k]:
                                pos_t[k] = (
                                    len(st.request.tokens) + len(st.out) - 1
                                )
                                pos_d[k] = pos_t[k]
                            else:
                                pos_t[k] = st.prefill_pos
                                pos_d[k] = st.draft_pos
                        with span("serve.decode"):
                            dispatch()
                            logits, toks_v, tokens_c, cache, draft_cache = (
                                self._spec_round(
                                    self.params, cache, draft_cache,
                                    jnp.asarray(next_tok), jnp.asarray(pos_t),
                                    jnp.asarray(pos_d), prog,
                                    self.draft_programmed, jnp.asarray(mask),
                                    t_arg, jnp.asarray(keys_d),
                                    jnp.asarray(keys_v), temps, tks_a, tps_a,
                                )
                            )
                        decode_steps += 1
                        occupancy += int(mask.sum())
                        with span("serve.sync", what="spec"):
                            toks_v_np = np.asarray(toks_v)
                            tokens_c = np.asarray(tokens_c)
                        l_np = None
                        if self.collect_logits:
                            with span("serve.sync", what="logits"):
                                l_np = np.asarray(logits)
                        with span("serve.emit"):
                            for k in range(K):
                                if not mask[k]:
                                    continue
                                st = slot_state[k]
                                st.decode_steps += 1
                                fin = False
                                for c in range(C):
                                    # the target's token at this position
                                    # is ALWAYS what gets emitted (greedy
                                    # argmax or the per-emission-key draw
                                    # on the target's logits): acceptance
                                    # only decides how many columns of
                                    # this round are usable
                                    tok_t = int(toks_v_np[k, c])
                                    row = (
                                        l_np[k, c] if l_np is not None
                                        else None
                                    )
                                    generated += 1
                                    fin = self._emit(st, tok_t, row)
                                    if fin or c == C - 1:
                                        break
                                    # the draft for the NEXT column is
                                    # examined: column c+1's logits are
                                    # valid iff its input token (the
                                    # draft) equals tok_t
                                    st.tokens_drafted += 1
                                    if int(tokens_c[k, c + 1]) != tok_t:
                                        break
                                    st.tokens_accepted += 1
                                if fin:
                                    results[st.request.rid] = self._result(
                                        st, now()
                                    )
                                    self._blocks.release(st.plan)
                                    slot_state[k] = None
                                    active[k] = False
                                else:
                                    next_tok[k] = int(st.out[-1])
                total_chunks += chunks_run
                # one record of every iteration — including idle deferral
                # re-checks below, so sum(t["deferred"]) ==
                # report.admission_deferrals — for the iteration's span and
                # the collected trace alike
                record = {
                    "chunks": chunks_run,
                    "decoded": decoded,
                    "admitted": admitted_now,
                    "deferred": queue.deferrals - def_before,
                }
                it.set_metadata(**record)
                if trace is not None:
                    trace.append(record)
                if decoded == 0 and chunks_run == 0 and draft_chunks == 0:
                    if len(results) == len(requests):
                        break
                    if queue.has_ready(now()):
                        continue  # pool-starved; a retirement frees blocks
                    nxt = queue.next_arrival()
                    if nxt is None:  # pragma: no cover - defensive
                        break
                    wait = nxt - now()
                    if wait > 0:
                        with span("serve.wait"):
                            time.sleep(min(wait, 0.05))

        wall = now()
        ordered = [results[r.rid] for r in requests]
        alloc = self._blocks
        return ServeReport(
            results=ordered,
            wall_s=wall,
            decode_steps=decode_steps,
            generated_tokens=generated,
            occupancy=(
                occupancy / (decode_steps * K) if decode_steps else 0.0
            ),
            kv_blocks=self.kv_blocks,
            kv_blocks_reused=alloc.blocks_reused,
            prefix_cache_hits=alloc.hits,
            prefix_cache_misses=alloc.misses,
            prefix_cache_evictions=alloc.evictions,
            prefix_cache_cow_copies=alloc.cow_copies,
            admission_deferrals=queue.deferrals,
            scheduler_skips=queue.skips,
            aged_admissions=queue.aged_admissions,
            prefill_chunks_run=total_chunks,
            reprogram_swaps=swaps,
            tokens_drafted=sum(res.tokens_drafted for res in ordered),
            tokens_accepted=sum(res.tokens_accepted for res in ordered),
            trace=trace,
        )
