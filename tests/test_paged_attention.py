"""Paged-attention kernel equivalence suite (repro/kernels/paged_attention.py).

Contract under test (DESIGN.md §3/§7):

* **Kernel == dense gather, bitwise.**  `paged_decode_attention` /
  `paged_chunk_attention` produce bit-identical outputs to the XLA
  oracle path — ``attention_decode`` / ``attention_dense`` over
  ``_paged_gather``'s materialised logical view — across block layouts:
  identity and permuted tables, trash-block tail entries, and
  pool-pressure layouts where freed physical blocks are re-used by other
  slots.  (Chunk kernel: bitwise on the valid query rows; pad rows see a
  zero tail instead of gathered junk and are discarded by callers.)
* **Layer index.**  The kernels read one layer of a stacked arena
  ``(L, n_blocks, bs, KV*hd)`` whose layers hold different values: at
  the first, a middle and the last layer they are bitwise the call on
  a one-layer arena holding just that layer.
* **Block-boundary writes.**  ``_paged_token_write`` at
  ``pos % block_size == 0`` lands the token in the freshly mapped block
  at offset 0 (regression: the first token of every new block), and
  inactive rows route to the trash block.
* **ServeLoop end-to-end.**  Batched == solo token equivalence holds
  with the kernel backend forced on (interpret mode) — the PR 4/5
  serving contract extends to the kernel path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import DPEConfig, spec
from repro.core.layers import MemPolicy
from repro.kernels import ops as kops
from repro.kernels.paged_attention import (
    paged_chunk_attention,
    paged_decode_attention,
)
from repro.models import init_params, program_params
from repro.models.attention import (
    TRASH_BLOCK,
    _paged_gather,
    _paged_token_write,
    attention_decode,
    attention_dense,
)
from repro.serve import Request, ServeConfig, ServeLoop, greedy_generate

BS, NB, N_BLOCKS = 4, 8, 24  # S = 32 logical positions per slot
KV, HD, H = 2, 16, 8
LAYERS = 3  # a stacked arena: first, middle and last layer
LAYER_IDS = [0, LAYERS // 2, LAYERS - 1]


@pytest.fixture(autouse=True)
def _force_interpret():
    prev = kops.set_interpret(True)
    yield
    kops.set_interpret(prev)


@pytest.fixture(scope="module", autouse=True)
def _release_compile_cache():
    # interpret-mode kernel tests compile many distinct XLA programs;
    # drop them at module exit so later test files don't inherit the
    # accumulated executable memory (full-suite in-process runs)
    yield
    jax.clear_caches()


def _pools(seed=0, dtype=jnp.bfloat16):
    """A stacked K and V arena; every layer holds different values."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    mk = lambda k: jax.random.normal(
        k, (LAYERS, N_BLOCKS, BS, KV * HD), jnp.float32
    ).astype(dtype)
    return mk(k1), mk(k2)


def _one_layer(pool, layer):
    """The arena of that layer alone — what a single-layer call reads."""
    return pool[layer:layer + 1]


# block layouts: (block_tables, pos) pairs covering identity, permuted,
# trash-padded tails, and cross-slot physical reuse (pool pressure)
def _layouts():
    ident = jnp.arange(1, 1 + NB, dtype=jnp.int32)[None].repeat(3, 0)
    perm = jnp.array(
        [
            [5, 17, 2, 9, 0, 0, 0, 0],
            [11, 3, 22, 7, 15, 0, 0, 0],
            [20, 1, 4, 0, 0, 0, 0, 0],
        ],
        jnp.int32,
    )
    # slot 0 freed blocks {5, 9}; slots 1/2 now map them — stale slot-0
    # table still points there, but its pos fences it to its live prefix
    reuse = jnp.array(
        [
            [13, 5, 9, 0, 0, 0, 0, 0],
            [5, 2, 21, 9, 6, 0, 0, 0],
            [9, 5, 13, 0, 0, 0, 0, 0],
        ],
        jnp.int32,
    )
    return [
        ("identity", ident, jnp.array([31, 16, 7], jnp.int32)),
        ("permuted", perm, jnp.array([13, 18, 2], jnp.int32)),
        ("reuse", reuse, jnp.array([3, 17, 11], jnp.int32)),
        # block-boundary positions: pos % BS == 0 (first token of a
        # freshly mapped block) and the last position of a block
        ("boundary", perm, jnp.array([8, 4, 3], jnp.int32)),
    ]


@pytest.mark.parametrize("layer", LAYER_IDS)
@pytest.mark.parametrize("name,bt,pos", _layouts(), ids=[l[0] for l in _layouts()])
@pytest.mark.parametrize("window", [0, 6])
def test_decode_kernel_bitwise(name, bt, pos, window, layer):
    pool_k, pool_v = _pools()
    q = jax.random.normal(jax.random.PRNGKey(7), (bt.shape[0], H, HD), jnp.float32)
    ref = attention_decode(
        q, _paged_gather(pool_k, layer, bt, HD),
        _paged_gather(pool_v, layer, bt, HD), pos, window=window,
    )
    out = paged_decode_attention(
        q, pool_k, pool_v, jnp.int32(layer), bt, pos, window=window,
        interpret=True,
    )
    one = paged_decode_attention(
        q, _one_layer(pool_k, layer), _one_layer(pool_v, layer),
        jnp.int32(0), bt, pos, window=window, interpret=True,
    )
    assert out.dtype == ref.dtype
    assert bool(jnp.array_equal(
        out.astype(jnp.float32), ref.astype(jnp.float32)
    )), f"{name} window={window} layer={layer}"
    assert bool(jnp.array_equal(
        out.astype(jnp.float32), one.astype(jnp.float32)
    )), f"{name} window={window} layer={layer}"


@pytest.mark.parametrize("layer", LAYER_IDS)
@pytest.mark.parametrize("name,bt,pos", _layouts(), ids=[l[0] for l in _layouts()])
def test_decode_kernel_bitwise_f32_pool(name, bt, pos, layer):
    pool_k, pool_v = _pools(dtype=jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(7), (bt.shape[0], H, HD), jnp.float32)
    ref = attention_decode(
        q, _paged_gather(pool_k, layer, bt, HD),
        _paged_gather(pool_v, layer, bt, HD), pos,
    )
    out = paged_decode_attention(
        q, pool_k, pool_v, jnp.int32(layer), bt, pos, interpret=True
    )
    one = paged_decode_attention(
        q, _one_layer(pool_k, layer), _one_layer(pool_v, layer),
        jnp.int32(0), bt, pos, interpret=True,
    )
    assert bool(jnp.array_equal(out, ref)), f"{name} layer={layer}"
    assert bool(jnp.array_equal(out, one)), f"{name} layer={layer}"


@pytest.mark.parametrize("layer", LAYER_IDS)
@pytest.mark.parametrize(
    "start,n_valid,window",
    [(0, 6, 0), (8, 6, 0), (8, 3, 0), (13, 6, 0), (26, 6, 0), (8, 6, 5)],
)
def test_chunk_kernel_bitwise_valid_rows(start, n_valid, window, layer):
    """Chunk kernel == dense path on every VALID query row.  Pad rows
    (>= n_valid) attend over a zero tail instead of gathered junk — the
    caller discards them — but must stay finite."""
    pool_k, pool_v = _pools(seed=3)
    bt_row = jnp.array([5, 17, 2, 9, 12, 21, 7, 3], jnp.int32)
    C = 6
    q = jax.random.normal(jax.random.PRNGKey(11), (1, C, H, HD), jnp.float32)
    ref = attention_dense(
        q,
        _paged_gather(pool_k, layer, bt_row[None], HD),
        _paged_gather(pool_v, layer, bt_row[None], HD),
        q_off=start,
        window=window,
    )
    out = paged_chunk_attention(
        q, pool_k, pool_v, jnp.int32(layer), bt_row, jnp.int32(start),
        jnp.int32(n_valid), window=window, interpret=True,
    )
    one = paged_chunk_attention(
        q, _one_layer(pool_k, layer), _one_layer(pool_v, layer),
        jnp.int32(0), bt_row, jnp.int32(start), jnp.int32(n_valid),
        window=window, interpret=True,
    )
    assert out.dtype == ref.dtype
    r = ref.astype(jnp.float32)[:, :n_valid]
    o = out.astype(jnp.float32)[:, :n_valid]
    assert bool(jnp.array_equal(o, r)), f"start={start} n_valid={n_valid}"
    assert bool(jnp.array_equal(out, one)), f"layer={layer}"
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


def test_paged_token_write_block_boundary():
    """pos % block_size == 0 writes into offset 0 of the freshly mapped
    block of the written layer — and nothing else in the arena moves."""
    layer = 1
    pool = jnp.zeros((LAYERS, 6, BS, KV * HD), jnp.float32)
    bt = jnp.array([[2, 3], [4, 5]], jnp.int32)
    val = jnp.ones((2, KV, HD), jnp.float32) * jnp.array(
        [[[1.0]], [[2.0]]]
    )
    pos = jnp.array([BS, 0], jnp.int32)  # slot 0: block 3 offset 0;
    active = jnp.array([True, True])     # slot 1: block 4 offset 0
    new = _paged_token_write(pool, layer, bt, pos, val, active)
    g = _paged_gather(new, layer, bt, HD)
    assert bool(jnp.array_equal(g[0, BS], val[0]))
    assert bool(jnp.array_equal(g[1, 0], val[1]))
    # exactly two rows of the written layer were touched
    changed = jnp.any(new != pool, axis=(2, 3))
    assert [(int(l), int(b)) for l, b in zip(*jnp.where(changed))] == [
        (layer, 3), (layer, 4)]
    # and the decode kernel sees the fresh block bitwise
    q = jax.random.normal(jax.random.PRNGKey(0), (2, H, HD), jnp.float32)
    ref = attention_decode(q, g, g, pos)
    out = paged_decode_attention(q, new, new, jnp.int32(layer), bt, pos,
                                 interpret=True)
    assert bool(jnp.array_equal(out, ref))


def test_paged_token_write_inactive_routes_to_trash():
    pool = jnp.zeros((LAYERS, 6, BS, KV * HD), jnp.float32)
    bt = jnp.array([[2, 3]], jnp.int32)
    val = jnp.ones((1, KV, HD), jnp.float32)
    new = _paged_token_write(pool, 2, bt, jnp.array([BS], jnp.int32), val,
                             jnp.array([False]))
    # the mapped block is untouched; the write landed in the trash block
    # of the written layer, and no other layer moved
    assert bool(jnp.all(new[2, 3] == 0))
    assert bool(jnp.array_equal(new[2, TRASH_BLOCK, 0], val[0].reshape(-1)))
    assert bool(jnp.all(new[:2] == 0))


# ---------------------------------------------------------------------------
# ServeLoop end-to-end with kernels forced (interpret)
# ---------------------------------------------------------------------------

INT8 = spec("int8")
POLICIES = {
    "fast": MemPolicy(
        default=DPEConfig(input_spec=INT8, weight_spec=INT8, mode="fast")
    ),
    "faithful": MemPolicy(
        default=DPEConfig(
            input_spec=INT8, weight_spec=INT8, array_size=(32, 32),
            mode="faithful", adc_mode="dynamic_row",
        )
    ),
}
MAX_LEN = 32
WORKLOAD = [(4, 5), (7, 3), (12, 2)]


def _serve_case(mode):
    cfg = get_smoke("qwen2-0.5b").replace(vocab=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    policy = POLICIES[mode]
    prog = program_params(params, cfg, policy, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab, size=l).astype(np.int32)
        for l, _ in WORKLOAD
    ]
    reqs = [
        Request(rid=i, tokens=p, max_new_tokens=m)
        for i, (p, (_, m)) in enumerate(zip(prompts, WORKLOAD))
    ]
    assert kops.resolve_attention_backend() == "pallas"
    loop = ServeLoop(
        params, cfg, ServeConfig(
            policy=policy, slots=2, max_len=MAX_LEN,
            compute_dtype=jnp.float32,
        ), programmed=prog,
    )
    report = loop.run(reqs)
    for res, p, (_, m) in zip(report.results, prompts, WORKLOAD):
        ref = greedy_generate(
            params, cfg, jnp.asarray(p)[None], m - 1, policy=policy,
            compute_dtype=jnp.float32, programmed=prog, max_len=MAX_LEN,
        )
        assert res.tokens == list(np.asarray(ref[0])), (
            f"request {res.rid} (len {len(p)}, max_new {m})"
        )


def test_serveloop_batched_equals_solo_kernel_backend():
    """Batched == solo with the Pallas paged-attention kernels live in
    the serve loop (fast engine: attention kernels only)."""
    _serve_case("fast")


@pytest.mark.slow
def test_serveloop_batched_equals_solo_kernel_backend_faithful():
    """Same, faithful dynamic_row engine: the fused DPE GEMM kernel AND
    the paged attention kernels run in every chunk/decode step."""
    _serve_case("faithful")
