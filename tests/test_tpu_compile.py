"""Compile the serving kernels for a TPU v5e that is described, not
attached, at qwen2-0.5b's published widths.

Interpret mode cannot show what Mosaic refuses (block shapes off the
(8, 128) tiling, VMEM overflow, unsupported layouts or accumulators), so
these tests lower and compile each kernel of the serve path for a
described ``v5e:2x2`` topology.  Nothing runs; a compile that passes is
not a chip run.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU compiler library at a time, and a test
worker that cannot describe it skips these tests from the fixture.

Each compile also pins the name the kernel's operation carries, which
the benchmark's readers look up in the device trace.  The serve loop's
chunk and decode steps are compiled whole at a small paged config, to
hold that no step copies, slices or writes back the KV arena.
"""
import functools
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get, get_smoke
from repro.core import DPEConfig, spec
from repro.core.layers import MemPolicy
from repro.kernels import ops as kops
from repro.kernels.paged_attention import (
    paged_chunk_attention,
    paged_decode_attention,
)
from repro.kernels.sliced_matmul import fused_sliced_matmul_pallas
from repro.models import init_params, program_params
from repro.models.model import init_paged_cache
from repro.serve.batching import _jit_chunk, _jit_decode

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.trace import op_name  # noqa: E402

QWEN = get("qwen2-0.5b")
INT8 = spec("int8")
TILE = 128  # the simulated crossbar tile (launch.dryrun.make_policy)
BLOCK_SIZE, MAX_LEN, SLOTS, CHUNK = 16, 2048, 8, 128


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler library in this process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _pad(n):
    return -(-n // TILE) * TILE


def _kernels(compiled) -> set:
    """The names of the compiled program's Mosaic kernels, as the device
    trace's reduction (``bench.trace.op_name``) gives them (the trace's
    event carries the instruction without the text's ``ROOT`` mark)."""
    return {
        op_name(line.strip().removeprefix("ROOT "))
        for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    }


@pytest.mark.parametrize(
    "k,n",
    [(QWEN.d_model, QWEN.d_ff), (QWEN.d_ff, QWEN.d_model)],
    ids=["d_model-d_ff", "d_ff-d_model"],
)
def test_fused_gemm_compiles(one_chip, k, n):
    kp, np_ = _pad(k), _pad(n)
    fn = functools.partial(
        fused_sliced_matmul_pallas, input_spec=INT8, weight_spec=INT8,
        array_size=(TILE, TILE), rdac=256, radc=1024,
        adc_mode="dynamic_row",
    )
    compiled = jax.jit(fn).lower(
        _shape(one_chip, (TILE, kp), jnp.float32),
        _shape(one_chip, (INT8.n_slices, kp, np_), jnp.float32),
        _shape(one_chip, (kp // TILE, np_ // TILE), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _kernels(compiled) == {"fused_sliced_matmul_pallas"}


def _pool(one_chip, dtype):
    n_blocks = SLOTS * (MAX_LEN // BLOCK_SIZE) + 1
    return _shape(
        one_chip,
        (QWEN.n_layers, n_blocks, BLOCK_SIZE,
         QWEN.n_kv_heads * QWEN.head_dim),
        dtype,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_decode_compiles(one_chip, dtype):
    nb = MAX_LEN // BLOCK_SIZE
    compiled = paged_decode_attention.lower(
        _shape(one_chip, (SLOTS, QWEN.n_heads, QWEN.head_dim), dtype),
        _pool(one_chip, dtype),
        _pool(one_chip, dtype),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (SLOTS, nb), jnp.int32),
        _shape(one_chip, (SLOTS,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _kernels(compiled) == {"paged_decode_attention"}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_chunk_compiles(one_chip, dtype):
    nb = MAX_LEN // BLOCK_SIZE
    compiled = jax.jit(paged_chunk_attention).lower(
        _shape(one_chip, (1, CHUNK, QWEN.n_heads, QWEN.head_dim), dtype),
        _pool(one_chip, dtype),
        _pool(one_chip, dtype),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (nb,), jnp.int32),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _kernels(compiled) == {"_paged_chunk_call"}


# A small paged config whose heads fill one lane row, as qwen2-0.5b's
# (2 x 64): one layer pool (8.4 MB) outweighs everything else a step
# holds, so a step that cuts a layer's pool out of the arena, or writes
# it back, shows in its temporaries.
STEP_CFG = get_smoke("qwen2-0.5b").replace(n_heads=2, n_kv_heads=2,
                                           head_dim=64)
STEP_SLOTS, STEP_MAX_LEN, STEP_BLOCK = 8, 2048, 16
ARENA_OPS = ("copy", "copy-start", "copy-done", "dynamic-slice",
             "dynamic-update-slice")
_INSTR = re.compile(r"= (\w+)\[([\d,]*)\]\S*\s+([\w-]+)\(")
_ITEMSIZE = {"bf16": 2, "s8": 1, "u8": 1, "pred": 1}  # else 4 bytes


def _arena_moves(text: str, min_bytes: int) -> list:
    """Every copy, slice or update-slice of the compiled program (fused
    computations included) whose result holds at least ``min_bytes``."""
    out = []
    for line in text.splitlines():
        m = _INSTR.search(line)
        if m is None or m.group(3) not in ARENA_OPS:
            continue
        n = int(np.prod([int(d) for d in m.group(2).split(",") if d]))
        if n * _ITEMSIZE.get(m.group(1), 4) >= min_bytes:
            out.append(line.strip()[:160])
    return out


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_paged_step_updates_arena_in_place(one_chip, step):
    """The chunk and decode steps update the paged KV arena in place:
    the layer scan carries the stacked pools and the paged kernels read
    them by layer index, so no whole-arena or layer-pool sized copy,
    slice or update-slice is left, the step's temporaries stay below one
    layer pool, and the donated arena aliases the output."""
    cfg = STEP_CFG
    policy = MemPolicy(default=DPEConfig(
        input_spec=INT8, weight_spec=INT8, mode="fast"))
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    key = jax.random.PRNGKey(0)
    params = place(jax.eval_shape(lambda: init_params(cfg, key)))
    prog = place(jax.eval_shape(
        lambda p: program_params(p, cfg, policy, key), params))
    n_blocks = STEP_SLOTS * (STEP_MAX_LEN // STEP_BLOCK) + 1
    cache = place(jax.eval_shape(lambda: init_paged_cache(
        cfg, STEP_SLOTS, STEP_MAX_LEN, STEP_BLOCK, n_blocks, jnp.float32)))
    arena = sum(a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(cache["blocks"]))
    pool = cache["blocks"]["seg0"]["k"]
    layer_pool = pool.size // pool.shape[0] * pool.dtype.itemsize
    i32 = lambda *shape: _shape(one_chip, shape, jnp.int32)
    prev = (kops.set_kernels_enabled(True), kops.set_interpret(False))
    try:
        if step == "decode":
            compiled = _jit_decode(cfg, policy, jnp.float32, None).lower(
                params, cache, i32(STEP_SLOTS), prog,
                _shape(one_chip, (STEP_SLOTS,), jnp.bool_), None,
            ).compile()
        else:
            compiled = _jit_chunk(cfg, policy, jnp.float32, None).lower(
                params, cache, i32(CHUNK), i32(), i32(), i32(),
                _shape(one_chip, (), jnp.bool_), prog, None,
            ).compile()
    finally:
        kops.set_kernels_enabled(prev[0])
        kops.set_interpret(prev[1])
    text = compiled.as_text()
    kernel = {"decode": "paged_decode_attention",
              "chunk": "_paged_chunk_call"}[step]
    assert kernel in _kernels(compiled)
    assert _arena_moves(text, layer_pool) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_pool, (
        mem.temp_size_in_bytes, layer_pool)
    assert mem.alias_size_in_bytes >= arena, (mem.alias_size_in_bytes, arena)
