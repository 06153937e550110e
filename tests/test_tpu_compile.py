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
the benchmark's readers look up in the device trace.
"""
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get
from repro.core import spec
from repro.kernels.paged_attention import (
    paged_chunk_attention,
    paged_decode_attention,
)
from repro.kernels.sliced_matmul import fused_sliced_matmul_pallas

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
        (n_blocks, BLOCK_SIZE, QWEN.n_kv_heads, QWEN.head_dim),
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
        _shape(one_chip, (nb,), jnp.int32),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _kernels(compiled) == {"_paged_chunk_call"}
