"""Compile a cell's programs for a described TPU v5e, without the chip,
and print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py <cell> [<cell> ...]

For each cell: the programming pass, the chunk step and the decode step
at the cell's slots and max_len, compiled for one chip of a described
``v5e:2x2`` with the Pallas kernels on, and their ``memory_analysis()``.
Never run by ``bench/run.py``.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(cells) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness import load_cell, program_setup
    from bench.model import _make_weights, sizes, to_program
    from repro.kernels import ops as kops
    from repro.models.model import init_paged_cache
    from repro.models.programmed import _program_params_impl
    from repro.serve.batching import _jit_chunk, _jit_decode

    jax.config.update("jax_enable_compilation_cache", False)
    kops.set_kernels_enabled(True)
    kops.set_interpret(False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    def report(label, compiled):
        m = compiled.memory_analysis()
        gb = lambda b: f"{b / 1e9:.3f} GB"
        print(f"{label}: arguments {gb(m.argument_size_in_bytes)}, output "
              f"{gb(m.output_size_in_bytes)}, temporaries "
              f"{gb(m.temp_size_in_bytes)}, aliased "
              f"{gb(m.alias_size_in_bytes)}", flush=True)

    for name in cells:
        cell = load_cell(name)
        s = sizes(cell.conf)
        cfg, policy = program_setup(cell.conf)
        sv = cell.mix["serving"]
        slots, max_len, block = sv["slots"], sv["max_len"], sv["block_size"]
        kv_blocks = slots * (-(-max_len // block)) + 1
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
        params = place(jax.eval_shape(
            lambda k: to_program(_make_weights(k, tuple(sorted(s.items())))),
            jax.random.PRNGKey(0)))
        t = jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)
        prog_c = _program_params_impl.lower(params, cfg, policy, key,
                                            t).compile()
        report(f"{name} programming pass", prog_c)
        prog = place(jax.eval_shape(
            lambda p, k, tp: _program_params_impl(p, cfg, policy, k, tp),
            params, key, t))
        cache = place(jax.eval_shape(lambda: init_paged_cache(
            cfg, slots, max_len, block, kv_blocks, jnp.float32)))
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                  sharding=chip)
        chunk = _jit_chunk(cfg, policy, jnp.float32, None)
        report(f"{name} chunk step (chunk {sv['prefill_chunk']})",
               chunk.lower(params, cache, i32(sv["prefill_chunk"]), i32(),
                           i32(), i32(),
                           jax.ShapeDtypeStruct((), jnp.bool_, sharding=chip),
                           prog, None).compile())
        decode = _jit_decode(cfg, policy, jnp.float32, None)
        report(f"{name} decode step ({slots} slots, max_len {max_len})",
               decode.lower(params, cache, i32(slots), prog,
                            jax.ShapeDtypeStruct((slots,), jnp.bool_,
                                                 sharding=chip),
                            None).compile())
        kv = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(cache))
        print(f"{name} paged KV arena {kv / 1e9:.3f} GB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
