"""Checkpointing (async/atomic/resume/elastic) + fault-tolerance units."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    latest_step,
    restore_checkpoint,
    save_checkpoint,
    wait_for_saves,
)
from repro.distributed.compression import GradCompression, _quant_dequant
from repro.distributed.ft import StepMonitor, plan_elastic_mesh, run_with_recovery


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (16, 8)), "b": jnp.zeros(8)},
        "opt": {"m": {"w": jnp.ones((16, 8)), "b": jnp.zeros(8)}},
        "step": jnp.int32(7),
    }


def test_save_restore_roundtrip(tmp_path):
    st = _state()
    save_checkpoint(tmp_path, 7, st, async_save=False)
    assert latest_step(tmp_path) == 7
    restored, step = restore_checkpoint(tmp_path, jax.eval_shape(lambda: st))
    assert step == 7
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(restored)):
        assert jnp.allclose(a, b)


def test_async_save_and_keep_last_k(tmp_path):
    st = _state()
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(tmp_path, s, st, async_save=True, keep=2)
    wait_for_saves()
    steps = sorted(
        int(p.name.split("_")[1]) for p in tmp_path.glob("step_*")
    )
    assert steps[-1] == 5 and len(steps) <= 2


def test_tree_mismatch_rejected(tmp_path):
    save_checkpoint(tmp_path, 1, _state(), async_save=False)
    bad = {"params": {"w": jnp.zeros((16, 8))}, "step": jnp.int32(0)}
    with pytest.raises(ValueError):
        restore_checkpoint(tmp_path, jax.eval_shape(lambda: bad))


def test_elastic_restore_new_sharding(tmp_path):
    """Reshard-on-restore: the same checkpoint loads under a different
    device layout (elastic scaling after losing/gaining hosts)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    st = _state()
    save_checkpoint(tmp_path, 3, st, async_save=False)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    shardings = jax.tree.map(
        lambda _: NamedSharding(mesh, P()), jax.eval_shape(lambda: st)
    )
    restored, _ = restore_checkpoint(
        tmp_path, jax.eval_shape(lambda: st), shardings=shardings
    )
    assert jnp.allclose(restored["params"]["w"], st["params"]["w"])


def test_step_monitor_flags_stragglers():
    m = StepMonitor(ema_decay=0.5, straggler_factor=1.5)
    import time

    for i in range(3):
        m.start()
        time.sleep(0.01)
        m.stop(i)
    m.start()
    time.sleep(0.08)
    stats = m.stop(99)
    assert stats["straggler"]
    assert m.slow_steps and m.slow_steps[-1][0] == 99


def test_plan_elastic_mesh():
    assert plan_elastic_mesh(256, 16) == (16, 16)
    assert plan_elastic_mesh(240, 16) == (15, 16)  # lost a host
    with pytest.raises(RuntimeError):
        plan_elastic_mesh(8, 16)


def test_run_with_recovery(tmp_path):
    calls = {"n": 0}
    saved = {"state": 0}

    def restore():
        return saved["state"]

    def save(_):
        pass

    def loop(state):
        calls["n"] += 1
        if calls["n"] < 3:
            saved["state"] = calls["n"]
            raise RuntimeError("node failure")
        return state + 100

    out = run_with_recovery(
        loop, save_emergency=save, restore_latest=restore, max_restarts=3
    )
    assert out == 102 and calls["n"] == 3


def test_grad_compression_error_feedback():
    gc = GradCompression(block=64)
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (128,)) * 1e-3}
    state = {"ef": gc.init(g)}
    total_raw = jnp.zeros_like(g["w"])
    total_comp = jnp.zeros_like(g["w"])
    for _ in range(20):
        comp, state = gc.apply(g, state)
        total_raw = total_raw + g["w"]
        total_comp = total_comp + comp["w"]
    # error feedback keeps the *accumulated* update unbiased
    rel = float(
        jnp.linalg.norm(total_comp - total_raw) / jnp.linalg.norm(total_raw)
    )
    assert rel < 0.05, rel


def test_quant_dequant_bounded():
    x = jax.random.normal(jax.random.PRNGKey(1), (1000,))
    d = _quant_dequant(x)
    assert float(jnp.max(jnp.abs(d - x))) <= float(jnp.max(jnp.abs(x))) / 127 + 1e-6


def test_compressed_psum_single_device():
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.distributed.compression import compressed_psum
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(2), (64,))
    f = jax.shard_map(
        lambda a: compressed_psum(a, "data"),
        mesh=mesh,
        in_specs=P(),
        out_specs=P(),
    )
    out = f(x)
    assert float(jnp.max(jnp.abs(out - x))) < float(jnp.max(jnp.abs(x))) / 100
