"""The benchmark's model: its configuration files, its weights made from
the seed, and the program's parameter tree built from those weights.

The weights belong to the benchmark, not to the program: the plain
reference (``bench/reference.py``) makes the same arrays again from the
same seed, and never reads anything the program built.
"""
from __future__ import annotations

import json
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parent

# The published configuration's keys that the benchmark reads, by the
# names of the model's own ``config.json``.
SIZE_KEYS = (
    "num_hidden_layers",
    "hidden_size",
    "num_attention_heads",
    "num_key_value_heads",
    "intermediate_size",
    "vocab_size",
)


def load(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json`` — a configuration, a traffic mix or a
    cell's limits, found by the name ``BENCHMARK.json`` gives it."""
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    return json.loads(path.read_text())


def sizes(conf: dict) -> dict:
    """The model's sizes, from a configuration file."""
    s = {k: int(conf[k]) for k in SIZE_KEYS}
    s["head_dim"] = s["hidden_size"] // s["num_attention_heads"]
    s["rope_theta"] = float(conf["rope_theta"])
    s["rms_norm_eps"] = float(conf["rms_norm_eps"])
    return s


def root_key(seed: int) -> jax.Array:
    """PRNG key of a non-negative seed of any size up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed must lie in [0, 2**64), got {seed}")
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32
    )


def weight_key(seed: int) -> jax.Array:
    return jax.random.fold_in(root_key(seed), 1)


def noise_key(seed: int) -> jax.Array:
    """The programming-noise key handed to ``program_params``."""
    return jax.random.fold_in(root_key(seed), 2)


@partial(jax.jit, static_argnums=(1,))
def _make_weights(key, shape_items):
    s = dict(shape_items)
    L, d, ff, V = (s["num_hidden_layers"], s["hidden_size"],
                   s["intermediate_size"], s["vocab_size"])
    hq = s["num_attention_heads"] * s["head_dim"]
    hkv = s["num_key_value_heads"] * s["head_dim"]
    shapes = {
        "embed": (V, d), "head": (d, V),
        "wq": (L, d, hq), "wk": (L, d, hkv), "wv": (L, d, hkv),
        "wo": (L, hq, d), "wi": (L, d, ff), "wg": (L, d, ff),
        "wd": (L, ff, d), "bq": (L, hq), "bk": (L, hkv), "bv": (L, hkv),
    }
    keys = jax.random.split(key, len(shapes) + 3)
    # initializer_range 0.02 of the published config, for every matrix
    # and bias; norm scales near their initial 1.
    w = {
        name: 0.02 * jax.random.normal(k, shape, jnp.float32)
        for (name, shape), k in zip(sorted(shapes.items()), keys)
    }
    for name, shape, k in (("n1", (L, d), keys[-3]), ("n2", (L, d), keys[-2]),
                           ("norm", (d,), keys[-1])):
        w[name] = 1.0 + 0.1 * jax.random.uniform(k, shape, jnp.float32,
                                                 -1.0, 1.0)
    return w


def make_weights(seed: int, size: dict) -> dict:
    """Every weight of the model in one jitted call on the device, in
    float32 (the type it is served in), from the seed alone."""
    return _make_weights(weight_key(seed), tuple(sorted(size.items())))


def to_program(w: dict) -> dict:
    """The program's parameter tree over the same arrays (no copies)."""
    return {
        "embed": {"w": w["embed"]},
        "final_norm": {"scale": w["norm"]},
        "lm_head": {"w": w["head"]},
        "blocks": {"seg0": {
            "norm1": {"scale": w["n1"]},
            "norm2": {"scale": w["n2"]},
            "attn": {
                "q_proj": {"w": w["wq"], "b": w["bq"]},
                "k_proj": {"w": w["wk"], "b": w["bk"]},
                "v_proj": {"w": w["wv"], "b": w["bv"]},
                "o_proj": {"w": w["wo"]},
            },
            "mlp": {"wi": {"w": w["wi"]}, "wg": {"w": w["wg"]},
                    "wo": {"w": w["wd"]}},
        }},
    }
