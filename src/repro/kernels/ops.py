"""Jit'd dispatch and the ONE kernel selection path.

Every Pallas entry point (staged / fused sliced matmul, paged
attention) and every backend resolver (``core.dpe.resolve_backend``,
``models.attention``'s paged-attention switch) consults this module, so
CPU CI (interpret mode) and TPU runs share a single selection mechanism
instead of each entry point re-deriving its own ``jax.default_backend()``
check:

* :func:`set_interpret` / env ``REPRO_KERNEL_INTERPRET`` — force the
  kernels to run (``True`` = interpret mode, the CI configuration;
  ``False`` = compiled, TPU only; ``None`` = auto: interpret iff not on
  TPU).  Forcing interpret mode on a TPU is an error, not a silent
  slowdown of the serve path.
* :func:`kernels_enabled` — should ``auto`` backends pick the Pallas
  kernels at all?  True on real TPU hardware, and under a forced
  ``set_interpret(True)`` (differential tests / kernel CI legs, where
  exercising the kernel path *is* the point).
* :func:`set_paged_attention_backend` — ``auto`` / ``xla`` / ``pallas``
  for the paged serving attention (``models/attention.py``).

Wrappers here pad M (and K for the fused path) to the kernel tiles and
slice the padding back off.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core.slicing import SliceSpec

from .ref import sliced_matmul_ref
from .sliced_matmul import fused_sliced_matmul_pallas, sliced_matmul_pallas

__all__ = [
    "sliced_matmul",
    "fused_sliced_matmul",
    "sliced_matmul_ref",
    "set_interpret",
    "kernel_interpret",
    "kernels_enabled",
    "set_kernels_enabled",
    "set_paged_attention_backend",
    "resolve_attention_backend",
]

_INTERPRET: bool | None = None
_ENABLED: bool | None = None
_ATTN_BACKEND: str = "auto"


def set_kernels_enabled(value: bool | None) -> bool | None:
    """Force (or reset, with ``None``) the :func:`kernels_enabled`
    answer — ``False`` pins every ``auto`` backend to the XLA oracle
    paths even on TPU (``launch/serve.py --kernels off``).  Returns the
    previous override."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = value
    return prev


def set_interpret(value: bool | None) -> bool | None:
    """Force (or reset, with ``None``) kernel interpret mode globally.

    Returns the previous override so tests can restore it.  Callers that
    flip this between traces must also re-acquire any jitted functions
    keyed on :func:`kernels_enabled` (serve/batching.py keys its step
    cache on it).
    """
    global _INTERPRET
    prev = _INTERPRET
    _INTERPRET = value
    return prev


def _interpret_override() -> bool | None:
    if _INTERPRET is not None:
        return _INTERPRET
    env = os.environ.get("REPRO_KERNEL_INTERPRET", "").lower()
    if env in ("1", "true", "yes"):
        return True
    if env in ("0", "false", "no"):
        return False
    return None


def kernel_interpret(override: bool | None = None) -> bool:
    """Resolve the interpret flag for one kernel call.

    Per-call ``override`` beats the global/env override beats auto
    (interpret iff the default backend is not a TPU).  A global/env
    override that forces interpret mode on a TPU raises."""
    if override is not None:
        return override
    on_tpu = jax.default_backend() == "tpu"
    forced = _interpret_override()
    if forced is None:
        return not on_tpu
    if forced and on_tpu:
        raise RuntimeError(
            "kernel interpret mode is forced (set_interpret(True) or "
            "REPRO_KERNEL_INTERPRET) on a TPU backend: the serving kernels "
            "would run in the Pallas interpreter instead of Mosaic"
        )
    return forced


def kernels_enabled() -> bool:
    """Should ``auto`` backend selection route to the Pallas kernels?

    True on real TPU hardware, and whenever interpret mode is explicitly
    forced on (the CPU-CI kernel legs opt in via ``set_interpret(True)``
    or ``REPRO_KERNEL_INTERPRET=1``) — everywhere else the interpret-mode
    kernel would be orders of magnitude slower than the XLA engine.
    :func:`set_kernels_enabled` overrides both."""
    if _ENABLED is not None:
        return _ENABLED
    if _interpret_override() is True:
        return True
    return jax.default_backend() == "tpu"


def set_paged_attention_backend(mode: str) -> str:
    """Select the paged serving-attention implementation: ``auto``
    (pallas iff :func:`kernels_enabled`), ``xla`` (dense gather — the
    bitwise oracle), ``pallas``.  Returns the previous mode."""
    global _ATTN_BACKEND
    if mode not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown paged-attention backend {mode!r}")
    prev = _ATTN_BACKEND
    _ATTN_BACKEND = mode
    return prev


def resolve_attention_backend() -> str:
    if _ATTN_BACKEND != "auto":
        return _ATTN_BACKEND
    return "pallas" if kernels_enabled() else "xla"


def sliced_matmul(
    xs: jax.Array,
    sx: jax.Array,
    ws: jax.Array,
    sw: jax.Array,
    *,
    input_spec: SliceSpec,
    weight_spec: SliceSpec,
    array_size: tuple[int, int],
    radc: int,
    adc_mode: str,
    bm: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Staged faithful DPE matmul via the Pallas kernel (M auto-padded):
    operands are pre-sliced on the host (``core.dpe.prepare_input``)."""
    interpret = kernel_interpret(interpret)
    sxn, m, kp = xs.shape
    pad = (-m) % bm
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        sx = jnp.pad(sx, ((0, pad), (0, 0)))
    y = sliced_matmul_pallas(
        xs,
        sx,
        ws,
        sw,
        input_spec=input_spec,
        weight_spec=weight_spec,
        array_size=array_size,
        radc=radc,
        adc_mode=adc_mode,
        bm=bm,
        interpret=interpret,
    )
    return y[:m] if pad else y


def fused_sliced_matmul(
    x: jax.Array,  # (M, K) raw float input
    ws: jax.Array,  # (Sw, Kp, Np)
    sw: jax.Array,  # (nk, nn)
    *,
    input_spec: SliceSpec,
    weight_spec: SliceSpec,
    array_size: tuple[int, int],
    rdac: int,
    radc: int,
    adc_mode: str,
    bm: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused faithful DPE matmul (M/K auto-padded): prepare_input runs
    IN the kernel — callers hand the raw activations straight in, no
    (Sx, M, Kp) slice stack ever touches HBM (the serve hot path)."""
    interpret = kernel_interpret(interpret)
    bk, _ = array_size
    m, k = x.shape
    kp = ws.shape[1]
    padm = (-m) % bm
    padk = kp - k
    if padk < 0 or padk >= bk:
        raise ValueError(f"K={k} inconsistent with prepared Kp={kp}")
    if padm or padk:
        x = jnp.pad(x, ((0, padm), (0, padk)))
    y = fused_sliced_matmul_pallas(
        x,
        ws,
        sw,
        input_spec=input_spec,
        weight_spec=weight_spec,
        array_size=array_size,
        rdac=rdac,
        radc=radc,
        adc_mode=adc_mode,
        bm=bm,
        interpret=interpret,
    )
    return y[:m] if padm else y
