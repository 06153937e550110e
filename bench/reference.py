"""Plain reference of the served model on the simulated crossbar.

Written from the published architecture (Qwen2: RMSNorm, rotary
positions, grouped-query causal attention with QKV bias, SwiGLU) and the
simulator's stated device semantics (MemIntelli §3.2-3.3), in
straightforward ``jax.numpy`` at float32.  It imports nothing of the
program and takes nothing the program made: the weights come again from
the seed (``bench/model.py``), and the programming noise is drawn again
from the noise key by the key chain the configuration file states under
``assumed``.

The faithful device: per 128 x 128 block, symmetric int8 quantisation
of weights (per block) and inputs (per row and block), bit-slicing into
unsigned (1, 1, 2, 4) slices of which the first carries the sign, a
mean-preserving log-normal programming error on every weight slice's
conductance, one analog product per (input slice, weight slice) pair
and K-block, a 1024-level ADC ranged per row over the 128 bit-lines of
each array, and digital recombination.  The folded device sums the
noisy weight slices first, stores the effective weight in bfloat16, and
multiplies it with the dequantised int8 input.

``dot`` sets how the float32 contractions are computed: ``"f32"`` at
full precision (the reference), ``"bf16x3"`` as three bfloat16 passes
(what ``Precision.HIGH`` computes) or ``"bf16"`` as one.  ``store`` sets
the type the folded weight is kept in and ``slice_store`` that of the
faithful weight slices.  Those are the controls.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.model import make_weights, sizes

_EPS = 1e-30
# elements of one slice-pair partial-sum tile the reference holds at once
_TILE = 1 << 25


def _offsets(bits) -> list[int]:
    """Bit position of each slice's least significant bit, MSB first."""
    offs, acc = [], sum(bits)
    for b in bits:
        acc -= b
        offs.append(acc)
    return offs


def _significances(bits) -> np.ndarray:
    """Recombination weight of each slice; the first carries the sign."""
    sig = np.array([2.0 ** o for o in _offsets(bits)])
    sig[0] = -(2.0 ** (sum(bits) - 1))
    return sig


# Rounding is written with ``reduce_precision``: XLA may drop a float32
# -> bfloat16 -> float32 round trip of ``astype`` as excess precision.
_BITS = {"bfloat16": (8, 7), "float8_e4m3": (4, 3), "float32": None}


def _round_to(x, dtype: str):
    """``x`` rounded to the values ``dtype`` holds, kept in float32."""
    bits = _BITS[dtype]
    if bits is None:
        return x.astype(jnp.float32)
    return lax.reduce_precision(x.astype(jnp.float32), exponent_bits=bits[0],
                                mantissa_bits=bits[1])


def _dot(a, b, dims, mode):
    """A float32 contraction computed as ``mode`` says: exactly rounded
    products with float32 sums (``"f32"``), or over bfloat16 parts of the
    operands, in one pass (``"bf16"``) or three (``"bf16x3"``, what
    ``Precision.HIGH`` computes)."""
    exact = partial(lax.dot_general, dimension_numbers=dims,
                    precision=lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    if mode == "f32":
        return exact(a, b)
    ah, bh = _round_to(a, "bfloat16"), _round_to(b, "bfloat16")
    if mode == "bf16":
        return exact(ah, bh)
    if mode == "bf16x3":
        al, bl = _round_to(a - ah, "bfloat16"), _round_to(b - bh, "bfloat16")
        return exact(al, bh) + exact(ah, bl) + exact(ah, bh)
    raise ValueError(f"unknown dot mode {mode!r}")


def _pad(n: int, b: int) -> int:
    return -(-n // b) * b


def _pad2(w, bk, bn):
    k, n = w.shape
    return jnp.pad(w, ((0, (-k) % bk), (0, (-n) % bn)))


def _int_quant(x, scale, bits):
    total = sum(bits)
    q = jnp.round(x / scale)
    return jnp.clip(q, -(2 ** (total - 1)), 2 ** (total - 1) - 1).astype(
        jnp.int32)


def _slices(q, bits):
    """Unsigned MSB-first slices of two's-complement ints, as floats."""
    u = jnp.bitwise_and(q, (1 << sum(bits)) - 1)
    return [
        jnp.bitwise_and(jnp.right_shift(u, off), (1 << b) - 1).astype(
            jnp.float32)
        for b, off in zip(bits, _offsets(bits))
    ]


def _program(w, key, dev):
    """Quantise, slice and program one weight: the noisy slice values
    (Sw, nk, bk, nn, bn) and the block scales (nk, nn)."""
    bk, bn = dev["array"]
    bits = dev["weight_bits"]
    wp = _pad2(w.astype(jnp.float32), bk, bn)
    kp, np_ = wp.shape
    wb = wp.reshape(kp // bk, bk, np_ // bn, bn)
    absmax = jnp.max(jnp.abs(wb), axis=(1, 3))
    scale = jnp.maximum(absmax, _EPS) / (2.0 ** (sum(bits) - 1) - 1.0)
    q = _int_quant(wb, scale[:, None, :, None], bits)
    cv, hgs, lgs = dev["cv"], dev["hgs"], dev["lgs"]
    sigma = jnp.sqrt(jnp.log(jnp.float32(cv * cv + 1.0)))
    out = []
    for s, (b, v) in enumerate(zip(bits, _slices(q, bits))):
        if cv > 0:
            dg = (hgs - lgs) / (2.0 ** b - 1.0)
            g = lgs + v * dg
            mu = jnp.log(jnp.maximum(g, _EPS)) - 0.5 * sigma * sigma
            z = jax.random.normal(jax.random.fold_in(key, s), v.shape,
                                  jnp.float32)
            v = (jnp.exp(mu + sigma * z) - lgs) / dg
        out.append(v)
    return _round_to(jnp.stack(out), dev.get("slice_store", "float32")), scale


def _input_slices(x, dev):
    """(M, K) -> slices (Sx, M, nk, bk) and row-block scales (M, nk).
    The 8-bit DAC is exact on slices of at most 4 bits."""
    bk, _ = dev["array"]
    bits = dev["input_bits"]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, (-x.shape[1]) % bk)))
    m, kp = xp.shape
    xb = xp.reshape(m, kp // bk, bk)
    absmax = jnp.max(jnp.abs(xb), axis=2)
    sx = jnp.maximum(absmax, _EPS) / (2.0 ** (sum(bits) - 1) - 1.0)
    return jnp.stack(_slices(_int_quant(xb, sx[:, :, None], bits), bits)), sx


def _faithful(x, prog, dev, dot):
    """Simulated x @ w on programmed slices, one slice pair, K-block and
    ADC conversion at a time, in tiles of rows and bit-line columns."""
    ws, sw = prog  # (Sw, nk, bk, nn, bn), (nk, nn)
    bk, bn = dev["array"]
    sigx = jnp.asarray(_significances(dev["input_bits"]), jnp.float32)
    sigw = jnp.asarray(_significances(dev["weight_bits"]), jnp.float32)
    pair_sig = sigx[:, None] * sigw[None, :]
    levels = dev["radc"] - 1
    xs, sx = _input_slices(x, dev)  # (Sx, M, nk, bk), (M, nk)
    nsx, m, nk, _ = xs.shape
    nsw, _, _, nn, _ = ws.shape
    rows = min(_pad(m, 8), 512)
    mp = _pad(m, rows)
    xs = jnp.pad(xs, ((0, 0), (0, mp - m), (0, 0), (0, 0)))
    sx = jnp.pad(sx, ((0, mp - m), (0, 0)))
    cols = min(nn, max(1, _TILE // (nsx * nsw * rows * bn)))
    nnp = _pad(nn, cols)
    ws = jnp.pad(ws, ((0, 0), (0, 0), (0, 0), (0, nnp - nn), (0, 0)))
    sw = jnp.pad(sw, ((0, 0), (0, nnp - nn)))

    def tile(xs_r, sx_r, ws_c, sw_c):
        # xs_r (Sx, rows, nk, bk); ws_c (Sw, nk, bk, cols, bn)
        def kblock(acc, kb):
            xk = xs_r[:, :, kb]  # (Sx, rows, bk)
            wk = ws_c[:, kb]  # (Sw, bk, cols, bn)
            p = _dot(xk, wk, (((2,), (1,)), ((), ())), dot)
            # (Sx, rows, Sw, cols, bn); ADC range per row and array
            ymax = jnp.max(p, axis=4, keepdims=True)
            step = jnp.maximum(ymax, _EPS) / levels
            codes = jnp.round(p / step) * step
            y = jnp.sum(pair_sig[:, None, :, None, None] * codes, axis=(0, 2))
            y = y * sx_r[:, kb][:, None, None] * sw_c[kb][None, :, None]
            return acc + y, None

        acc0 = jnp.zeros((xs_r.shape[1], ws_c.shape[3], bn), jnp.float32)
        acc, _ = lax.scan(kblock, acc0, jnp.arange(nk))
        return acc

    def row_tile(r):
        xs_r = lax.dynamic_slice_in_dim(xs, r * rows, rows, axis=1)
        sx_r = lax.dynamic_slice_in_dim(sx, r * rows, rows, axis=0)

        def col_tile(c):
            ws_c = lax.dynamic_slice_in_dim(ws, c * cols, cols, axis=3)
            sw_c = lax.dynamic_slice_in_dim(sw, c * cols, cols, axis=1)
            return tile(xs_r, sx_r, ws_c, sw_c)

        out = lax.map(col_tile, jnp.arange(nnp // cols))  # (nc, rows, cols, bn)
        return jnp.moveaxis(out, 0, 1).reshape(rows, nnp * bn)

    out = lax.map(row_tile, jnp.arange(mp // rows))
    return out.reshape(mp, nnp * bn)[:m, :nn * bn]


def _fold(prog, dev):
    """The folded device's stored effective weight (Kp, Np)."""
    ws, sw = prog
    sig = _significances(dev["weight_bits"])
    acc = jnp.zeros(ws.shape[1:], jnp.float32)
    for s in range(ws.shape[0]):
        acc = acc + float(sig[s]) * ws[s]
    w = acc * sw[:, None, :, None]
    nk, bk, nn, bn = w.shape
    return _round_to(w.reshape(nk * bk, nn * bn), dev["store"])


def _folded(x, w_eff, dev, dot):
    bk, _ = dev["array"]
    xs, sx = _input_slices(x, dev)  # (Sx, M, nk, bk)
    sig = jnp.asarray(_significances(dev["input_bits"]), jnp.float32)
    xq = jnp.einsum("s,smkb->mkb", sig, xs)
    xd = _round_to((xq * sx[:, :, None]).reshape(x.shape[0], -1),
                   dev["input_store"])
    return _dot(xd, w_eff, (((1,), (0,)), ((), ())), dot)


def _linear(x, w, key, dev, dot):
    n = w.shape[1]
    prog = _program(w, key, dev)
    if dev["mode"] == "faithful":
        y = _faithful(x, prog, dev, dot)
    else:
        y = _folded(x, _fold(prog, dev), dev, dot)
    return y[:, :n]


def _layer_key(base, name: str):
    return jax.random.fold_in(base, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, dot):
    """Causal grouped-query attention; q (T, H, hd), k/v (T, KV, hd)."""
    t, h, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(t, kv, h // kv, hd)
    rows = min(t, 512)
    keys = jnp.arange(t)

    def block(i):
        qb = lax.dynamic_slice_in_dim(qg, i * rows, rows, axis=0)
        s = _dot(qb, k, (((3,), (2,)), ((1,), (1,))), dot)  # (KV, rows, G, T)
        s = s * hd ** -0.5
        qpos = i * rows + jnp.arange(rows)
        mask = keys[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, :, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = _dot(p, v, (((3,), (0,)), ((0,), (1,))), dot)  # (KV, rows, G, hd)
        return jnp.moveaxis(o, 0, 1).reshape(rows, h * hd)

    return lax.map(block, jnp.arange(t // rows)).reshape(t, h * hd)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _hidden(w, tokens, shape_items, dev_items, dot):
    """Final-norm hidden states (T, d) of one padded token sequence."""
    s, dev = dict(shape_items), _unfreeze(dev_items)
    key = w["noise"]
    seg = jax.random.fold_in(key, 0)
    x = w["embed"][tokens]
    pos = jnp.arange(tokens.shape[0])
    h_, kv_, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                   s["head_dim"])
    eps = s["rms_norm_eps"]

    def layer(x, inp):
        lw, i = inp
        lk = jax.random.fold_in(seg, i)

        def lin(name, xin, wname):
            return _linear(xin, lw[wname], _layer_key(lk, name), dev, dot)

        h = _rms(x, lw["n1"], eps)
        q = lin("L.attn.q", h, "wq") + lw["bq"]
        k = lin("L.attn.k", h, "wk") + lw["bk"]
        v = lin("L.attn.v", h, "wv") + lw["bv"]
        q = _rope(q.reshape(-1, h_, hd), pos, s["rope_theta"])
        k = _rope(k.reshape(-1, kv_, hd), pos, s["rope_theta"])
        o = _attention(q, k, v.reshape(-1, kv_, hd), dot)
        x = x + lin("L.attn.o", o, "wo")
        h = _rms(x, lw["n2"], eps)
        up = lin("L.attn.mlp.wi", h, "wi")
        gate = lin("L.attn.mlp.wg", h, "wg")
        x = x + lin("L.attn.mlp.wo", jax.nn.silu(gate) * up, "wd")
        return x, None

    names = ("wq", "wk", "wv", "wo", "wi", "wg", "wd", "bq", "bk", "bv",
             "n1", "n2")
    stack = {n: w[n] for n in names}
    x, _ = lax.scan(layer, x, (stack, jnp.arange(s["num_hidden_layers"])))
    return _rms(x, w["norm"], eps)


@partial(jax.jit, static_argnums=(3, 4))
def _logits(w, hidden, rows, dev_items, dot):
    dev = _unfreeze(dev_items)
    key = _layer_key(w["noise"], "lm_head")
    return _linear(hidden[rows], w["head"], key, dev, dot)


def _freeze(dev: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in dev.items()))


def _unfreeze(items) -> dict:
    return {k: list(v) if isinstance(v, tuple) else v for k, v in items}


class Reference:
    """The reference for one configuration and seed.  Build it only once
    the program's state is freed: it makes the weights again."""

    def __init__(self, conf: dict, seed: int, noise: jax.Array):
        self.size = sizes(conf)
        self.device = conf["device"]
        dev = self.device
        if dev["mode"] == "faithful" and dev["adc"] != "dynamic_row":
            raise NotImplementedError(f"ADC {dev['adc']!r}")
        if any((dev["rdac"] - 1) % (2 ** b - 1) for b in dev["input_bits"]):
            raise NotImplementedError("a DAC that is not exact on the slices")
        w = make_weights(seed, self.size)
        w["noise"] = noise
        self.w = w

    def _forward(self, tokens, rows, device, dot):
        sh = tuple(sorted(self.size.items()))
        h = _hidden(self.w, tokens, sh, _freeze(device), dot)
        return _logits(self.w, h, rows, _freeze(device), dot)

    def readings(self, prompt, served, pad_to: int, rows_to: int,
                 controls: dict | None = None) -> dict:
        """Per emitted token, how far the reference's logit of that token
        lies below the reference's best: under ``"program"`` for the
        program's served tokens, and under each name of ``controls``
        (``{"dot": ..., "store": ...}``) for what that control puts first
        at each position of the same prompt and tokens."""
        seq = np.concatenate([np.asarray(prompt), np.asarray(served[:-1])])
        n, g = len(seq), len(served)
        if n > pad_to or g > rows_to:
            raise ValueError(f"sequence {n}/{g} exceeds {pad_to}/{rows_to}")
        tokens = np.zeros(pad_to, np.int32)
        tokens[:n] = seq
        rows = np.zeros(rows_to, np.int32)
        rows[:g] = len(prompt) - 1 + np.arange(g)
        tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
        ref = self._forward(tokens, rows, self.device, "f32")
        best = jnp.max(ref, axis=-1)
        picks = {"program": jnp.asarray(
            np.pad(np.asarray(served, np.int32), (0, rows_to - g)))}
        for name, spec in (controls or {}).items():
            dev = dict(self.device)
            dev.update({k: v for k, v in spec.items() if k != "dot"})
            ctl = self._forward(tokens, rows, dev, spec.get("dot", "f32"))
            picks[name] = jnp.argmax(ctl, axis=-1)
        return {
            name: np.asarray(
                best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
            )[:g]
            for name, pick in picks.items()
        }
