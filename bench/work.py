"""The work the traffic needs, counted from shapes, and the peaks it is
held against.

Operations count the rows the window processed (prompt tokens prefilled
plus tokens decoded), never the padded tiles a kernel runs.  Bytes count
what a step must read once: the programmed state of every layer it runs
and the live keys and values it attends over.  None of this looks at how
the program implements the work, so a kernel change cannot move it.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table[device_kind]


@dataclass(frozen=True)
class Work:
    ops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def least_seconds(self, peak: dict) -> float:
        """The least time the chip could take: compute or memory bound."""
        return max(self.ops / peak["flops_bf16"],
                   self.bytes / peak["hbm_bytes_per_s"])


@dataclass(frozen=True)
class Served:
    """One finished request: its prompt and how many tokens it emitted."""
    prompt_len: int
    generated: int


def _pad(n: int, b: int) -> int:
    return -(-n // b) * b


def layer_gemms(size: dict) -> list[tuple[int, int]]:
    """(K, N) of every projection of one decoder layer."""
    d, ff = size["hidden_size"], size["intermediate_size"]
    hq = size["num_attention_heads"] * size["head_dim"]
    hkv = size["num_key_value_heads"] * size["head_dim"]
    return [(d, hq), (d, hkv), (d, hkv), (hq, d), (d, ff), (d, ff), (ff, d)]


def head_gemm(size: dict) -> tuple[int, int]:
    return size["hidden_size"], size["vocab_size"]


def slice_pairs(device: dict) -> int:
    """Simulated products per multiply-add: every input-slice x
    weight-slice pair under the faithful engine, one when folded."""
    if device["mode"] == "faithful":
        return len(device["input_bits"]) * len(device["weight_bits"])
    return 1


def state_bytes(k: int, n: int, device: dict) -> int:
    """Programmed state of one K x N projection as the engine keeps it."""
    bk, bn = device["array"]
    kp, np_ = _pad(k, bk), _pad(n, bn)
    if device["mode"] == "faithful":
        slices = len(device["weight_bits"]) * kp * np_ * 4
        return slices + (kp // bk) * (np_ // bn) * 4
    return kp * np_ * (2 if device["store"] == "bfloat16" else 4)


def dpe_gemm(size: dict, device: dict, served: list[Served],
             layer_calls: int, head_calls: int) -> Work:
    """The simulated GEMMs: every projection of every layer for each
    processed row, and the vocabulary head for each emitted token.
    ``layer_calls`` is the number of model steps (prefill chunks plus
    decode steps) and ``head_calls`` those that ran the head."""
    n_layers = size["num_hidden_layers"]
    pairs = slice_pairs(device)
    layer_rows = sum(s.prompt_len + s.generated - 1 for s in served)
    head_rows = sum(s.generated for s in served)
    ops, act = 0.0, 0.0
    for k, n in layer_gemms(size):
        ops += 2.0 * pairs * k * n * n_layers * layer_rows
        act += 4.0 * (k + n) * n_layers * layer_rows
    k, n = head_gemm(size)
    ops += 2.0 * pairs * k * n * head_rows
    act += 4.0 * (k + n) * head_rows
    state = layer_calls * n_layers * sum(
        state_bytes(k, n, device) for k, n in layer_gemms(size)
    ) + head_calls * state_bytes(*head_gemm(size), device)
    return Work(ops, state + act)


def _attn_row_ops(size: dict, ctx: int) -> float:
    """QK and PV products of one query over ``ctx`` keys, all heads."""
    return 4.0 * size["num_attention_heads"] * size["head_dim"] * ctx


def _kv_bytes(size: dict, ctx: int, kv_itemsize: int) -> float:
    return 2.0 * ctx * size["num_key_value_heads"] * size["head_dim"] * kv_itemsize


def _qo_bytes(size: dict, rows: int) -> float:
    return 2.0 * rows * size["num_attention_heads"] * size["head_dim"] * 4


def paged_decode(size: dict, served: list[Served], kv_itemsize: int) -> Work:
    """The paged decode kernel: each decoded token's query attends over
    its whole live context, in every layer."""
    ops = byt = 0.0
    for s in served:
        for pos in range(s.prompt_len, s.prompt_len + s.generated - 1):
            ops += _attn_row_ops(size, pos + 1)
            byt += _kv_bytes(size, pos + 1, kv_itemsize) + _qo_bytes(size, 1)
    n = size["num_hidden_layers"]
    return Work(ops * n, byt * n)


def paged_chunk(size: dict, served: list[Served], chunk: int,
                kv_itemsize: int) -> Work:
    """The paged chunk kernel: each prompt token attends causally over
    the prefix, and each chunk reads the prefix it walks once."""
    ops = byt = 0.0
    for s in served:
        for start in range(0, s.prompt_len, chunk):
            nv = min(chunk, s.prompt_len - start)
            # sum of (pos + 1) for pos in [start, start + nv)
            ops += _attn_row_ops(size, 1) * (nv * start + nv * (nv + 1) / 2)
            byt += _kv_bytes(size, start + nv, kv_itemsize)
            byt += _qo_bytes(size, nv)
    n = size["num_hidden_layers"]
    return Work(ops * n, byt * n)


def share(work: Work, seconds: float, peak: dict) -> float | None:
    """Percent of the roofline: least time over the measured time.  No
    measured time means nothing to read."""
    if not seconds or seconds <= 0 or work.ops <= 0:
        return None
    return 100.0 * work.least_seconds(peak) / seconds


def chunk_calls(served: list[Served], chunk: int) -> int:
    return sum(math.ceil(s.prompt_len / chunk) for s in served)
