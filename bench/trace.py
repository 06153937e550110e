"""Reduction of a profiler trace to device busy time, per-operation and
per-program device time, and the idle gaps of the measured window.

The window is the benchmark's own span around ``ServeLoop.run`` on the
host; device intervals are clipped to it.  Busy time is the union of the
intervals in which an operation ran on a chip, averaged over the chips
the cell uses.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    window_s: float
    busy_s: float
    ops: dict = field(default_factory=dict)  # op name -> [calls, seconds]
    modules: dict = field(default_factory=dict)  # name -> [durations s]
    gaps: list = field(default_factory=list)  # [(seconds, label)]

    def op_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for name, (_, s) in self.ops.items() if rx.search(name))

    def module_durations(self, pattern: str) -> list[float]:
        rx = re.compile(pattern)
        return [d for name, ds in self.modules.items() if rx.search(name)
                for d in ds]

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:10]
        gaps = sorted(self.gaps, key=lambda g: -g[0])[:10]
        return {
            "device_ops": [[name, s] for name, (_, s) in top],
            "idle_gaps": [[label, s] for s, label in gaps],
        }


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _module_at(mods, t):
    """Name of the last program that started at or before ``t``."""
    i = bisect.bisect_right(mods, (t, "\uffff"))
    return mods[i - 1][1] if i else "start of window"


def _strip(name: str) -> str:
    """A program's name without its fingerprint: ``jit_step(123)``."""
    return re.sub(r"\(\d+\)$", "", name)


_HLO = re.compile(r"%?([^\s=]+?)(?:\.\d+)*\s*=")


def op_name(name: str) -> str:
    """An operation's name without its number and signature: the event
    ``%fused_sliced_matmul_pallas.1 = f32[128,4864] custom-call(...)`` is
    ``fused_sliced_matmul_pallas``."""
    m = _HLO.match(name)
    return m.group(1) if m else name


def _leaves(events):
    """Events that hold no other event of their line (a ``while`` over the
    layers holds the operations of each layer)."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    leaf = [True] * len(evs)
    stack = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].start_ns + evs[stack[-1]].duration_ns \
                <= e.start_ns:
            stack.pop()
        if stack:
            leaf[stack[-1]] = False
        stack.append(i)
    return [e for e, is_leaf in zip(evs, leaf) if is_leaf]


def reduce_planes(planes, span: str, chips: int) -> Trace:
    """``planes``: objects with ``name`` and ``lines``, each line with
    ``name`` and ``events`` (``name``, ``start_ns``, ``duration_ns``), as
    ``jax.profiler.ProfileData`` gives them."""
    win = None
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            idx = plane.name[len("/device:TPU:"):]
            if idx.isdigit() and int(idx) < chips:
                devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == span:
                    win = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if win is None:
        raise ValueError(f"no {span!r} span in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    w0, w1 = win
    ops: dict = {}
    modules: dict = {}
    busy = 0.0
    gaps = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        op_line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        intervals = []
        for ev in _leaves(op_line.events if op_line else ()):
            a, b = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
            if b <= a:
                continue
            intervals.append((a, b))
            rec = ops.setdefault(op_name(ev.name), [0, 0.0])
            rec[0] += 1
            rec[1] += (b - a) * 1e-9
        mods = []
        for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines
                   else ()):
            if w0 <= ev.start_ns < w1:
                name = _strip(ev.name)
                modules.setdefault(name, []).append(ev.duration_ns * 1e-9)
                mods.append((ev.start_ns, name))
        mods.sort()
        merged = _union(intervals)
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) * 1e-9,
                             f"host loop after {_module_at(mods, a)}"))
    n = len(devices)
    return Trace(window_s=(w1 - w0) * 1e-9, busy_s=busy / n,
                 ops=ops, modules=modules, gaps=gaps)


def reduce(trace_dir: str, span: str, chips: int) -> Trace:
    """Read the ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace file, found {files}")
    return reduce_planes(ProfileData.from_file(files[0]).planes, span, chips)
