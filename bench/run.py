"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and limits are found by name
from ``BENCHMARK.json``.  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the result carries its per-layer metrics.  The last line
of standard output is one JSON object; the numbers the correctness check
compared, each with its limit, are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    # the persistent compilation cache lives at a fixed path inside the
    # checkout, so only a cell's first run in a checkout compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from bench.harness import load_cell, log, run_cell

    cell = load_cell(args.workload)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"sees {len(devices)} {devices[0].platform} device(s)")
        return 2
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    devices, T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
