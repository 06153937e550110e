"""Readings that set the benchmark's numbers, run by hand on the chip.
Never run by ``bench/run.py``.

    python3 bench/calibrate.py limits --workload <cell> --seeds 1 2 ... --seconds s
    python3 bench/calibrate.py limits --workload <cell> --program compute_dtype=bfloat16 ...
    python3 bench/calibrate.py sweep --workload <cell> --rates 4 6 8 --seconds s
    python3 bench/calibrate.py rate --workload <cell> --rate r --seconds s

``limits`` serves the cell on each seed in one process and prints, per
seed, the widest gap of the program's served tokens and of each control
(the reference in the next precision below the configuration's), on the
same sample: the two readings a cell's limit is set between.  Each
control's numbers are held to the cell's limits file and its verdict is
printed: a limit stands only where every control reads not correct.
With ``--program KEY=VALUE`` the program itself runs with that key of
the configuration's ``program`` block changed (a lower-precision path of
its own, which then is the control) and the verdict is that run's.
``sweep`` offers the cell's traffic at each rate and prints the tails,
the queue waits early and late in the window (a growing backlog shows
as late waits far above early ones) and the completion rate.  ``rate``
runs an offline batch at one rate and prints how long it took to drain.
Each prints one JSON line per reading on standard output.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

# The controls of each configuration's stated precision (PERF.md).
CONTROLS = {
    "faithful": {"high": {"dot": "bf16x3"}, "bf16": {"dot": "bf16"},
                 "bf16_state": {"slice_store": "bfloat16"}},
    "fast": {"fp8_state": {"store": "float8_e4m3"},
             "bf16_gemm": {"dot": "bf16"}},
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("command", choices=("limits", "sweep", "rate"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[1])
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--rate", type=float)
    ap.add_argument("--program", nargs="*", default=[],
                    metavar="KEY=VALUE")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.harness import load_cell, run_cell

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    cell = load_cell(args.workload)
    for item in args.program:
        key, value = item.split("=", 1)
        cell.conf["program"][key] = value

    def emit(rec):
        print(json.dumps(rec), flush=True)

    if args.command == "limits":
        controls = ({} if args.program
                    else CONTROLS[cell.conf["device"]["mode"]])
        for seed in args.seeds:
            line = run_cell(cell, seed, args.seconds, False, devices,
                            time.monotonic(), controls=controls)
            verdicts = line["controls"]
            if args.program:
                verdicts = {" ".join(args.program): {
                    "correct": line["correct"], "compared": line["compared"]}}
            for name, v in verdicts.items():
                print(f"control {name}: "
                      f"{'NOT caught' if v['correct'] else 'caught'} "
                      f"{v['compared']}", file=sys.stderr, flush=True)
            emit({"seed": seed, "readings": line["readings"],
                  "controls": verdicts, "correct": line["correct"],
                  "metrics": line["metrics"], "failed": line["failed"]})
    else:
        rates = args.rates if args.command == "sweep" else [args.rate]
        for rate in rates:
            c = copy.deepcopy(cell)
            c.mix["arrivals"]["rate"] = rate
            got = run_cell(c, args.seeds[0], args.seconds, False, devices,
                           time.monotonic(), verify=False)
            emit({"rate": rate, **sweep_record(got["ctx"])})
    return 0


def sweep_record(ctx) -> dict:
    from bench.harness import END_TO_END
    from bench.traffic import percentile

    res = ctx["results"]
    waits = [r.admit_time - r.submit_time for r in res]
    third = max(1, len(res) // 3)
    rep = ctx["report"]
    return {
        "requests": len(res), "wall_s": rep.wall_s,
        "completed_per_s": len(res) / rep.wall_s,
        "sim_tokens_per_s": END_TO_END["sim_tokens_per_s"](ctx),
        "ttft_p90_s": END_TO_END["ttft_p90_s"](ctx),
        "tpot_p90_ms": END_TO_END["tpot_p90_ms"](ctx),
        "wait_p50_first_third_s": percentile(waits[:third], 50),
        "wait_p50_last_third_s": percentile(waits[-third:], 50),
        "occupancy": rep.occupancy,
        "setup_s": ctx["setup_s"],
    }


if __name__ == "__main__":
    sys.exit(main())
