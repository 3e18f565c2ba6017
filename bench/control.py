#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/control.py --workload serve-granite-chat --seconds 6 --seeds 11 12 13

For each seed it drives a short run of the cell (the timed path, at the
cell's own sizes and load) and prints the program's widest gap (a lower
reading), and the control's (an upper reading): the plain reference one
precision step down (fp8 operands for the configuration's bfloat16), read
at the same positions.  The mean gap and the off share are printed beside
them.  Both are judged by ``run.is_correct`` against the cell's limit: the
program should come out correct and the control not.  All seeds run in
one process.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import run as bench  # noqa: E402  (bench/ is on sys.path as the script's directory)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    manifest = bench.load_manifest()
    cell, config, traffic = bench.find_cell(manifest, args.workload)
    devices = bench.init_jax(cell)
    if devices is None:
        return 3
    from lib.compile_events import compile_counters
    from lib.peaks import peaks_for

    driver = bench._module(os.path.join(bench.BENCH, "drivers", f"{config['driver']}.py"),
                           f"bench_driver_{config['driver']}")
    compiles = compile_counters()
    rows = []
    for seed in args.seeds:
        ctx = SimpleNamespace(
            root=bench.ROOT, cell=cell, config=config, traffic=traffic, seed=seed,
            seconds=args.seconds, trace=False, t_start=time.perf_counter(),
            compiles=compiles, peaks=peaks_for(devices[0].device_kind), smoke=False,
            control=True,
        )
        res = driver.run(ctx)
        row = {"seed": seed, "correct": bench.is_correct(res["check"]),
               "control_correct": bench.is_correct(ctx.control_check),
               "check": res["check"], "info": ctx.info_check,
               "metrics": res["metrics"], "peak": res["peak"]}
        rows.append(row)
        print(f"[control] {json.dumps(row)}", flush=True)

    def col(key, sub=None):
        return [(r["info"][sub] if sub else r["info"])[key] for r in rows]

    summary = {"workload": cell["name"], "seeds": args.seeds}
    for key in ("mean_gap", "widest_gap", "off_share"):
        summary[f"program_{key}"] = col(key)
        summary[f"control_{key}"] = col(key, "control")
    summary["lower"] = max(summary["program_widest_gap"])
    summary["upper"] = min(summary["control_widest_gap"])
    summary["program_correct"] = [r["correct"] for r in rows]
    summary["control_correct"] = [r["control_correct"] for r in rows]
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
