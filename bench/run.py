#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload serve-granite-chat --seed 7 --seconds 30 --trace 0

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file ``bench/configs/<config>.json`` (its ``driver`` names
``bench/drivers/<driver>.py``), the traffic file
``bench/traffic/<traffic>.json``, and one reader per per-layer metric,
``bench/metrics/<metric>.py``.  A new cell or metric is new files plus
``BENCHMARK.json`` entries.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (a profiler trace of the window), plus
``busy_s``/``window_s`` and a ``breakdown``.  The last line of standard
output is one JSON object; the numbers compared for ``correct`` are the
last lines of standard error and the last key of that object.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  JAX's persistent compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache/`` at the
checkout's root.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest: dict, workload: str):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    with open(os.path.join(BENCH, "configs", f"{cell['config']}.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    return cell, config, traffic


def metrics_for(manifest: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports in a run of this kind."""
    name = cell["name"]
    if not trace:
        return [m for m in manifest["end_to_end"] if name in m.get("workloads", [name])]
    e2e = {m["name"] for m in metrics_for(manifest, cell, False)}
    return [m for m in manifest["per_layer"]
            if name in m.get("workloads", [name]) and m["moves"] in e2e]


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_per_layer(manifest, cell, R) -> dict:
    out = {}
    for m in metrics_for(manifest, cell, True):
        reader = _module(os.path.join(BENCH, "metrics", f"{m['name']}.py"),
                         f"bench_metric_{m['name'].replace('.', '_').replace('-', '_')}")
        value = reader.read(R)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def init_jax(cell: dict):
    """The TPU devices for ``cell``, with JAX's persistent compilation cache
    at ``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/`` in the checkout;
    None, after saying why, where JAX finds no TPU or too few chips."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devices


def is_correct(check: dict) -> bool:
    return all(limit is not None and value <= limit for value, limit in check.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = load_manifest()
    cell, config, traffic = find_cell(manifest, args.workload)
    devices = init_jax(cell)
    if devices is None:
        return 3
    from lib.compile_events import compile_counters
    from lib.peaks import peaks_for

    kind = devices[0].device_kind
    ctx = SimpleNamespace(
        root=ROOT, cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_start=T_START,
        compiles=compile_counters(), peaks=peaks_for(kind), smoke=False,
    )
    driver = _module(os.path.join(BENCH, "drivers", f"{config['driver']}.py"),
                     f"bench_driver_{config['driver']}")
    res = driver.run(ctx)
    line = result_line(manifest, cell, res, ctx, devices)
    print(f"[info] {json.dumps(res['info'])} compile {json.dumps(ctx.compiles)} "
          f"check {json.dumps(getattr(ctx, 'info_check', {}))}", file=sys.stderr)
    for name, (value, limit) in res["check"].items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def result_line(manifest, cell, res, ctx, devices) -> dict:
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": res["peak"]}
    if ctx.trace:
        from lib import trace as T

        R = res["R"]
        metrics = read_per_layer(manifest, cell, R)
        device["busy_s"] = T.mean_busy_s(R.trace) if R.trace else 0.0
        device["window_s"] = R.window_s
    else:
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
        metrics = {name: {"value": v, "unit": units[name]} for name, v in res["metrics"].items()
                   if v is not None and name in {m["name"] for m in metrics_for(manifest, cell, False)}}
    line = {
        "correct": is_correct(res["check"]),
        "attempted": res["attempted"],
        "failed": res["attempted"] - res["completed"],
        "metrics": metrics,
        "device": device,
    }
    if ctx.trace and res["R"].trace:
        from lib import trace as T

        line["breakdown"] = T.breakdown(res["R"].trace)
    line["check"] = {name: {"value": v, "limit": lim} for name, (v, lim) in res["check"].items()}
    return line


if __name__ == "__main__":
    sys.exit(main())
