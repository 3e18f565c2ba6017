import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

The FIRST two lines above must run before any jax import: jax locks the
device count at first init, and the dry-run needs 512 placeholder host
devices to build the production meshes (16×16 single pod, 2×16×16 two pods).

Per cell this script:
  1. builds the production mesh and the cell's step function + sharded
     ShapeDtypeStruct inputs (launch/steps.make_cell — the same builder the
     real launchers execute),
  2. ``.lower().compile()`` — any sharding mismatch, unsupported collective,
     or compile-time OOM is a FAILURE of the framework,
  3. records memory_analysis / cost_analysis / collective-bytes into a JSON
     artifact that benchmarks/bench_roofline.py and EXPERIMENTS.md consume.

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--skip-existing]
"""
import argparse
import json
import sys
import time
import traceback


def probe_costs(cfg, shape: str, mesh, opts_kw, microbatch: int) -> dict:
    """Exact per-cell cost accounting via unrolled 1- and 2-period compiles.

    XLA's cost_analysis counts while-loop bodies ONCE (verified in
    tests/test_dryrun.py), so scanned models under-report FLOPs by ~n_periods.
    Probe compiles unroll every scan (layers, CE chunks, microbatches) and
    use direct attention / whole-sequence mamba chunks (identical FLOPs to
    the masked chunked implementations, tiny HLO).  Costs are affine in the
    period count, so:  total = C(1) + (n_periods − 1)·(C(2) − C(1)).
    """
    import dataclasses as dc

    from repro.launch import roofline as R
    from repro.launch.steps import StepOptions, make_cell

    vals = {}
    for npd in (1, 2):
        pcfg = dc.replace(cfg, n_layers=cfg.period * npd)
        opts = StepOptions(**{**opts_kw, "probe": True, "microbatch": microbatch})
        cell = make_cell(pcfg, shape, mesh, opts)
        compiled = cell.lower().compile()
        ca = compiled.cost_analysis()
        coll = R.collective_bytes(compiled.as_text())
        vals[npd] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
            "wire_bytes": sum(v["wire_bytes"] for v in coll.values()),
            "collectives": coll,
        }
    NP = cfg.n_periods
    ex = lambda k: vals[1][k] + (NP - 1) * (vals[2][k] - vals[1][k])
    out = {
        "period1": vals[1],
        "period2": vals[2],
        "n_periods": NP,
        "flops": ex("flops"),
        "bytes_accessed": ex("bytes_accessed"),
        "transcendentals": ex("transcendentals"),
        "wire_bytes": ex("wire_bytes"),
    }
    out["collectives"] = {
        op: {
            k: vals[1]["collectives"][op][k]
            + (NP - 1) * (vals[2]["collectives"][op][k] - vals[1]["collectives"][op][k])
            for k in ("count", "result_bytes", "wire_bytes")
        }
        for op in vals[1]["collectives"]
    }
    return out


def run_cell(
    arch: str, shape: str, multi_pod: bool, out_dir: str, opts_kw=None,
    probes: bool = False,
) -> dict:
    import jax

    from repro import configs
    from repro.launch import roofline as R
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import StepOptions, auto_microbatch, make_cell

    cfg = configs.get_config(arch)
    ok, why = configs.cell_applicable(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "devices": 512 if multi_pod else 256, "status": "skipped", "reason": why,
    }
    if not ok:
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    opts = StepOptions(**(opts_kw or {}))
    t0 = time.time()
    cell = make_cell(arch, shape, mesh, opts)
    lowered = cell.lower()
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    rec.update(status="ok", lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
               mode=cell.mode, opts=str(opts))

    # ---- memory --------------------------------------------------------
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(ma, k))
            for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(ma, k)
        }
        if rec["memory"]:
            m = rec["memory"]
            live = (
                m.get("argument_size_in_bytes", 0)
                + m.get("output_size_in_bytes", 0)
                + m.get("temp_size_in_bytes", 0)
                - m.get("alias_size_in_bytes", 0)
            )
            rec["memory"]["live_bytes_per_device"] = int(live)
            rec["memory"]["fits_16gb_hbm"] = bool(live < 16 * 1024**3)
    except Exception as e:  # pragma: no cover
        rec["memory_error"] = repr(e)

    # ---- cost ----------------------------------------------------------
    try:
        ca = compiled.cost_analysis()
        rec["cost"] = {
            "flops": float(ca.get("flops", -1.0)),
            "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
            "transcendentals": float(ca.get("transcendentals", -1.0)),
        }
    except Exception as e:  # pragma: no cover
        rec["cost_error"] = repr(e)

    # ---- collectives ----------------------------------------------------
    try:
        hlo = compiled.as_text()
        coll = R.collective_bytes(hlo)
        rec["collectives"] = coll
        rec["wire_bytes_per_device"] = sum(v["wire_bytes"] for v in coll.values())
        rec["hlo_lines"] = hlo.count("\n")
    except Exception as e:  # pragma: no cover
        rec["collective_error"] = repr(e)

    # ---- roofline -------------------------------------------------------
    cellspec = configs.SHAPES[shape]
    rec["model_flops_global"] = R.model_flops(
        cfg, cell.mode, cellspec.global_batch, cellspec.seq_len
    )
    rec["n_params"] = cfg.n_params()
    rec["n_active_params"] = cfg.n_active_params()
    if "cost" in rec and rec["cost"]["flops"] > 0:
        terms = R.roofline_terms(
            rec["cost"]["flops"],
            rec["cost"]["bytes_accessed"],
            rec.get("wire_bytes_per_device", 0.0),
        )
        terms["model_vs_hlo_flops"] = rec["model_flops_global"] / (
            rec["cost"]["flops"] * rec["devices"]
        )
        rec["roofline"] = terms

    # ---- probe-corrected roofline (unrolled cost accounting) -------------
    if probes:
        try:
            dp = rec["devices"] // 16  # model axis is always 16
            mbv = 1
            if cell.mode == "train":
                mbv = opts.microbatch or auto_microbatch(
                    cfg, cellspec.global_batch, cellspec.seq_len, dp
                )
            pr = probe_costs(cfg, shape, mesh, opts_kw or {}, mbv)
            rec["probe"] = pr
            terms = R.roofline_terms(
                pr["flops"], pr["bytes_accessed"], pr["wire_bytes"]
            )
            terms["model_vs_hlo_flops"] = rec["model_flops_global"] / max(
                pr["flops"] * rec["devices"], 1.0
            )
            # kernel-corrected memory term: subtract the direct-attention
            # score materialization the flash kernel keeps in VMEM on TPU
            scores = R.attn_scores_traffic(
                cfg, cell.mode, cellspec.global_batch, cellspec.seq_len,
                rec["devices"],
            )
            terms["attn_scores_bytes"] = scores
            terms["memory_kernel_s"] = max(
                pr["bytes_accessed"] - scores, 0.0
            ) / R.HW["hbm_bw"]
            floor = R.analytic_memory_floor(
                cfg, cell.mode, cellspec.global_batch, cellspec.seq_len,
                rec["devices"], mbv,
            )
            terms["memory_floor_bytes"] = floor
            terms["memory_floor_s"] = floor / R.HW["hbm_bw"]
            rec["roofline_probe"] = terms
            rec["microbatch"] = mbv
        except Exception as e:  # pragma: no cover
            rec["probe_error"] = repr(e)
            rec["probe_traceback"] = traceback.format_exc()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--remat", default="block")
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ce-chunk", type=int, default=1024)
    ap.add_argument("--seq-shard", type=int, default=0)
    ap.add_argument("--master-in-opt", type=int, default=0)
    ap.add_argument("--mamba-tp", type=int, default=1)
    ap.add_argument("--probes", action="store_true",
                    help="add unrolled probe compiles for exact cost accounting")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    from repro import configs

    os.makedirs(args.out_dir, exist_ok=True)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for arch in configs.ARCHS:
            canon = arch.replace("_", "-") if arch.replace("_", "-") in configs.ALIASES else arch
            for shape in configs.SHAPES:
                cells.append((canon, shape))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    opts_kw = dict(
        remat=args.remat, fsdp=bool(args.fsdp), microbatch=args.microbatch,
        ce_chunk=args.ce_chunk, seq_shard=bool(args.seq_shard),
        master_in_opt=bool(args.master_in_opt),
        mamba_tp=bool(args.mamba_tp),
    )
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"__{args.tag}" if args.tag else ""
            name = f"{arch}__{shape}__{'multi' if mp else 'single'}{tag}.json"
            path = os.path.join(args.out_dir, name)
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] skip (exists): {name}")
                continue
            print(f"[dryrun] {arch} × {shape} × {'multi' if mp else 'single'} ...",
                  flush=True)
            try:
                rec = run_cell(arch, shape, mp, args.out_dir, opts_kw,
                               probes=args.probes and not mp)
            except Exception as e:
                rec = {
                    "arch": arch, "shape": shape,
                    "mesh": "multi" if mp else "single",
                    "status": "error", "error": repr(e),
                    "traceback": traceback.format_exc(),
                }
                failures += 1
            with open(path, "w") as f:
                json.dump(rec, f, indent=2)
            status = rec["status"]
            extra = ""
            if status == "ok":
                extra = (
                    f" lower={rec['lower_s']}s compile={rec['compile_s']}s "
                    f"live={rec.get('memory', {}).get('live_bytes_per_device', 0)/2**30:.2f}GiB"
                )
            print(f"[dryrun]   -> {status}{extra}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
