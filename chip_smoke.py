#!/usr/bin/env python3
"""Run the monitored main path once on a TPU and check what comes out.

    python chip_smoke.py            # one chip: the four phases below
    python chip_smoke.py --chips 4  # four chips: the distributed AD step only

One chip, one process, phases in order:

  serve          granite-moe-1b-a400m at published widths through
                 ``launch.serve.serve`` with a ChimbukoMonitor ingesting
                 every wave;
  serve_logits   prefill(S-1) + decode(last) through the launcher's step
                 builders == ``models.model.forward`` over all S tokens;
  train          ``launch.train.train`` at smoke widths with async
                 checkpoints, socket PS/provenance shards in spawned
                 workers and a live trace export, then
                 ``python -m repro.export --validate`` on that trace;
  device_ad      the Mosaic moments kernel (``kernels.ops.moments_update``)
                 == XLA ``core.jax_ad.ad_step`` == the host NumPy detector
                 on 64K-event frames at F=2048.

``--chips 4`` runs ``core.jax_ad.make_distributed_ad_step`` on a ranks=4 and
a ranks=2 x funcs=2 mesh, with and without the kernel, against a one-device
``ad_step`` on the same events.

JAX's persistent compilation cache is on (``launch.compile_cache``).  The
script exits non-zero, printing no result, when JAX finds no TPU or a phase
fails.  Its last line of output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Everything that touches JAX runs under ``main``: the shard workers of the
train phase are spawned processes that re-import this module and must stay
off the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import subprocess
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
OUT = os.path.join(ROOT, ".chip_smoke")  # checkpoints and monitor output
ARCH = "granite_moe_1b_a400m"
ALPHA, MIN_COUNT = 6.0, 10.0


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _rel_err(got, want) -> float:
    """max |got - want| over the RMS of ``want``."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.sqrt(np.mean(want * want)))


def _check_close(name, got, want, rtol) -> float:
    """Assert ``got`` within ``rtol`` of nonzero ``want``; return the max relative error."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    rel = np.abs(got - want) / np.abs(want)
    if not rel.max() <= rtol:
        i = int(np.argmax(rel))
        raise AssertionError(
            f"{name}: {int((rel > rtol).sum())} of {rel.size} differ beyond "
            f"rtol={rtol}; worst at {i}: {got.flat[i]!r} vs {want.flat[i]!r}"
        )
    return float(rel.max())


# ------------------------------------------------------------------ phases
def phase_serve(full=True, n_requests=16, batch=8, prompt_len=512, max_new=32):
    from repro.launch.serve import serve
    from repro.trace.monitor import ChimbukoMonitor

    monitor = ChimbukoMonitor(num_funcs=16, min_samples=8)
    try:
        out = serve(
            arch=ARCH, smoke=not full, n_requests=n_requests, batch=batch,
            prompt_len=prompt_len, max_new=max_new, monitor=monitor,
        )
    finally:
        monitor.close()
    waves = -(-n_requests // batch)
    mon = out["monitor"]
    assert out["requests"] == n_requests, out["requests"]
    assert out["tokens"] == n_requests * max_new, out["tokens"]
    assert mon["frames"] == waves, (mon["frames"], waves)
    # entry + exit of one prefill and max_new decode-step spans per wave
    assert mon["events"] == 2 * waves * (1 + max_new), mon["events"]
    return {
        "arch": ARCH, "full_width": full, "requests": out["requests"],
        "batch": batch, "prompt_len": prompt_len, "max_new": max_new,
        "tokens": out["tokens"], "compile_s": out["compile_s"],
        "serve_s": out["serve_s"], "tok_per_s": out["tok_per_s"],
        "monitor_frames": mon["frames"], "monitor_events": mon["events"],
        "monitor_anomalies": mon["anomalies"], "peak_bytes_in_use": _peak_bytes(),
    }


# Logit errors are max |path - forward| over the forward's RMS.
#  * Both sides at "highest" precision: f32 arithmetic, so the error is
#    summation order (prefill + cached decode vs one pass) through 24
#    layers, ~1e-6.  A wrong cache slot, mask or position moves the logits
#    by their own scale.  Bound 1e-3.
#  * The serving default precision (one bf16 pass per f32 matmul on TPU,
#    8-bit mantissas) against the "highest" forward: rounding of ~2^-8 per
#    product, plus top-8 router choices that flip where two experts' scores
#    lie within that rounding.  Bound 0.25: it catches garbage and lost
#    precision paths, the bound above catches cache logic.
TOL_HIGHEST, TOL_DEFAULT = 1e-3, 0.25


def phase_serve_logits(full=True, B=2, S=128, seed=0):
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.launch.steps import (
        StepOptions, build_decode_step, build_prefill_step, make_shard_ctx,
    )
    from repro.models import model as M
    from repro.models.common import init_params

    cfg = configs.get_config(ARCH) if full else configs.smoke(ARCH)
    # f32 compute; capacity E/k makes MoE dropless (a token sends at most one
    # slot to an expert), so prefill of S-1 and forward of S drop nothing.
    cfg = dataclasses.replace(
        cfg, compute_dtype=jnp.float32,
        moe_capacity_factor=cfg.moe_experts / cfg.moe_topk,
    )
    params = init_params(cfg, jax.random.key(seed))
    tokens = jax.random.randint(jax.random.key(seed + 1), (B, S), 0, cfg.vocab)
    opts = StepOptions()
    ctx = make_shard_ctx(cfg, None, B, opts)
    prefill = jax.jit(build_prefill_step(cfg, ctx, opts, max_seq=S))
    decode = jax.jit(build_decode_step(cfg, ctx, opts))
    V = cfg.vocab  # logits past it are the masked vocab padding
    forward = jax.jit(lambda p, t: M.forward(cfg, p, {"tokens": t})[:, -1, :V])

    def cached(params, tokens):
        _, cache = prefill(params, {"tokens": tokens[:, : S - 1]})
        return decode(params, cache, tokens[:, S - 1 :])[0][:, 0, :V]

    with jax.default_matmul_precision("highest"):
        ref = forward(params, tokens)
        hi = cached(params, tokens)
    lo = cached(params, tokens)
    assert bool(jnp.isfinite(ref).all() & jnp.isfinite(lo).all())
    assert ref.shape == (B, V), ref.shape
    err_hi, err_lo = _rel_err(hi, ref), _rel_err(lo, ref)
    info = {
        "B": B, "S": S, "vocab": cfg.vocab, "err_highest": err_hi,
        "err_default": err_lo, "tol_highest": TOL_HIGHEST,
        "tol_default": TOL_DEFAULT, "peak_bytes_in_use": _peak_bytes(),
    }
    print(f"  serve_logits {json.dumps(info)}", flush=True)
    assert err_hi <= TOL_HIGHEST, err_hi
    assert err_lo <= TOL_DEFAULT, err_lo
    return info


def phase_train(steps=12):
    from repro.checkpoint import ckpt as CK
    from repro.launch.train import train

    ckpt_dir = os.path.join(OUT, "ckpt")
    mon_dir = os.path.join(OUT, "monitor")
    out = train(
        arch=ARCH, smoke=True, steps=steps, global_batch=8, seq=64,
        ckpt_dir=ckpt_dir, monitor_dir=mon_dir, ckpt_interval=4, log_every=4,
        ps_transport="socket", provdb_transport="socket",
        shard_endpoints="spawn:2", export_trace=True,
    )
    losses = [h["loss"] for h in out["history"]]
    mon = out["monitor"]
    assert len(losses) == steps and all(math.isfinite(l) for l in losses), losses
    assert mon["frames"] == steps, mon["frames"]
    assert mon["ps_transport"] == mon["provdb_transport"] == "socket"
    assert CK.latest_step(ckpt_dir) == steps, CK.latest_step(ckpt_dir)
    trace = os.path.join(mon_dir, "trace.json")
    r = subprocess.run(
        [sys.executable, "-m", "repro.export", "--validate", trace],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": SRC},
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return {
        "arch": ARCH, "full_width": False, "steps": steps,
        "loss_first": losses[0], "loss_last": losses[-1],
        "monitor_frames": mon["frames"], "monitor_events": mon["events"],
        "ps_transport": mon["ps_transport"], "ps_shards": mon["ps_shards"],
        "checkpoint_step": steps, "trace_validate": r.stdout.strip()[-300:],
        "peak_bytes_in_use": _peak_bytes(),
    }


def make_frames(num_funcs, num_events, frames, seed=0):
    """Synthetic AD frames: per-function mean runtimes 1..1000 µs, ±10%
    uniform noise, and 50x outliers at a rate of 2^-12 in the last frame only.

    A normal event lies within 1.8σ of its function's mean and an outlier
    about 850σ above it, so no event is near the 6σ threshold where float32
    and float64 detectors could disagree.
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    base = np.exp(rng.uniform(0.0, np.log(1000.0), num_funcs))
    out = []
    for k in range(frames):
        fids = rng.integers(0, num_funcs, num_events).astype(np.int32)
        durs = base[fids] * rng.uniform(0.9, 1.1, num_events)
        outlier = np.zeros(num_events, bool)
        if k == frames - 1:
            outlier = rng.random(num_events) < 2.0**-12
            durs[outlier] *= 50.0
        out.append((fids, durs.astype(np.float32), outlier))
    return out


# Table tolerances, against the float64 host path or the one-device XLA
# step (columns n, mean, M2, min, max):
#  * n, min, max are exact: counts below 2^24 and the same f32 inputs.
#  * mean: float32 sums of ~32 events per frame, merged over frames;
#    relative error ~ n·eps ≈ 2e-6.  rtol 1e-5.
#  * M2, XLA path: two-pass per-frame M2 then Pébay merges.  rtol 1e-4.
#  * M2, kernel path: recovered per frame as Σx² − nμ², which loses
#    log10(1 + 1/CV²) ≈ 2.5 digits at CV ≈ 0.058 (±10% uniform noise); the
#    6σ detector needs ~3 digits of σ.  rtol 1e-2.
def _check_table(name, table, want, kernel_m2: bool):
    import numpy as np

    got, want = np.asarray(table, np.float64), np.asarray(want, np.float64)
    seen = want[:, 0] > 0
    np.testing.assert_array_equal(got[:, 0], want[:, 0], err_msg=f"{name} n")
    np.testing.assert_array_equal(got[seen, 3:], want[seen, 3:], err_msg=f"{name} min/max")
    return {
        "mean": _check_close(f"{name} mean", got[seen, 1], want[seen, 1], 1e-5),
        "m2": _check_close(f"{name} M2", got[seen, 2], want[seen, 2],
                           1e-2 if kernel_m2 else 1e-4),
    }


def phase_device_ad(num_funcs=2048, num_events=65536, frames=4, check_mosaic=True):
    import jax
    import numpy as np

    from repro.core import jax_ad as J
    from repro.core import stats as HS
    from repro.core.ad import SstdDetector
    from repro.kernels import ops

    data = make_frames(num_funcs, num_events, frames)
    host, det = HS.StatsTable(num_funcs), SstdDetector(ALPHA, int(MIN_COUNT))
    t_x = t_k = J.init_table(num_funcs)
    if check_mosaic:
        f0, d0, _ = data[0]
        text = ops.moments_update.lower(t_k, f0, d0).compile().as_text()
        assert "tpu_custom_call" in text, "kernel did not compile to Mosaic"
    times = {"xla_ms": [], "kernel_ms": []}
    flagged = 0
    for fids, durs, outlier in data:
        x64 = durs.astype(np.float64)
        lab_h = det.label(host, fids, x64)
        host.update_batch(fids, x64)
        fd, dd = jax.device_put(fids), jax.device_put(durs)
        t0 = time.perf_counter()
        t_x, lab_x = jax.block_until_ready(J.ad_step(t_x, fd, dd, ALPHA, MIN_COUNT))
        t1 = time.perf_counter()
        t_k, lab_k = jax.block_until_ready(ops.moments_update(t_k, fd, dd, ALPHA, MIN_COUNT))
        t2 = time.perf_counter()
        times["xla_ms"].append((t1 - t0) * 1e3)
        times["kernel_ms"].append((t2 - t1) * 1e3)
        np.testing.assert_array_equal(np.asarray(lab_x), lab_h, err_msg="XLA labels")
        np.testing.assert_array_equal(np.asarray(lab_k), lab_h, err_msg="kernel labels")
        np.testing.assert_array_equal(lab_h.astype(bool), outlier, err_msg="host labels")
        flagged += int(lab_h.sum())
    assert flagged > 0
    want = host.table[:, [HS.N, HS.MEAN, HS.M2, HS.MIN, HS.MAX]]
    err_x = _check_table("XLA", t_x, want, kernel_m2=False)
    err_k = _check_table("kernel", t_k, want, kernel_m2=True)
    return {
        "F": num_funcs, "events_per_frame": num_events, "frames": frames,
        "anomalies": flagged, "mosaic": check_mosaic,
        "max_rel_err_xla": err_x, "max_rel_err_kernel": err_k,
        "step_ms_host_clock": times, "peak_bytes_in_use": _peak_bytes(),
    }


def phase_distributed_ad(num_funcs=2048, ranks=8, events_per_rank=8192, frames=3,
                         check_mosaic=True):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import jax_ad as J

    devices = jax.devices()
    assert len(devices) == 4, f"--chips 4 needs 4 devices, JAX has {len(devices)}"
    data = make_frames(num_funcs, ranks * events_per_rank, frames, seed=1)
    auto = jax.sharding.AxisType.Auto
    meshes = {
        "ranks=4": (jax.make_mesh((4,), ("ranks",), (auto,), devices=devices), None),
        "ranks=2,funcs=2": (
            jax.make_mesh((2, 2), ("ranks", "funcs"), (auto, auto), devices=devices),
            "funcs",
        ),
    }
    # One-device reference on the same events.
    ref_table, ref_labels = J.init_table(num_funcs), []
    for fids, durs, _ in data:
        ref_table, lab = J.ad_step(ref_table, fids, durs, ALPHA, MIN_COUNT)
        ref_labels.append(np.asarray(lab))
    info = {}
    for mesh_name, (mesh, func_axis) in meshes.items():
        for use_pallas in (False, True):
            name = f"{mesh_name},pallas={use_pallas}"
            step = J.make_distributed_ad_step(
                mesh, ("ranks",), ALPHA, MIN_COUNT, use_pallas=use_pallas,
                func_axis=func_axis,
            )
            ev_sh = NamedSharding(mesh, P("ranks"))
            table = jax.device_put(
                J.init_table(num_funcs), NamedSharding(mesh, P(func_axis))
            )
            devs = set()
            for k, (fids, durs, _) in enumerate(data):
                f = jax.device_put(fids.reshape(ranks, -1), ev_sh)
                d = jax.device_put(durs.reshape(ranks, -1), ev_sh)
                if k == 0 and use_pallas and check_mosaic:
                    text = step.lower(table, f, d).compile().as_text()
                    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel"
                table, labels = step(table, f, d)
                for arr in (table, labels):
                    devs |= {dev.id for dev in arr.sharding.device_set}
                np.testing.assert_array_equal(
                    np.asarray(labels).reshape(-1), ref_labels[k], err_msg=f"{name} labels"
                )
            errs = _check_table(name, table, ref_table, kernel_m2=use_pallas)
            info[name] = {
                "table_devices": sorted(d.id for d in table.sharding.device_set),
                "label_devices": sorted(d.id for d in labels.sharding.device_set),
                "max_rel_err": errs, "anomalies": int(np.asarray(labels).sum()),
            }
            print(f"  {name} {json.dumps(info[name])}", flush=True)
            assert len(devs) == 4, f"{name}: outputs on devices {sorted(devs)}"
    return info


# -------------------------------------------------------------------- main
def _run_phase(name, fn, results):
    print(f"[phase] {name} start", flush=True)
    t0 = time.perf_counter()
    try:
        info = fn()
        ok = True
    except Exception:
        traceback.print_exc()
        info, ok = {}, False
    wall = time.perf_counter() - t0
    status = "PASS" if ok else "FAIL"
    print(f"[phase] {name} {status} wall_s={wall:.3f} {json.dumps(info, default=str)}",
          flush=True)
    results[name] = ok


def _compile_counters():
    """Backend compile time and persistent-cache traffic, from JAX's own events."""
    from jax import monitoring

    c = {"backend_compile_s": 0.0, "backend_compiles": 0, "cache_hits": 0,
         "cache_writes": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            c["backend_compile_s"] += duration
            c["backend_compiles"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            c["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            c["cache_writes"] += 1  # JAX records this event as it writes an entry

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return c


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed AD step across four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    counters = _compile_counters()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"[chip_smoke] device {json.dumps(dev)} jax {jax.__version__} "
          f"compile_cache {cache_dir}", flush=True)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)

    results = {}
    if args.chips == 4:
        _run_phase("distributed_ad", phase_distributed_ad, results)
    else:
        _run_phase("serve", phase_serve, results)
        _run_phase("serve_logits", phase_serve_logits, results)
        _run_phase("train", phase_train, results)
        _run_phase("device_ad", phase_device_ad, results)
    print(f"[compile] {json.dumps(counters)}", flush=True)
    failed = [name for name, ok in results.items() if not ok]
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
