"""Serve cells: a monitored ``launch.serve.serve()`` call is the window.

Set-up: one warm-up ``serve()`` call of one wave at the cell's shapes
(it builds its parameters on the device from the seed and compiles both
steps, or loads them from the persistent cache), then the measured call's
own parameter build, compile load and first wave.  The window runs from
the return of the first wave's ``ChimbukoMonitor.ingest`` to the return of
the call, so it holds waves 2..n whole, ``block_until_ready`` included.

The benchmark observes the call through what ``serve()`` already accepts
and builds: the monitor it is handed (a subclass that stamps the host
clock at every ``record_step_times`` and ``ingest`` and passes each call
on unchanged) and its ``Request`` objects (a subclass that keeps a
reference to each, for the check after the window).
"""
from __future__ import annotations

import importlib.util
import os
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np


def _load_reference(root: str, config_name: str):
    path = os.path.join(root, "bench", "configs", f"{config_name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{config_name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check_program_config(c: dict, mcfg) -> None:
    """The program's config must be the one the file states."""
    import jax.numpy as jnp

    want = {
        "d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
        "n_layers": c["num_hidden_layers"], "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "moe_experts": c["num_local_experts"], "moe_topk": c["num_experts_per_tok"],
        "moe_dff": c["intermediate_size"], "vocab": c["vocab_size"],
        "vocab_padded": c["program"]["embedding_rows"],
        "rope_theta": c["rope_theta"], "norm_eps": c["rms_norm_eps"],
        "tie_embeddings": c["tie_word_embeddings"],
        "moe_capacity_factor": c["program"]["moe_capacity_factor"],
        "param_dtype": jnp.dtype(c["program"]["param_dtype"]),
        "compute_dtype": jnp.dtype(c["program"]["compute_dtype"]),
    }
    got = {k: getattr(mcfg, k) for k in want}
    got["param_dtype"] = jnp.dtype(got["param_dtype"])
    got["compute_dtype"] = jnp.dtype(got["compute_dtype"])
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise SystemExit(f"program config differs from the configuration file: {bad}")


def smoke_config(mcfg) -> dict:
    """A configuration dict for the program's smoke widths (CPU tests)."""
    import jax.numpy as jnp

    return {
        "name": "smoke", "hidden_size": mcfg.d_model, "intermediate_size": mcfg.moe_dff,
        "num_hidden_layers": mcfg.n_layers, "num_attention_heads": mcfg.n_heads,
        "num_key_value_heads": mcfg.n_kv_heads, "head_dim": mcfg.head_dim,
        "num_local_experts": mcfg.moe_experts, "num_experts_per_tok": mcfg.moe_topk,
        "vocab_size": mcfg.vocab, "rope_theta": mcfg.rope_theta,
        "rms_norm_eps": mcfg.norm_eps, "tie_word_embeddings": True,
        "program": {
            "arch": mcfg.name, "param_dtype": jnp.dtype(mcfg.param_dtype).name,
            "compute_dtype": jnp.dtype(mcfg.compute_dtype).name,
            "moe_capacity_factor": mcfg.moe_capacity_factor,
            "embedding_rows": mcfg.vocab_padded,
        },
    }


def _log(ctx, msg):
    print(f"[bench {time.perf_counter() - ctx.t_start:8.2f}s] {msg}", file=sys.stderr, flush=True)


def _peak_bytes():
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices())


def run(ctx) -> dict:
    import jax

    from repro import configs
    from repro.launch import serve as S
    from repro.telemetry import registry as telemetry
    from repro.trace.monitor import ChimbukoMonitor

    from lib import stages
    from lib import trace as T

    c, tr = ctx.config, ctx.traffic
    arch = c["program"]["arch"]
    mcfg = configs.smoke(arch) if ctx.smoke else configs.get_config(arch)
    if not ctx.smoke:
        _check_program_config(c, mcfg)
    B, P, NEW = tr["batch"], tr["prompt_len"], tr["max_new"]
    prog_seed = ctx.seed % (2**31)

    class Observer(ChimbukoMonitor):
        def __init__(self, on_first_wave=None):
            super().__init__(num_funcs=16, min_samples=8)
            self.step_ns, self.ingest_ns = [], []
            self._on_first_wave = on_first_wave

        # The annotations name the monitor's calls in a profiler trace, so
        # that idle gaps they cause are labelled by them.
        def record_step_times(self, step, times_by_rank):
            self.step_ns.append(time.perf_counter_ns())
            with jax.profiler.TraceAnnotation("bench/monitor.record_step_times"):
                return super().record_step_times(step, times_by_rank)

        def ingest(self, frame):
            with jax.profiler.TraceAnnotation("bench/monitor.ingest"):
                res = super().ingest(frame)
            self.ingest_ns.append(time.perf_counter_ns())
            if len(self.ingest_ns) == 1 and self._on_first_wave is not None:
                self._on_first_wave()
            return res

    made = []

    class Req(S.Request):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    def call(n_requests, monitor):
        orig, S.Request = S.Request, Req
        try:
            return S.serve(arch=arch, smoke=ctx.smoke, n_requests=n_requests, batch=B,
                           prompt_len=P, max_new=NEW, seed=prog_seed, monitor=monitor)
        finally:
            S.Request = orig

    # ---- set-up: one warm-up wave at the cell's shapes sizes the window
    warm_mon = Observer()
    warm = call(B, warm_mon)
    warm_mon.close()
    wave_s = warm["serve_s"]
    n_win = max(1, round(ctx.seconds / wave_s))
    n_waves = 1 + n_win
    made.clear()
    _log(ctx, f"warm-up wave {wave_s:.3f}s (serve compile_s {warm['compile_s']:.2f}); "
              f"measuring {n_win} waves")

    trace_dir = os.path.join(ctx.root, "bench", ".traces", ctx.cell["name"])
    win = {}

    def open_window():
        if ctx.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        win["stages"] = telemetry.get_registry().snapshot()
        win["compiles"] = dict(ctx.compiles)
        win["t0"] = time.perf_counter()

    mon = Observer(on_first_wave=open_window)
    out = call(B * n_waves, mon)
    t_end = time.perf_counter()
    compiles_in_window = ctx.compiles["backend_compiles"] - win["compiles"]["backend_compiles"]
    if ctx.trace:
        jax.profiler.stop_trace()
    stage_delta = stages.delta(win["stages"], telemetry.get_registry().snapshot())
    summary = out["monitor"]
    mon.close()
    window_s = t_end - win["t0"]
    setup_s = win["t0"] - ctx.t_start
    _log(ctx, f"window closed: {window_s:.3f}s, set-up {setup_s:.3f}s")

    # ---- end-to-end metrics (host clock, the benchmark's own stamps)
    gaps_ms = decode_gaps_ms(mon.step_ns, n_waves, NEW)
    completed_in_window = out["requests"] - B
    metrics = {
        "serve_req_s": completed_in_window / window_s,
        "decode_gap_p95_ms": float(np.percentile(gaps_ms, 95)) if gaps_ms.size else None,
        "setup_s": setup_s,
    }
    peak = _peak_bytes()

    # ---- per-layer context for the metric readers
    from lib import flops as F

    param_bytes = np.dtype(c["program"]["param_dtype"]).itemsize
    kv_bytes = 2 if c["program"]["compute_dtype"] == "bfloat16" else np.dtype(c["program"]["compute_dtype"]).itemsize
    trace = T.load(trace_dir) if ctx.trace else None
    if ctx.trace:
        _log(ctx, f"trace read: {sum(len(d['ops']) for d in trace['devices'].values()) if trace else 0}"
                  f" device ops, {len(trace['host']) if trace else 0} host events")
    R = SimpleNamespace(
        config=c, traffic=tr, cell=ctx.cell, window_s=window_s, trace=trace,
        peaks=ctx.peaks, stage_delta=stage_delta, waves_in_window=n_win, gaps_ms=gaps_ms,
        wave_flops=F.wave_flops(c, B, P, NEW),
        decode_step_flops=[F.decode_step_flops(c, B, P + t) for t in range(NEW)],
        decode_step_bytes=[F.decode_step_bytes(c, B, P + t, param_bytes, kv_bytes) for t in range(NEW)],
    )

    # ---- correctness, once the window has closed and its state is freed
    del out, warm
    check = _check(ctx, c, tr, made, summary, n_waves, B, P, NEW, gaps_ms, prog_seed)
    return {
        "attempted": B * n_waves, "completed": sum(len(r.out) == NEW for r in made),
        "metrics": metrics, "R": R, "peak": peak, "check": check,
        "info": {
            "wave_s_warmup": wave_s, "waves": n_waves, "window_waves": n_win,
            "gaps": int(gaps_ms.size), "compiles_in_window": compiles_in_window,
            "monitor": {k: summary[k] for k in ("frames", "events", "anomalies")},
        },
    }


def decode_gaps_ms(step_ns: list, waves: int, steps: int) -> np.ndarray:
    """Every time between consecutive output tokens of one wave, over the
    waves after the first (the window's), in ms; empty where the number of
    step stamps is not ``waves * steps``."""
    if len(step_ns) != waves * steps:
        return np.zeros(0)
    ns = np.asarray(step_ns, np.int64).reshape(waves, steps)
    return (np.diff(ns[1:], axis=1) / 1e6).reshape(-1)


def token_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """At each position, the gap by which the chosen token's reference logit
    lies below the reference's best (0 where the reference agrees)."""
    got = np.take_along_axis(ref_logits, tokens[..., None], axis=-1)[..., 0]
    return ref_logits.max(-1) - got


def gap_stats(gaps: np.ndarray) -> dict:
    """The widest gap (the number compared), and beside it the mean gap and
    the share of positions whose token is not the reference's first choice."""
    if not gaps.size:
        return {"mean_gap": float("inf"), "widest_gap": float("inf"), "off_share": 1.0}
    return {"mean_gap": float(gaps.mean()), "widest_gap": float(gaps.max()),
            "off_share": float((gaps > 0).mean())}


def _check(ctx, c, tr, made, summary, n_waves, B, P, NEW, gaps_ms, prog_seed):
    """Numbers compared, each with its limit (None: not set)."""
    done = [r for r in made if len(r.out) == NEW]
    check = {
        "requests_missing": [B * n_waves - len(done), 0],
        "frames_off": [abs(summary["frames"] - n_waves), 0],
        "events_off": [abs(summary["events"] - 2 * n_waves * (1 + NEW)), 0],
        "gaps_off": [abs(int(gaps_ms.size) - (n_waves - 1) * (NEW - 1)), 0],
    }
    rng = np.random.default_rng(ctx.seed)
    window_waves = list(range(1, n_waves))
    pick = sorted(rng.choice(window_waves, size=min(tr["check_waves"], len(window_waves)),
                             replace=False).tolist())
    ref = _load_reference(ctx.root, c["name"]) if not ctx.smoke else ctx.reference
    weights = ref.init_weights(c, prog_seed)
    _log(ctx, "reference weights drawn")
    served_gaps, control_gaps, dropped, incomplete = [], [], 0, False
    for w in pick:
        wave = made[w * B : (w + 1) * B]
        if len(wave) < B or any(len(r.out) != NEW for r in wave):
            incomplete = True
            continue
        served = np.asarray([r.out for r in wave], np.int64)  # (B, NEW)
        tokens = np.concatenate([np.stack([r.prompt for r in wave]), served[:, :-1]], axis=1)
        lg, drop = ref.logits(c, weights, tokens, P)
        served_gaps.append(token_gaps(lg, served).ravel())
        dropped += drop
        if getattr(ctx, "control", False):
            # The control: the reference one precision step down, read at
            # the same positions; its first choice is judged like a token.
            lo, _ = ref.logits(c, weights, tokens, P, precision="fp8")
            control_gaps.append(token_gaps(lg, lo.argmax(-1)).ravel())
    del weights
    gaps = np.zeros(0) if incomplete else np.concatenate(served_gaps or [np.zeros(0)])
    stats = gap_stats(gaps)
    _log(ctx, f"reference compared {gaps.size} served tokens")
    check["widest_gap"] = [stats["widest_gap"], tr["widest_gap_limit"]]
    ctx.info_check = {"sampled_waves": pick, "served_tokens_compared": int(gaps.size),
                      "ref_dropped_assignments": dropped, **stats}
    if control_gaps:
        # The control in the program's place: the same check, its gaps for the served ones.
        ctl = gap_stats(np.concatenate(control_gaps))
        ctx.info_check["control"] = ctl
        ctx.control_check = dict(check, widest_gap=[ctl["widest_gap"], tr["widest_gap_limit"]])
    return check
