"""Serve cells of an MLA + shared-expert MoE configuration (DeepSeek-V2 keys).

The window, the set-up and the observation are ``drivers/serve.py``'s:
one warm-up ``serve()`` wave at the cell's shapes, then a monitored call
whose window runs from the return of its first wave's
``ChimbukoMonitor.ingest`` to its return.  What differs is the
configuration check, the counts (``lib/flops_mla.py``) and the check
after the window, which compares the mean gap as well as the widest:
each served token's gap below the reference's best logit, limits
``widest_gap_limit`` and ``mean_gap_limit`` in the traffic file.
"""
from __future__ import annotations

import importlib.util
import os
import shutil
import time
from types import SimpleNamespace

import numpy as np


def _serve_driver():
    """``drivers/serve.py``, for its gap statistics, peaks and check."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve.py")
    spec = importlib.util.spec_from_file_location("bench_driver_serve_for_mla", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SERVE = _serve_driver()


def _check_program_config(c: dict, mcfg) -> None:
    """The program's config must be the one the file states, and the file
    must ask only for what the program implements."""
    import jax.numpy as jnp

    rs, p = c["rope_scaling"], c["program"]
    want = {
        "d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
        "n_layers": c["num_hidden_layers"], "first_k_dense": c["first_k_dense_replace"],
        "n_heads": c["num_attention_heads"], "q_lora_rank": c["q_lora_rank"] or 0,
        "kv_lora_rank": c["kv_lora_rank"], "qk_nope_dim": c["qk_nope_head_dim"],
        "qk_rope_dim": c["qk_rope_head_dim"], "v_head_dim": c["v_head_dim"],
        "moe_experts": p["router_experts"], "n_experts_held": c["n_routed_experts"],
        "moe_held_offset": p["held_offset"], "moe_topk": c["num_experts_per_tok"],
        "moe_dff": c["moe_intermediate_size"],
        "moe_shared_dff": c["n_shared_experts"] * c["moe_intermediate_size"],
        "moe_norm_topk": c["norm_topk_prob"],
        "moe_capacity_factor": p["moe_capacity_factor"],
        "vocab": c["vocab_size"], "vocab_padded": p["embedding_rows"],
        "rope_theta": c["rope_theta"], "yarn_factor": rs["factor"],
        "yarn_original_max_pos": rs["original_max_position_embeddings"],
        "yarn_beta_fast": rs["beta_fast"], "yarn_beta_slow": rs["beta_slow"],
        "yarn_mscale": rs["mscale"], "yarn_mscale_all_dim": rs["mscale_all_dim"],
        "norm_eps": c["rms_norm_eps"], "tie_embeddings": c["tie_word_embeddings"],
        "param_dtype": jnp.dtype(p["param_dtype"]),
        "compute_dtype": jnp.dtype(p["compute_dtype"]),
    }
    got = {k: getattr(mcfg, k) for k in want}
    got["param_dtype"] = jnp.dtype(got["param_dtype"])
    got["compute_dtype"] = jnp.dtype(got["compute_dtype"])
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    fixed = {"hidden_act": "silu", "scoring_func": "softmax", "topk_method": "greedy",
             "n_group": 1, "topk_group": 1, "moe_layer_freq": 1, "attention_bias": False,
             "routed_scaling_factor": 1, "model_type": "deepseek_v2"}
    bad.update({k: (v, c[k]) for k, v in fixed.items() if c[k] != v})
    if rs["type"] != "yarn":
        bad["rope_scaling.type"] = ("yarn", rs["type"])
    if bad:
        raise SystemExit(f"program config differs from the configuration file: {bad}")


def smoke_config(mcfg) -> dict:
    """A configuration dict for the program's smoke widths (CPU tests)."""
    import jax.numpy as jnp

    return {
        "name": "smoke", "hidden_size": mcfg.d_model, "intermediate_size": mcfg.d_ff,
        "num_hidden_layers": mcfg.n_layers, "first_k_dense_replace": mcfg.first_k_dense,
        "num_attention_heads": mcfg.n_heads, "q_lora_rank": None,
        "kv_lora_rank": mcfg.kv_lora_rank, "qk_nope_head_dim": mcfg.qk_nope_dim,
        "qk_rope_head_dim": mcfg.qk_rope_dim, "v_head_dim": mcfg.v_head_dim,
        "n_routed_experts": mcfg.n_experts_held, "num_experts_per_tok": mcfg.moe_topk,
        "moe_intermediate_size": mcfg.moe_dff,
        "n_shared_experts": mcfg.moe_shared_dff // mcfg.moe_dff,
        "norm_topk_prob": mcfg.moe_norm_topk, "routed_scaling_factor": 1,
        "vocab_size": mcfg.vocab, "rope_theta": mcfg.rope_theta,
        "rope_scaling": {"type": "yarn", "factor": mcfg.yarn_factor,
                         "original_max_position_embeddings": mcfg.yarn_original_max_pos,
                         "beta_fast": mcfg.yarn_beta_fast, "beta_slow": mcfg.yarn_beta_slow,
                         "mscale": mcfg.yarn_mscale, "mscale_all_dim": mcfg.yarn_mscale_all_dim},
        "rms_norm_eps": mcfg.norm_eps, "tie_word_embeddings": mcfg.tie_embeddings,
        "program": {
            "arch": mcfg.name, "param_dtype": jnp.dtype(mcfg.param_dtype).name,
            "compute_dtype": jnp.dtype(mcfg.compute_dtype).name,
            "moe_capacity_factor": mcfg.moe_capacity_factor,
            "router_experts": mcfg.moe_experts, "n_held": mcfg.n_experts_held,
            "held_offset": mcfg.moe_held_offset, "embedding_rows": mcfg.vocab_padded,
        },
    }


def run(ctx) -> dict:
    import jax

    from repro import configs
    from repro.launch import serve as S
    from repro.telemetry import registry as telemetry
    from repro.trace.monitor import ChimbukoMonitor

    from lib import flops_mla as F
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
    SERVE._log(ctx, f"warm-up wave {wave_s:.3f}s (serve compile_s {warm['compile_s']:.2f}); "
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
    SERVE._log(ctx, f"window closed: {window_s:.3f}s, set-up {setup_s:.3f}s")

    # ---- end-to-end metrics (host clock, the benchmark's own stamps)
    gaps_ms = SERVE.decode_gaps_ms(mon.step_ns, n_waves, NEW)
    metrics = {
        "serve_req_s": (out["requests"] - B) / window_s,
        "decode_gap_p95_ms": float(np.percentile(gaps_ms, 95)) if gaps_ms.size else None,
        "setup_s": setup_s,
    }
    peak = SERVE._peak_bytes()

    # ---- per-layer context for the metric readers
    param_bytes = np.dtype(c["program"]["param_dtype"]).itemsize
    cache_bytes = 2 if c["program"]["compute_dtype"] == "bfloat16" else np.dtype(c["program"]["compute_dtype"]).itemsize
    trace = T.load(trace_dir) if ctx.trace else None
    R = SimpleNamespace(
        config=c, traffic=tr, cell=ctx.cell, window_s=window_s, trace=trace,
        trace_dir=trace_dir, peaks=ctx.peaks, stage_delta=stage_delta,
        waves_in_window=n_win, gaps_ms=gaps_ms,
        wave_flops=F.wave_flops(c, B, P, NEW),
        decode_step_flops=[F.decode_step_flops(c, B, P + t) for t in range(NEW)],
        decode_step_bytes=[F.decode_step_bytes(c, B, P + t, param_bytes, cache_bytes)
                           for t in range(NEW)],
        latent_cache_bytes=[F.latent_cache_bytes(c, B, P + t, cache_bytes) for t in range(NEW)],
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


def _check(ctx, c, tr, made, summary, n_waves, B, P, NEW, gaps_ms, prog_seed):
    """``drivers/serve.py``'s check, with the mean gap compared beside the
    widest, for the program and for the control alike."""
    check = SERVE._check(ctx, c, tr, made, summary, n_waves, B, P, NEW, gaps_ms, prog_seed)
    check["mean_gap"] = [ctx.info_check["mean_gap"], tr["mean_gap_limit"]]
    if "control" in ctx.info_check:
        ctx.control_check["mean_gap"] = [ctx.info_check["control"]["mean_gap"],
                                         tr["mean_gap_limit"]]
    return check
