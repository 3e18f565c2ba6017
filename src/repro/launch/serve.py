"""Serving driver: batched prefill + decode with Chimbuko monitoring.

Continuous-batching-lite: a request queue fills decode slots; each decode
step advances every active slot one token; finished requests free slots.
Per-phase tracing (prefill/decode/detokenize) streams to the monitor; decode
step-time anomalies (e.g. a slow host) surface exactly like the paper's
workflow delays.

Usage:
  python -m repro.launch.serve --requests 8 --max-new 16          # smoke widths
  python -m repro.launch.serve --full --batch 8 --prompt-len 512   # published widths
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import StepOptions, build_decode_step, build_prefill_step, make_shard_ctx
from repro.models import model as M
from repro.models.common import init_params
from repro.telemetry import registry as telemetry
from repro.telemetry.phases import PhaseClock
from repro.trace.monitor import ChimbukoMonitor
from repro.trace.tracer import Tracer, now_us

# The loop's phases, each a ``repro_serve_phase_us`` series and a
# ``repro/serve/<phase>`` profiler event: per wave ``prefill`` (its dispatch
# and argmax), then per decode step ``dispatch`` (step n and its argmax),
# ``wait`` (step n-1 finishing while step n is queued behind it),
# ``readback`` (step n-1's tokens, one transfer) and ``monitor_step``; per
# wave ``drain`` and ``ingest``; once ``finish``.
SERVE_PHASES = ("prefill", "dispatch", "wait", "readback", "monitor_step", "drain",
                "ingest", "finish")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


def serve(
    arch: str = "granite_moe_1b_a400m",
    smoke: bool = True,
    n_requests: int = 8,
    batch: int = 4,
    prompt_len: int = 16,
    max_new: int = 16,
    seed: int = 0,
    monitor: Optional[ChimbukoMonitor] = None,
) -> Dict:
    cfg = configs.smoke(arch) if smoke else configs.get_config(arch)
    assert not cfg.is_encoder, "decode serving needs a decoder arch"
    opts = StepOptions()
    ctx = make_shard_ctx(cfg, None, batch, opts)
    max_seq = prompt_len + max_new
    # The masters, kept resident as a deployment keeps them.  The copy waits
    # for them: dispatched at once, its buffers would be allocated while the
    # initialisation's temporaries are still held, and raise the peak.
    params = jax.block_until_ready(init_params(cfg, jax.random.key(seed)))
    # The weights never change here, so the steps run on their compute-dtype
    # copy, made once, and cast nothing themselves.
    cparams = M.compute_params(cfg, params)
    copied = sum(c.nbytes for c, p in zip(jax.tree.leaves(cparams), jax.tree.leaves(params))
                 if c.dtype != p.dtype)
    reg = telemetry.get_registry()
    reg.gauge("repro_serve_compute_param_bytes",
              "Bytes of the compute-dtype weight copy serve() made.").set(copied)
    # Both steps compile before the clock starts: tok_per_s excludes it.
    t_compile = time.perf_counter()
    prefill = build_prefill_step(cfg, ctx, opts, max_seq=max_seq)
    prompt_spec = {"tokens": jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)}
    prefill_fn = jax.jit(prefill).lower(cparams, prompt_spec).compile()
    cache_spec = jax.eval_shape(prefill, cparams, prompt_spec)[1]
    cache_bytes = reg.gauge("repro_serve_cache_bytes",
                            "Bytes of the decode cache serve() holds, by kind.", ("kind",))
    for kind, nbytes in M.cache_bytes_by_kind(cache_spec).items():
        cache_bytes.labels(kind=kind).set(nbytes)
    decode_fn = jax.jit(build_decode_step(cfg, ctx, opts), donate_argnums=(1,)).lower(
        cparams, cache_spec, jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    ).compile()
    compile_s = time.perf_counter() - t_compile

    own_monitor = monitor is None
    monitor = monitor or ChimbukoMonitor(num_funcs=16, min_samples=8)
    tracer = Tracer(monitor.registry, rank=0)
    clock = PhaseClock("serve", "repro_serve_phase_us",
                       "serve() loop phase latency in microseconds.", "phase", SERVE_PHASES)
    m_steps = reg.counter("repro_serve_decode_steps_total", "Decode steps serve() ran.")
    m_tokens = reg.counter("repro_serve_tokens_total", "Tokens serve() read back.")
    m_syncs = reg.counter("repro_serve_host_syncs_total",
                          "Device-to-host reads serve()'s loop issued.")

    rng = np.random.default_rng(seed)
    pending = [
        Request(i, rng.integers(0, cfg.vocab, prompt_len).astype(np.int32), max_new)
        for i in range(n_requests)
    ]
    finished: List[Request] = []
    step = 0
    next_tok = None
    t_start = time.perf_counter()
    tokens_out = 0
    while pending:
        wave, pending = pending[:batch], pending[batch:]
        with tracer.span("serve/prefill"), clock.phase("prefill"):
            prompts = np.stack([r.prompt for r in wave])
            if len(wave) < batch:  # pad the wave to the compiled batch
                pad = np.tile(prompts[-1:], (batch - len(wave), 1))
                prompts = np.concatenate([prompts, pad])
            logits, cache = prefill_fn(cparams, {"tokens": jnp.asarray(prompts)})
            next_tok = jnp.argmax(logits[:, -1], axis=-1)
            next_tok.copy_to_host_async()
        for t in range(max_new):
            with tracer.span("serve/decode_step") as t_entry_us:
                # Step t is queued before step t-1's tokens are read, so the
                # device runs it while the host reads: a wave runs a fixed
                # max_new steps, and no stop condition needs the tokens first.
                with clock.phase("dispatch"):
                    logits, cache = decode_fn(cparams, cache, next_tok[:, None].astype(jnp.int32))
                    prev_tok, next_tok = next_tok, jnp.argmax(logits[:, 0], axis=-1)
                    next_tok.copy_to_host_async()
                with clock.phase("wait"):
                    jax.block_until_ready(prev_tok)
                with clock.phase("readback"):
                    for r, tok in zip(wave, np.asarray(prev_tok).tolist()):
                        r.out.append(tok)
                    m_syncs.inc()
                # The step time the straggler detector judges: this span so far.
                step_s = (now_us() - t_entry_us) / 1e6
                with clock.phase("monitor_step"):
                    monitor.record_step_times(step, {0: step_s})
            tokens_out += len(wave)
            m_steps.inc()
            m_tokens.inc(len(wave))
            step += 1
        finished.extend(wave)
        with clock.phase("drain"):
            frame = tracer.drain(step)
        with clock.phase("ingest"):
            monitor.ingest(frame)
    with clock.phase("finish"):
        jax.block_until_ready(next_tok)
    dt = time.perf_counter() - t_start
    out = {
        "requests": len(finished),
        "tokens": tokens_out,
        "compile_s": compile_s,
        "serve_s": dt,
        "tok_per_s": tokens_out / dt if dt > 0 else 0.0,
        "monitor": monitor.summary(),
        "samples": [r.out[:8] for r in finished[:3]],
    }
    if own_monitor:
        monitor.close()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_moe_1b_a400m")
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="published widths instead of the smoke config")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()
    out = serve(
        arch=args.arch, smoke=args.smoke, n_requests=args.requests, batch=args.batch,
        prompt_len=args.prompt_len, max_new=args.max_new,
    )
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
