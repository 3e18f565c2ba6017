"""The phase clock in the serve loop and the monitor, the Tracer's spans on
the profiler's clock, and the programs' name scopes, at smoke widths on
the CPU."""
from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys

import pytest

WAVES, BATCH, PROMPT, NEW = 2, 2, 8, 3
STEPS = WAVES * NEW
PHASE_CALLS = {"prefill": WAVES, "wait": STEPS, "readback": STEPS, "dispatch": STEPS,
               "monitor_step": STEPS, "drain": WAVES, "ingest": WAVES, "finish": 1}


def _series(snapshot, family):
    """{label values: value} of one family of a registry snapshot."""
    fam = snapshot.get(family, {"series": {}})
    return {tuple(v for _k, v in json.loads(key)): vec for key, vec in fam["series"].items()}


def _count_delta(before, after, family, key):
    """Observations (histogram) or value (counter) added between snapshots."""
    def count(snap):
        vec = _series(snap, family).get(key, 0)
        return vec[-1] if isinstance(vec, list) else vec
    return count(after) - count(before)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One smoke serve() under the profiler: the registry before and after,
    and the host events the trace holds."""
    import jax
    from jax.profiler import ProfileData

    from repro.launch.serve import serve
    from repro.telemetry.registry import get_registry

    log_dir = str(tmp_path_factory.mktemp("profile"))
    before = get_registry().snapshot()
    jax.profiler.start_trace(log_dir)
    try:
        out = serve(smoke=True, n_requests=WAVES * BATCH, batch=BATCH, prompt_len=PROMPT,
                    max_new=NEW, seed=3)
    finally:
        jax.profiler.stop_trace()
    after = get_registry().snapshot()
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)[0]
    events = [(e.name, e.start_ns, e.end_ns)
              for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(("repro/", "serve/"))]
    return out, before, after, events


@pytest.mark.parametrize("phase", sorted(PHASE_CALLS))
def test_serve_phase_observed_once_per_call(served, phase):
    _out, before, after, _events = served
    assert _count_delta(before, after, "repro_serve_phase_us", (phase,)) == PHASE_CALLS[phase]


@pytest.mark.parametrize("family, per_step", [
    ("repro_serve_decode_steps_total", 1),
    ("repro_serve_tokens_total", BATCH),
    ("repro_serve_host_syncs_total", 1),  # one transfer of the whole batch per step
])
def test_serve_counters(served, family, per_step):
    out, before, after, _events = served
    assert _count_delta(before, after, family, ()) == STEPS * per_step
    assert out["tokens"] == STEPS * BATCH


@pytest.mark.parametrize("outer, phases, n", [
    ("serve/decode_step", ("dispatch", "wait", "readback", "monitor_step"), STEPS),
    ("serve/prefill", ("prefill",), WAVES),
])
def test_serve_phases_nest_in_tracer_spans_on_the_profiler_clock(served, outer, phases, n):
    """Each Tracer span (a Chimbuko event) encloses its phases, in order,
    as host events of the profiler's trace."""
    events = served[3]
    spans = sorted((s, e) for name, s, e in events if name == outer)
    assert len(spans) == n
    inner = {p: sorted((s, e) for name, s, e in events if name == f"repro/serve/{p}")
             for p in phases}
    for i, (s, e) in enumerate(spans):
        ends = [inner[p][i] for p in phases]
        assert all(s <= ps and pe <= e for ps, pe in ends), (outer, i)
        assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:])), (outer, i)


def _lowered_names(program, params_of):
    """The op names of ``program`` lowered at smoke widths on
    ``params_of(cfg, params)``."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.launch.steps import (StepOptions, build_decode_step, build_prefill_step,
                                    make_shard_ctx)
    from repro.models.common import init_params

    cfg = configs.smoke("granite_moe_1b_a400m")
    opts = StepOptions()
    ctx = make_shard_ctx(cfg, None, BATCH, opts)
    params = jax.eval_shape(lambda: params_of(cfg, init_params(cfg, jax.random.key(0))))
    prefill = build_prefill_step(cfg, ctx, opts, max_seq=PROMPT + NEW)
    prompts = {"tokens": jax.ShapeDtypeStruct((BATCH, PROMPT), jnp.int32)}
    if program == "prefill":
        lowered = jax.jit(prefill).lower(params, prompts)
    else:
        cache = jax.eval_shape(prefill, params, prompts)[1]
        lowered = jax.jit(build_decode_step(cfg, ctx, opts)).lower(
            params, cache, jax.ShapeDtypeStruct((BATCH, 1), jnp.int32))
    return re.findall(r'op_name="([^"]+)"', lowered.as_text(dialect="hlo", debug_info=True))


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_programs_carry_stable_name_scopes(program):
    op_names = _lowered_names(program, lambda cfg, p: p)
    scopes = {part for name in op_names for part in name.split("/")}
    for scope in (program, "embed", "cast_params", "attention", "kv_update", "moe", "lm_head"):
        assert scope in scopes, scope
    # the weight cast is the convert under cast_params
    assert any(name.endswith("cast_params/convert_element_type") for name in op_names)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_programs_on_compute_params_cast_nothing(program):
    """On compute_params' copy the steps' weight casts are no-ops: no
    convert under ``cast_params`` (on the float32 masters there is one,
    as the test above asserts)."""
    from repro.models.model import compute_params

    names = _lowered_names(program, compute_params)
    assert names and not any("cast_params/convert_element_type" in n for n in names)


def test_serve_compute_param_bytes(served):
    """The gauge holds the bytes of serve()'s compute-dtype copy: every
    layer weight and the embedding, in bfloat16."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.models.common import init_params

    cfg = configs.smoke("granite_moe_1b_a400m")
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    cast = jax.tree.leaves(shapes["layers"]) + [shapes["embed"]]
    want = sum(s.size for s in cast) * jnp.dtype(cfg.compute_dtype).itemsize
    _out, _before, after, _events = served
    assert _series(after, "repro_serve_compute_param_bytes")[()] == want


def test_serve_compute_param_bytes_zero_when_params_in_compute_dtype(monkeypatch):
    import dataclasses

    import jax.numpy as jnp

    from repro import configs
    from repro.launch.serve import serve
    from repro.telemetry.registry import get_registry

    smoke = configs.smoke
    monkeypatch.setattr(configs, "smoke",
                        lambda arch: dataclasses.replace(smoke(arch), compute_dtype=jnp.float32))
    serve(smoke=True, n_requests=1, batch=1, prompt_len=4, max_new=1)
    assert _series(get_registry().snapshot(), "repro_serve_compute_param_bytes")[()] == 0


@pytest.fixture(scope="module")
def ingested():
    """Six frames through a monitor with self-tracing on: the stage
    histograms before and after, and the self-trace spans recorded."""
    from repro.core.sim import WorkloadGenerator, nwchem_like
    from repro.telemetry.registry import get_registry
    from repro.telemetry.selftrace import get_self_tracer
    from repro.trace.monitor import ChimbukoMonitor

    gen = WorkloadGenerator(nwchem_like(anomaly_rate=0.05), n_ranks=2, seed=5)
    tracer = get_self_tracer()
    was_on = tracer.enabled
    monitor = ChimbukoMonitor(num_funcs=len(gen.registry), registry=gen.registry,
                              min_samples=4, self_trace=True)
    try:
        tracer.drain()
        before = get_registry().snapshot()
        for step in range(3):
            for rank in range(2):
                monitor.ingest(gen.frame(rank, step)[0])
        after = get_registry().snapshot()
        names = [name for name, *_ in tracer.drain()]
    finally:
        monitor.close()
        tracer.set_enabled(was_on)
    return before, after, names


STAGES = ("callstack", "ps_sync", "ad", "reduce", "ps", "prov", "write", "publish")


@pytest.mark.parametrize("stage", STAGES)
def test_monitor_stage_observed_once_per_frame(ingested, stage):
    before, after, names = ingested
    assert _count_delta(before, after, "repro_frame_stage_us", (stage,)) == 6
    assert names.count(f"ingest:{stage}") == 6


def test_monitor_stages_are_the_whole_set(ingested):
    _before, after, names = ingested
    assert set(_series(after, "repro_frame_stage_us")) == {(s,) for s in STAGES}
    assert {n for n in names if n.startswith("ingest:")} == {f"ingest:{s}" for s in STAGES}


def test_phase_clock_does_not_load_jax():
    """Shard workers import telemetry and the monitor's modules without JAX."""
    code = ("import sys; import repro.telemetry.phases, repro.core.ad, repro.trace.tracer, "
            "repro.trace.monitor; assert 'jax' not in sys.modules")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(p for p in sys.path if p))
    subprocess.run([sys.executable, "-c", code], check=True, env=env, timeout=120)
