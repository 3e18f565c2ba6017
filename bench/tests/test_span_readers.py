"""CPU tests of the readers of the program's own spans, counters and named
scopes: synthetic registry snapshots, synthetic profiler traces with host
events on the device clock, and one recorded chip step with its op metadata.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import os
import re
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from lib import scopes  # noqa: E402
from lib import trace as T  # noqa: E402


def _reader(name):
    import run as bench_run

    return bench_run._module(os.path.join(BENCH, "metrics", f"{name}.py"), f"reader_{name}")


def _ev(mid, start_ns, end_ns):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_ns * 1000} "
            f"duration_ps: {(end_ns - start_ns) * 1000} }}")


def _meta(mid, name, program=None, stack=None):
    stats = "" if program is None else f"stats {{ metadata_id: 10 uint64_value: {program} }} "
    stats += "" if stack is None else f'stats {{ metadata_id: 11 str_value: "{stack}" }} '
    return f'event_metadata {{ key: {mid} value {{ id: {mid} name: "{name}" {stats}}} }}'


def synthetic_xspace(cast_stack="jit(decode_step)/decode/cast_params/convert_element_type:"):
    """A chip-shaped trace, in ns on one clock.  Device: two executions of
    the decode program (its weight cast, then an MoE op, 30 ns each) and
    one of the prefill program (50 ns of cast).  Host: two decode steps,
    each wait, readback, dispatch and monitor_step, laid as ``serve()``
    runs them; the device idles through both readbacks (25 ns each)."""
    device = " ".join([
        'name: "/device:TPU:0"',
        'lines { name: "XLA Modules" timestamp_ns: 0',
        _ev(1, 0, 60), _ev(1, 100, 160), _ev(2, 200, 250), "}",
        'lines { name: "XLA Ops" timestamp_ns: 0',
        _ev(3, 0, 30), _ev(4, 30, 60), _ev(3, 100, 130), _ev(4, 130, 160), _ev(5, 200, 250), "}",
        _meta(1, "jit_decode_step(777)"), _meta(2, "jit_prefill_step(888)"),
        _meta(3, "%convert.29 = bf16[24,32]", 777, cast_stack),
        _meta(4, "%fusion.1 = bf16[8,1024]", 777, "jit(decode_step)/decode/while/body/moe/dot:"),
        _meta(5, "%convert.3 = bf16[24,32]", 888,
              "jit(prefill_step)/prefill/cast_params/convert_element_type:"),
        'stat_metadata { key: 10 value { id: 10 name: "program_id" } }',
        'stat_metadata { key: 11 value { id: 11 name: "tf_op" } }',
    ])
    names = ["serve/decode_step"] + [f"repro/serve/{p}" for p in
                                     ("wait", "readback", "dispatch", "monitor_step")]
    events = []
    for t0 in (40, 140):
        bounds = (t0, t0 + 20, t0 + 45, t0 + 60, t0 + 70)
        events.append(_ev(20, t0, t0 + 100))
        events += [_ev(21 + i, lo, hi) for i, (lo, hi) in enumerate(zip(bounds, bounds[1:]))]
    host = " ".join(['name: "/host:CPU"', 'lines { name: "python3" timestamp_ns: 0',
                     *events, "}", *(_meta(20 + i, n) for i, n in enumerate(names))])
    return f"planes {{ {device} }} planes {{ {host} }}"


def _write_xplane(tmp_path, text):
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_span_readers_on_synthetic_trace(tmp_path):
    R = SimpleNamespace(trace=T.load(_write_xplane(tmp_path, synthetic_xspace())),
                        window_s=300e-9)
    assert len(scopes.host_spans(R.trace, "serve/decode_step")) == 2
    assert _reader("decode_readback_ms").read(R) == pytest.approx(50 / 2 / 1e6)
    assert _reader("decode_dispatch_ms").read(R) == pytest.approx(30 / 2 / 1e6)
    assert _reader("serve_idle_readback_share").read(R) == pytest.approx(100 * 50 / 300)
    assert scopes.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10


def test_cast_share_on_synthetic_trace(tmp_path):
    log_dir = _write_xplane(tmp_path, synthetic_xspace())
    space = scopes.read_xspace(log_dir)
    # 60 of the decode program's 120 ns; the prefill's cast is not counted
    assert scopes.scope_share(space, "decode_step", "cast_params") == pytest.approx(50.0)
    assert scopes.scope_share(space, "prefill_step", "cast_params") == pytest.approx(100.0)
    reader = _reader("decode_cast_share")
    reader.TRACES = str(tmp_path.parent)
    cell = {"name": tmp_path.name}
    assert reader.read(SimpleNamespace(trace=T.load(log_dir), cell=cell)) == pytest.approx(50.0)


@pytest.mark.parametrize("stack", ["jit(decode_step)/decode/while/body/convert_element_type:",
                                   None])
def test_cast_share_is_none_where_no_op_carries_the_scope(tmp_path, stack):
    """A program without the scope, or loaded from a cache that kept an
    executable without op metadata, reads None and not 0."""
    text = synthetic_xspace(cast_stack=stack)
    if stack is None:
        text = re.sub(r'stats \{ metadata_id: 1[01] [^}]*\} ', "", text)
    space = scopes.read_xspace(_write_xplane(tmp_path, text))
    assert scopes.scope_share(space, "decode_step", "cast_params") is None


RECORDED_STEP = os.path.join(BENCH, "tests", "data", "decode_step_trace.txt")


def test_scope_shares_on_recorded_chip_trace():
    """One decode step of the chat cell (TPU v5 lite) with its op metadata:
    the expert-weight casts under ``cast_params`` take 57.9% of the step."""
    from google.protobuf import text_format

    with open(RECORDED_STEP) as f:
        space = text_format.Parse(f.read(), scopes.xspace_class()())
    share = {sc: scopes.scope_share(space, "decode_step", sc)
             for sc in ("cast_params", "attention", "moe", "lm_head", "decode")}
    assert share["cast_params"] == pytest.approx(57.867, abs=1e-3)
    assert share["moe"] == pytest.approx(18.283, abs=1e-3)
    assert sum(share[sc] for sc in ("cast_params", "attention", "moe", "lm_head")) \
        < share["decode"] < 100


def test_span_readers_are_silent_without_the_programs_spans():
    """A program that records no ``repro/serve`` spans (or no trace) reads None."""
    bare = {"devices": {0: {"ops": [("a", 0, 25)], "modules": []}},
            "host": [("main", "PjitFunction(argmax)", 0, 9)]}
    for name in ("decode_readback_ms", "decode_dispatch_ms", "serve_idle_readback_share",
                 "decode_cast_share"):
        R = SimpleNamespace(trace=bare, window_s=200e-9, cell={"name": "no-such-cell"})
        assert _reader(name).read(R) is None, name
        assert _reader(name).read(SimpleNamespace(trace=None, window_s=200e-9)) is None, name


def test_syncs_per_step_from_registry_snapshots():
    per_step = _reader("decode_syncs_per_step").per_step
    snap = {"repro_serve_host_syncs_total": {"series": {"[]": 1024}},
            "repro_serve_decode_steps_total": {"series": {"[]": 128}}}
    assert per_step(snap) == 8.0
    assert per_step({}) is None
    assert per_step(dict(snap, repro_serve_decode_steps_total={"series": {"[]": 0}})) is None

