"""CPU tests of the benchmark's pure parts: trace reductions, FLOP and byte
counts, the manifest, and the plain reference's weights.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import flops as F  # noqa: E402
from lib import stages  # noqa: E402
from lib import trace as T  # noqa: E402
from lib.peaks import peaks_for  # noqa: E402


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


GRANITE = _config("granite-moe-1b-a400m")


# ------------------------------------------------------------ trace reductions
SYNTH = {
    "devices": {0: {
        # a while op (0-20) encloses its body's ops a and b
        "ops": [("w", 0, 20), ("a", 0, 8), ("b", 10, 10), ("a", 30, 5), ("c", 50, 20)],
        "modules": [("jit_decode_step(1)", 0, 20), ("jit_argmax(2)", 30, 5),
                    ("jit_decode_step(1)", 50, 20)],
    }},
    "host": [("main", "PjitFunction(argmax)", 21, 12), ("main", "outer", 0, 100),
             ("main", "sync", 36, 10)],
}


def test_union_merges_overlaps_and_touching():
    assert T.union([(5, 15), (0, 10), (30, 35), (35, 40)]) == [(0, 15), (30, 40)]


def test_busy_and_gaps_on_synthetic_trace():
    dev = SYNTH["devices"][0]
    busy = T.busy_intervals(dev)
    assert busy == [(0, 20), (30, 35), (50, 70)]
    assert T.busy_s(dev) == pytest.approx(45e-9)
    assert T.idle_gaps(busy) == [(20, 30), (35, 50)]
    # the while op is not counted beside the ops of its body
    assert [e[0] for e in T.leaf_ops(dev["ops"])] == ["a", "b", "a", "c"]
    assert T.op_seconds(dev) == pytest.approx({"a": 13e-9, "b": 10e-9, "c": 20e-9})
    assert T.module_stats(dev, "decode_step") == (2, pytest.approx(40e-9))


def test_gap_labels_prefer_the_most_overlapping_then_shorter_event():
    # gap (20, 30): "outer" overlaps 10, PjitFunction 9 -> outer;
    # gap (35, 50): outer 15 vs sync 10 -> outer; without outer -> the others
    assert T.label_gaps([(20, 30), (35, 50), (200, 300)], SYNTH["host"]) == \
        ["outer", "outer", "host idle"]
    assert T.label_gaps([(20, 30), (35, 50)], SYNTH["host"][:1] + SYNTH["host"][2:]) == \
        ["PjitFunction(argmax)", "sync"]
    assert T.label_gaps([(0, 1)], []) == ["host idle"]


def test_breakdown_shape():
    bd = T.breakdown(SYNTH, top=2)
    assert bd["device_ops"] == [["c", pytest.approx(20e-9)], ["a", pytest.approx(13e-9)]]
    assert bd["idle_gaps"] == [["outer", pytest.approx(15e-9)], ["outer", pytest.approx(10e-9)]]


RECORDED = os.path.join(BENCH, "tests", "data", "trace_small.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded chip trace")
def test_reductions_on_recorded_chip_trace():
    with open(RECORDED) as f:
        raw = json.load(f)
    tr = {"devices": {int(k): {kk: [tuple(e) for e in v] for kk, v in d.items()}
                      for k, d in raw["devices"].items()},
          "host": [tuple(e) for e in raw["host"]]}
    dev = tr["devices"][0]
    busy = T.busy_intervals(dev)
    # disjoint, sorted, and never longer than the span of the ops
    assert all(a[1] < b[0] for a, b in zip(busy, busy[1:]))
    span = max(s + d for _, s, d in dev["ops"]) - min(s for _, s, d in dev["ops"])
    assert 0 < T.busy_s(dev) * 1e9 <= span
    # busy time is at most the summed op time and at least the longest op
    total = sum(d for _, _, d in dev["ops"])
    assert max(d for _, _, d in dev["ops"]) <= T.busy_s(dev) * 1e9 <= total
    n, secs = T.module_stats(dev, "prefill_step")  # the sample is the start of a prefill
    assert n > 0 and secs > 0
    bd = T.breakdown(tr)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_decode_gaps_are_every_step_of_the_window():
    import numpy as np

    from drivers.serve import decode_gaps_ms

    # three waves of 4 steps, stamps 1 ms apart within a wave and 50 ms
    # across waves: the first wave is set-up, wave boundaries are no gap
    ns = [w * 50_000_000 + t * 1_000_000 for w in range(3) for t in range(4)]
    gaps = decode_gaps_ms(ns, 3, 4)
    assert gaps.shape == (6,) and np.all(gaps == 1.0)
    assert decode_gaps_ms(ns[:-1], 3, 4).size == 0


def test_stage_deltas():
    def snap(ad_sum, ad_n):
        return {"repro_frame_stage_us": {"series": {
            '[["stage", "ad"]]': [0] * 32 + [ad_sum, ad_n],
            '[["stage", "prov"]]': [0] * 32 + [7, 1]}}}

    assert stages.delta(snap(10, 2), snap(25, 5)) == {"ad": (15, 3), "prov": (0, 0)}
    assert stages.read({}) == {}


# ------------------------------------------------------------ FLOPs and bytes
def test_active_parameters_by_hand():
    # attention 1024*1024 + 2*1024*512 + 1024*1024, router 1024*32,
    # 8 experts of 3*1024*512; 24 layers; tied head 49155*1024.
    per_layer = 3_145_728 + 32_768 + 12_582_912
    assert F.layer_active_weights(GRANITE) == per_layer
    assert F.n_active(GRANITE) == 24 * per_layer + 49155 * 1024
    assert F.n_active(GRANITE) == pytest.approx(429e6, rel=2e-3)


def test_wave_flops_by_hand():
    B, S, T_new = 8, 1024, 128
    body = 2 * 24 * F.layer_active_weights(GRANITE)
    attn = 4 * 16 * 64 * 24  # per (query, key) pair
    pre = B * S * body + B * 2 * 49155 * 1024 + attn * B * S * (S + 1) / 2
    dec = sum(B * (body + 2 * 49155 * 1024) + attn * B * (S + t + 1) for t in range(T_new))
    got = F.wave_flops(GRANITE, B, S, T_new)
    assert got["prefill"] == pytest.approx(pre)
    assert got["decode"] == pytest.approx(dec)
    # ~7.6 TFLOP: the LM head is paid at 8 + 8*128 positions, not at every
    # prompt position (that would add 0.8 TFLOP of logits nobody reads).
    assert got["total"] == pytest.approx(7.6e12, rel=0.01)


def test_decode_floor_by_hand():
    # fp32 weights: per layer active 15,761,408 + two norms of 1024; the
    # 49155-row head; final norm; 8 embedding rows.  bf16 K/V of positions
    # 0..pos over 24 layers, 8 KV heads of 64.
    B, pos = 8, 1024 + 64
    w = 24 * (15_761_408 + 2048) + 49155 * 1024 + 1024 + B * 1024
    kv = 24 * B * (pos + 1) * 8 * 64 * 2
    assert F.decode_step_bytes(GRANITE, B, pos, 4, 2) == w * 4 + kv * 2
    assert F.decode_step_bytes(GRANITE, B, pos, 4, 2) == pytest.approx(2.1e9, rel=0.05)


def test_unknown_device_kind_raises():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


# ------------------------------------------------------------------ manifest
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "e2e": {"name", "unit", "better", "bound", "source"},
    "layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_manifest_keys_names_and_units():
    m = _manifest()
    assert set(m) == KEYS["top"]
    assert m["command"][:2] == ["python3", "bench/run.py"] and m["paths"] == ["bench"]
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    for c in m["configs"]:
        assert set(c) == KEYS["config"] and NAME.match(c["name"])
        assert _line(c["why"]) and _line(c["source"])
        assert os.path.exists(os.path.join(ROOT, c["file"])) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    for w in m["workloads"]:
        assert set(w) == KEYS["workload"] and NAME.match(w["name"])
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4) and _line(w["why"])
        assert os.path.exists(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == KEYS["e2e"] and NAME.match(e["name"])
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        assert e["source"] in ("host_clock", "device_trace") and 0.01 <= e["bound"] <= 0.25
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == KEYS["layer"] and NAME.match(p["name"])
        assert UNIT.match(p["unit"]) and p["better"] in ("lower", "higher") and _line(p["layer"])
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{p['name']}.py"))
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in m[k]]
    assert len(names) == len(set(names))
    assert len(json.dumps(m)) < 64 * 1024


def test_every_cell_reports_what_its_metrics_move():
    import run as bench_run

    m = _manifest()
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e
    for w in m["workloads"]:
        reported = {e["name"] for e in bench_run.metrics_for(m, w, False)}
        assert "setup_s" in reported and len(reported) >= 2
        assert bench_run.metrics_for(m, w, True), w["name"]
    for p in m["per_layer"]:
        assert p["moves"] in e2e
        for cell in p.get("workloads", [w["name"] for w in m["workloads"]]):
            cell_e2e = {e["name"] for e in bench_run.metrics_for(
                m, next(w for w in m["workloads"] if w["name"] == cell), False)}
            assert p["moves"] in cell_e2e, (p["name"], cell)
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    for w in m["workloads"]:
        with open(os.path.join(BENCH, "traffic", f"{w['traffic']}.json")) as f:
            assert json.load(f)["widest_gap_limit"] > 0, w["traffic"]


# --------------------------------------------------------- reference weights
def _reference():
    path = os.path.join(BENCH, "configs", "granite-moe-1b-a400m.py")
    spec = importlib.util.spec_from_file_location("granite_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_reference_draws_the_programs_weights():
    import jax
    import numpy as np

    from repro import configs
    from repro.models.common import init_params
    from drivers.serve import smoke_config

    mcfg = configs.smoke("granite_moe_1b_a400m")
    c = smoke_config(mcfg)
    embed, layers = _reference().init_weights(c, 11)
    p = init_params(mcfg, jax.random.key(11))
    np.testing.assert_array_equal(np.asarray(embed), np.asarray(p["embed"]))
    lay = p["layers"][0]
    for ref_name, name in [("wq", "wq"), ("wk", "wk"), ("wv", "wv"), ("wo", "wo"),
                           ("router", "router"), ("gate", "moe_gate"), ("up", "moe_up"),
                           ("down", "moe_down")]:
        np.testing.assert_array_equal(np.asarray(layers[ref_name]), np.asarray(lay[name]))
    for name in ("ln1", "ln2"):
        assert np.all(np.asarray(lay[name]) == 1.0)
    assert np.all(np.asarray(p["final_ln"]) == 1.0)


def test_capacity_rule():
    ref = _reference()
    assert ref.capacity(GRANITE, 8 * 1024) == math.ceil(8 * 8192 / 32 * 1.25)
    assert ref.capacity(GRANITE, 8) == 8  # decode: C >= batch, never drops
    assert ref.capacity(GRANITE, 4) == 4
