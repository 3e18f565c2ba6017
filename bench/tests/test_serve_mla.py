"""The MLA serve cell's counts, reference weights and check, on the CPU.

Counts are checked by hand at the cell's shapes.  The reference must draw
exactly the program's weights, the held expert share included.  A whole
serve run of ``drivers/serve_mla.py`` at the program's smoke widths (no
look for a chip) must come out correct, the control (the reference one
precision step down, in the program's place) not, and a run with a fault
planted in the decode step that ``launch.serve.serve()`` builds not.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_serve_mla.py
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as bench  # noqa: E402
from drivers import serve_mla as D  # noqa: E402
from lib import flops_mla as F  # noqa: E402
from lib.compile_events import compile_counters  # noqa: E402
from lib.peaks import PEAKS  # noqa: E402

ARCH = "deepseek_v2_lite_ep8"
with open(os.path.join(BENCH, "configs", "deepseek-v2-lite-ep8.json")) as _f:
    DSV2 = json.load(_f)
REF = bench._module(os.path.join(BENCH, "configs", "deepseek-v2-lite-ep8.py"), "dsv2_ref_t")
TRAFFIC = {"batch": 4, "prompt_len": 32, "max_new": 16, "check_waves": 1}
COMPILES = compile_counters()
# Smoke widths (1 dense + 2 MoE layers, d 64, 8 router outputs top-2, 1
# held, shared 64, vocab 512), one window wave of 64 served tokens (CPU).
# Seed 23: a sound run reads a widest gap of 0.020 and a mean gap of
# 0.0004, the fp8 control 0.84 and 0.13.  These widths show the check's
# wiring on a fixed seed, not its limits, which come from chip readings
# at the cell's size (PERF.md).
SEED = 23
WIDEST, MEAN = 0.5, 0.05


# ------------------------------------------------------------------- counts
def test_flops_and_bytes_by_hand_at_the_cells_shapes():
    c = DSV2
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert F.attn_weights(c) == attn == 13_762_560
    assert F.dense_layer_weights(c) == attn + 3 * 2048 * 10944
    fixed = attn + 2048 * 64 + 2 * 3 * 2048 * 1408
    assert F.moe_layer_fixed_weights(c) == fixed
    assert F.routed_per_token(c) == 6 * 8 / 64 == 0.75
    head = 102400 * 2048
    per_token = F.dense_layer_weights(c) + 8 * (fixed + 0.75 * 3 * 2048 * 1408) + head
    assert F.n_active(c) == pytest.approx(per_token)
    # attention: 2·16·(128 + 64 + 128) = 10,240 FLOPs per causal pair, 9 layers
    pair = 10240 * 9
    assert F.decode_step_flops(c, 8, 16384) == pytest.approx(2 * per_token * 8 + pair * 8 * 16385)
    pre = 2 * (per_token - head) * 8 * 16384 + 2 * head * 8 + pair * 8 * 16384 * 16385 / 2
    assert F.prefill_flops(c, 8, 16384) == pytest.approx(pre)
    assert F.prefill_flops(c, 8, 16384) / 1e12 == pytest.approx(199.2, abs=0.1)
    # least decode bytes: 4.36 held experts of 8 reached by 8 x 6 uniform picks
    reached = 8 * (1 - (58 / 64) ** 8)
    assert F.experts_read(c, 8) == pytest.approx(reached) and round(reached, 2) == 4.36
    norms = 2 * 2048 + 512
    weights = (F.dense_layer_weights(c) + norms + 8 * (fixed + norms + reached * 3 * 2048 * 1408)
               + head + 2048 + 8 * 2048)
    latent = 9 * 8 * 16385 * 576 * 2
    assert F.latent_cache_bytes(c, 8, 16384, 2) == latent
    assert F.decode_step_bytes(c, 8, 16384, 2, 2) == pytest.approx(weights * 2 + latent)
    assert weights * 2 / 1e9 == pytest.approx(1.68, abs=0.01)


def test_program_config_matches_the_file_and_the_file_the_catalog():
    from repro import configs

    D._check_program_config(DSV2, configs.get_config(ARCH))  # raises on any difference
    published = configs.get_config("deepseek_v2_lite")
    assert (published.n_layers, published.n_experts_held) == (
        DSV2["published"]["num_hidden_layers"], DSV2["published"]["n_routed_experts"])
    assert configs.get_config(ARCH).n_params() == 1_303_685_632
    for asked in ({"num_hidden_layers": 27}, {"routed_scaling_factor": 2.0}):
        with pytest.raises(SystemExit):  # a deeper cut; a routed scale other than 1
            D._check_program_config(dict(DSV2, **asked), configs.get_config(ARCH))


# ---------------------------------------------------------- reference weights
def test_reference_draws_the_programs_weights_held_share_included():
    import jax

    from repro import configs
    from repro.models.common import init_params

    whole = configs.smoke("deepseek_v2_lite")  # 8 experts, all held
    share = dataclasses.replace(whole, moe_n_held=2, moe_held_offset=4)
    for cfg in (whole, share):
        p = init_params(cfg, jax.random.key(7))
        embed, head, lead, layers = REF.init_weights(D.smoke_config(cfg), 7)
        np.testing.assert_array_equal(embed, p["embed"])
        np.testing.assert_array_equal(head, p["unembed"])
        for ref, prog, names in (
            (lead, p["lead"][0], {"gate": "w_gate", "up": "w_up", "down": "w_down"}),
            (layers, p["layers"][0], {"router": "router", "gate": "moe_gate",
                                      "up": "moe_up", "down": "moe_down",
                                      "s_gate": "shared_gate", "s_up": "shared_up",
                                      "s_down": "shared_down"}),
        ):
            names.update(wq="wq", wkv_a="wdkv", wuk="wuk", wuv="wuv", wo="wo")
            for r, q in names.items():
                np.testing.assert_array_equal(ref[r], prog[q], err_msg=r)
    # the share's experts are the whole layer's experts 4 and 5
    pw = init_params(whole, jax.random.key(7))["layers"][0]["moe_up"]
    ps = init_params(share, jax.random.key(7))["layers"][0]["moe_up"]
    np.testing.assert_array_equal(ps, pw[:, 4:6])


def test_reference_blocked_attention_equals_plain_causal_attention(monkeypatch):
    """Blocks of queries over keys that end with their segment give the
    plain causal softmax over all keys (blocks of 4, segments of 2 blocks,
    21 positions: padded queries, a short last segment)."""
    import jax
    import jax.numpy as jnp

    from repro import configs

    c = D.smoke_config(configs.smoke(ARCH))
    w = {k: v[0].astype(jnp.float32) for k, v in REF.init_weights(c, 3)[3].items()}
    x = jax.random.normal(jax.random.key(1), (21, c["hidden_size"]))
    monkeypatch.setattr(REF, "Q_BLOCK", 4)
    monkeypatch.setattr(REF, "SEGMENT", 2)
    got = REF._attention(c, "f32", x, w)
    monkeypatch.setattr(REF, "Q_BLOCK", 32)  # one block over every key
    want = REF._attention(c, "f32", x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


# -------------------------------------------------------------------- check
def _smoke(monkeypatch, **changes):
    from repro import configs

    real = configs.smoke

    def smoke(arch):
        cfg = real(arch)
        return dataclasses.replace(cfg, **{k: v(cfg) for k, v in changes.items()})

    monkeypatch.setattr(configs, "smoke", smoke)
    return smoke(ARCH)


def _run(mcfg, seed=SEED, control=False, widest=WIDEST, mean=MEAN):
    traffic = dict(TRAFFIC, widest_gap_limit=widest, mean_gap_limit=mean)
    ctx = SimpleNamespace(
        root=ROOT, cell={"name": "smoke-check-mla"}, config=D.smoke_config(mcfg),
        traffic=traffic, seed=seed, seconds=0.01, trace=False, t_start=time.perf_counter(),
        compiles=COMPILES, peaks=PEAKS["TPU v5 lite"], smoke=True, reference=REF,
        control=control,
    )
    res = D.run(ctx)
    return bench.is_correct(res["check"]), res["check"], ctx, res["R"]


def test_reference_matches_the_program_exactly_in_float32(monkeypatch):
    """With the program in float32, prefill + absorbed decode through the
    latent cache, capacity-bounded routing over 8 router outputs of which
    2 are held, serve exactly the reference's tokens."""
    import jax.numpy as jnp

    mcfg = _smoke(monkeypatch, compute_dtype=lambda c: jnp.float32,
                  param_dtype=lambda c: jnp.float32, moe_n_held=lambda c: 2,
                  moe_capacity_factor=lambda c: 0.5)
    ok, check, ctx, _ = _run(mcfg, seed=2**31 + 5, widest=1e-5, mean=1e-6)
    info = ctx.info_check
    assert ok, (check, info)
    assert info["ref_dropped_assignments"] > 0  # the capacity rule was exercised
    assert info["off_share"] == 0 and info["served_tokens_compared"] == 64


def test_sound_run_is_correct_and_control_is_not(monkeypatch):
    ok, check, ctx, R = _run(_smoke(monkeypatch), control=True)
    assert ok, (check, ctx.info_check)
    assert not bench.is_correct(ctx.control_check), ctx.info_check
    assert ctx.control_check["widest_gap"][0] > WIDEST
    assert ctx.control_check["mean_gap"][0] > MEAN
    # Every reader under metrics/ reads this driver's R, so that any of
    # them can be listed on the cell: a number or nothing, never a raise.
    for path in sorted(glob.glob(os.path.join(BENCH, "metrics", "*.py"))):
        name = os.path.basename(path)[:-3]
        value = bench._module(path, f"mla_reader_{name}").read(R)
        assert value is None or np.isfinite(value), (name, value)


def test_state_returned_unchanged_fails(monkeypatch):
    """A decode step that returns the cache it was given (no latent written,
    no position advanced) is caught."""
    from repro.launch import serve as S

    mcfg = _smoke(monkeypatch)
    real = S.build_decode_step

    def build(cfg, ctx, opts):
        step = real(cfg, ctx, opts)

        def broken(params, cache, tokens):
            logits, _new = step(params, cache, tokens)
            return logits, cache

        return broken

    monkeypatch.setattr(S, "build_decode_step", build)
    ok, check, _, _ = _run(mcfg)
    assert not ok and check["widest_gap"][0] > WIDEST and check["mean_gap"][0] > MEAN, check
