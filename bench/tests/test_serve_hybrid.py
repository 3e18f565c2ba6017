"""The hybrid serve cell's counts, configuration check, reference weights
and check, on the CPU.

Counts are checked by hand at the cell's shapes.  The reference must draw
exactly the program's weights, the held expert share included.  A whole
serve run of ``drivers/serve_hybrid.py`` at the program's smoke widths (no
look for a chip) must come out correct, the control (the reference one
precision step down, in the program's place) not, and runs with a fault
planted in the Mamba-2 mixers that ``launch.serve.serve()`` compiles not.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_serve_hybrid.py
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
from drivers import serve_hybrid as D  # noqa: E402
from lib import flops_hybrid as F  # noqa: E402
from lib.compile_events import compile_counters  # noqa: E402
from lib.peaks import PEAKS  # noqa: E402

ARCH = "granite_4_0_h_small_ep8"
with open(os.path.join(BENCH, "configs", "granite-4.0-h-small-ep8.json")) as _f:
    G4H = json.load(_f)
REF = bench._module(os.path.join(BENCH, "configs", "granite-4.0-h-small-ep8.py"), "g4h_ref_t")
TRAFFIC = {"batch": 4, "prompt_len": 32, "max_new": 16, "check_waves": 1}
COMPILES = compile_counters()
# Smoke widths (2 periods of 9 Mamba-2 layers and one attention layer, d
# 64, 4 SSD heads of 32, state 8, 8 router outputs top-2, 1 held, shared
# 64, vocab 512), one window wave of 64 served tokens (CPU).  Seed 23: a
# sound run reads a widest gap of 0.00094 and a mean gap of 0.000048, the
# fp8 control 0.0103 and 0.00143; each planted fault below reads at least
# 0.0097 and 0.00125.  These widths show the check's wiring on a fixed
# seed, not its limits, which come from chip readings at the cell's size
# (PERF.md).
SEED = 23
WIDEST, MEAN = 0.005, 0.0005


# ------------------------------------------------------------------- counts
def test_flops_and_bytes_by_hand_at_the_cells_shapes():
    c = G4H
    mamba = 4096 * (8192 + 8448 + 128) + 4 * 8448 + 8192 * 4096
    assert F.mamba_weights(c) == mamba == 102_269_952
    attn = 2 * 4096 * 32 * 128 + 2 * 4096 * 8 * 128
    assert F.attn_weights(c) == attn == 41_943_040
    expert = 3 * 4096 * 768
    fixed = 4096 * 72 + 3 * 4096 * 1536
    assert F.moe_fixed_weights(c) == fixed
    assert F.routed_per_token(c) == 10 * 9 / 72 == 1.25
    head = 100352 * 4096
    per_token = 9 * mamba + attn + 10 * (fixed + 1.25 * expert) + head
    assert F.n_active(c) == pytest.approx(per_token) and per_token == 1_683_072_000
    # the SSD: 4 x 128 heads x 64 x state 128 per token, 9 Mamba-2 layers;
    # attention: 4 x 32 x 128 per causal pair, one layer
    ssd, pair = 4 * 128 * 64 * 128 * 9, 4 * 32 * 128
    assert F.ssd_token_flops(c) == ssd
    assert F.decode_step_flops(c, 16, 2048) == pytest.approx(
        (2 * per_token + ssd) * 16 + pair * 16 * 2049)
    pre = ((2 * (per_token - head) + ssd) * 16 * 2048 + 2 * head * 16
           + pair * 16 * 2048 * 2049 / 2)
    assert F.prefill_flops(c, 16, 2048) == pytest.approx(pre)
    assert F.prefill_flops(c, 16, 2048) / 1e12 == pytest.approx(85.16, abs=0.01)
    # least decode bytes: 8.18 of 9 held experts reached by 16 x 10 uniform picks
    reached = 9 * (1 - (62 / 72) ** 16)
    assert F.experts_read(c, 16) == pytest.approx(reached) and round(reached, 2) == 8.18
    state = 2 * 9 * 16 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert F.ssm_state_step_bytes(c, 16, 2) == state == 1_222_557_696
    kv = 16 * 2049 * 2 * 8 * 128 * 2
    assert F.kv_cache_bytes(c, 16, 2048, 2) == kv
    vectors = 8448 + 3 * 128 + 8192  # conv bias, dt_bias, A_log, D; the gated norm
    weights = (9 * (mamba + vectors) + attn + 10 * (fixed + reached * expert + 2 * 4096)
               + head + 4096 + 16 * 4096)
    assert F.decode_step_bytes(c, 16, 2048, 2, 2) == pytest.approx(weights * 2 + state + kv)
    assert (weights * 2 + state + kv) / 1e9 == pytest.approx(6.04, abs=0.01)


def test_program_config_matches_the_file_and_the_file_the_catalog():
    from repro import configs

    D._check_program_config(G4H, configs.get_config(ARCH))  # raises on any difference
    published = configs.get_config("granite_4_0_h_small")
    assert (published.n_layers, published.n_experts_held) == (
        G4H["published"]["num_hidden_layers"], G4H["published"]["num_local_experts"])
    assert configs.get_config(ARCH).n_params() == 2_414_692_992
    assert sorted(G4H["reduced"]) == sorted(G4H["published"])
    bad_program = dict(G4H["program"], ssm_state_dtype="bfloat16")
    for asked in ({"num_hidden_layers": 40}, {"mamba_proj_bias": True},
                  {"attention_multiplier": 1 / 128 ** 0.5}, {"program": bad_program}):
        with pytest.raises(SystemExit):  # a deeper cut; biases, a scale, a state dtype
            D._check_program_config(dict(G4H, **asked), configs.get_config(ARCH))


# ---------------------------------------------------------- reference weights
def test_reference_draws_the_programs_weights_held_share_included():
    import jax

    from repro import configs
    from repro.models.common import init_params

    whole = configs.smoke("granite_4_0_h_small")  # 8 experts, all held
    share = dataclasses.replace(whole, moe_n_held=2, moe_held_offset=4)
    mamba = {"in_proj": "in_proj", "conv_w": "conv_w", "conv_b": "conv_b",
             "dt_bias": "dt_bias", "A_log": "A_log", "D": "D", "norm": "ssm_norm",
             "out_proj": "out_proj"}
    attn = {k: k for k in ("wq", "wk", "wv", "wo")}
    moe = {"router": "router", "gate": "moe_gate", "up": "moe_up", "down": "moe_down",
           "s_gate": "shared_gate", "s_up": "shared_up", "s_down": "shared_down"}
    for cfg in (whole, share):
        p = init_params(cfg, jax.random.key(7))
        embed, layers = REF.init_weights(D.smoke_config(cfg), 7)
        np.testing.assert_array_equal(embed, p["embed"])
        assert len(layers) == cfg.n_layers
        for n, ref in enumerate(layers):
            j, i = divmod(n, cfg.period)
            names = dict(attn if cfg.layout[i].mixer == "full" else mamba, **moe)
            for r, q in names.items():
                np.testing.assert_array_equal(ref[r], p["layers"][i][q][j], err_msg=(n, r))
    # the share's experts are the whole layer's experts 4 and 5
    pw = init_params(whole, jax.random.key(7))["layers"][0]["moe_up"]
    ps = init_params(share, jax.random.key(7))["layers"][0]["moe_up"]
    np.testing.assert_array_equal(ps, pw[:, 4:6])


# -------------------------------------------------------------------- check
def _smoke(monkeypatch, **changes):
    from repro import configs

    real = configs.smoke

    def smoke(arch):
        cfg = real(arch)
        return dataclasses.replace(cfg, **{k: v(cfg) for k, v in changes.items()})

    monkeypatch.setattr(configs, "smoke", smoke)
    return smoke(ARCH)


def _run(mcfg, seed=SEED, control=False, widest=WIDEST, mean=MEAN, **traffic):
    traffic = dict(TRAFFIC, widest_gap_limit=widest, mean_gap_limit=mean, **traffic)
    ctx = SimpleNamespace(
        root=ROOT, cell={"name": "smoke-check-hybrid"}, config=D.smoke_config(mcfg),
        traffic=traffic, seed=seed, seconds=0.01, trace=False, t_start=time.perf_counter(),
        compiles=COMPILES, peaks=PEAKS["TPU v5 lite"], smoke=True, reference=REF,
        control=control,
    )
    res = D.run(ctx)
    return bench.is_correct(res["check"]), res["check"], ctx, res["R"]


def _f32(monkeypatch, **more):
    import jax.numpy as jnp

    return _smoke(monkeypatch, compute_dtype=lambda c: jnp.float32,
                  param_dtype=lambda c: jnp.float32, **more)


def test_reference_matches_the_program_exactly_in_float32(monkeypatch):
    """With the program in float32, the chunked SSD prefill and the stepwise
    decode through the state cache, capacity-bounded routing over 8 router
    outputs of which 2 are held, serve exactly the reference's tokens."""
    mcfg = _f32(monkeypatch, moe_n_held=lambda c: 2, moe_capacity_factor=lambda c: 0.5)
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
    assert R.wave_flops == F.wave_flops(ctx.config, 4, 32, 16)  # the hybrid's counts
    assert R.ssm_state_step_bytes == 2 * 18 * 4 * (4 * 32 * 8 * 4 + 3 * 144 * 2)
    assert not hasattr(R, "latent_cache_bytes")
    # Every reader under metrics/ reads this driver's R, so that any of
    # them can be listed on the cell: a number or nothing, never a raise.
    for path in sorted(glob.glob(os.path.join(BENCH, "metrics", "*.py"))):
        name = os.path.basename(path)[:-3]
        value = bench._module(path, f"hybrid_reader_{name}").read(R)
        assert value is None or np.isfinite(value), (name, value)


def _faults():
    """Faults in the program's Mamba-2 mixers: (module, name, replacement)."""
    import jax.numpy as jnp

    from repro.models import mamba as MB
    from repro.models import model as M
    from repro.models.layers import rms_norm

    seq, out = M.mamba2_sequence, MB._mamba2_out

    def no_carry(p, u, cfg, unroll=1):  # decode starts from a zero SSM state
        y, state = seq(p, u, cfg, unroll)
        return y, dict(state, h=jnp.zeros_like(state["h"]))

    def zero_tail(p, u, cfg, unroll=1):  # decode's conv window starts empty
        y, state = seq(p, u, cfg, unroll)
        return y, dict(state, conv=jnp.zeros_like(state["conv"]))

    def no_d(p, y, x, z, cfg, dtype):  # the skip D·x dropped
        return out(dict(p, D=jnp.zeros_like(p["D"])), y, x, z, cfg, dtype)

    def no_gate(p, y, x, z, cfg, dtype):  # rms(y) in the place of rms(y·silu(z))
        y = (y + x * p["D"][:, None]).reshape(y.shape[:-2] + (cfg.d_inner,))
        return rms_norm(y.astype(jnp.float32), p["ssm_norm"], cfg.norm_eps).astype(dtype) @ p["out_proj"]

    return {"state_not_carried": (M, "mamba2_sequence", no_carry),
            "conv_tail_zeroed": (M, "mamba2_sequence", zero_tail),
            "D_dropped": (MB, "_mamba2_out", no_d),
            "gate_dropped": (MB, "_mamba2_out", no_gate)}


@pytest.mark.parametrize("fault", ["state_not_carried", "conv_tail_zeroed", "D_dropped",
                                   "gate_dropped"])
def test_planted_mixer_fault_fails(monkeypatch, fault):
    mcfg = _smoke(monkeypatch)
    monkeypatch.setattr(*_faults()[fault])
    ok, check, _, _ = _run(mcfg)
    assert not ok and check["widest_gap"][0] > WIDEST and check["mean_gap"][0] > MEAN, check


def test_bfloat16_ssm_state_fails(monkeypatch):
    """A state held in bfloat16 where the configuration states float32: on
    a float32 program, which serves the reference's tokens exactly, 384
    served tokens show it (3 of them differ at this seed)."""
    import jax.numpy as jnp

    from repro.models import model as M

    mcfg = _f32(monkeypatch)
    real = M.mamba2_decode

    def bf16_state(p, u, state, cfg):
        def rounded(s):
            return dict(s, h=s["h"].astype(jnp.bfloat16).astype(jnp.float32))

        y, new = real(p, u, rounded(state), cfg)
        return y, rounded(new)

    more = {"batch": 8, "max_new": 48}
    ok, check, ctx, _ = _run(mcfg, widest=1e-5, mean=1e-6, **more)
    assert ok and ctx.info_check["served_tokens_compared"] == 384, check
    monkeypatch.setattr(M, "mamba2_decode", bf16_state)
    ok, check, _, _ = _run(mcfg, widest=1e-5, mean=1e-6, **more)
    assert not ok and check["widest_gap"][0] > 1e-5, check


def test_state_held_in_bfloat16_fails_at_the_cells_precision(monkeypatch):
    """A program that holds its SSM state in bfloat16, at the cell's bfloat16
    compute, where its gaps hardly move: serve() reports the state it holds,
    and the check compares that with the configuration's float32 state."""
    import jax.numpy as jnp

    from repro.models import model as M

    mcfg = _smoke(monkeypatch)
    ok, check, ctx, R = _run(mcfg)
    assert ok and check["state_bytes_off"] == [0, 0], check
    seq, dec = M.mamba2_sequence, M.mamba2_decode

    def bf16_seq(p, u, cfg, unroll=1):
        y, state = seq(p, u, cfg, unroll)
        return y, dict(state, h=state["h"].astype(jnp.bfloat16))

    def bf16_dec(p, u, state, cfg):
        y, new = dec(p, u, dict(state, h=state["h"].astype(jnp.float32)), cfg)
        return y, dict(new, h=new["h"].astype(jnp.bfloat16))

    monkeypatch.setattr(M, "mamba2_sequence", bf16_seq)
    monkeypatch.setattr(M, "mamba2_decode", bf16_dec)
    ok, check, _, _ = _run(mcfg)
    # the state's h at 2 bytes in the place of 4: a quarter of the read and
    # written bytes of R.ssm_state_step_bytes, less the conv tails' share
    h_bytes = 18 * 4 * 4 * 32 * 8 * 2
    assert not ok and check["state_bytes_off"][0] == h_bytes, check
