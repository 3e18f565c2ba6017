"""The serve cells' check comes out false when the timed path is broken.

Each test drives a whole serve run at the program's smoke widths on the
CPU (no look for a chip), with one fault planted in the decode step that
``launch.serve.serve()`` builds, and sees ``correct`` come out false.  A
sound run, and the control (the plain reference one precision step down,
in the program's place), are checked against the same limit.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_check.py
"""
from __future__ import annotations

import os
import sys
import time
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as bench  # noqa: E402
from drivers import serve as D  # noqa: E402
from lib.compile_events import compile_counters  # noqa: E402
from lib.peaks import PEAKS  # noqa: E402

ARCH = "granite_moe_1b_a400m"
TRAFFIC = {"batch": 4, "prompt_len": 32, "max_new": 16, "check_waves": 1}
COMPILES = compile_counters()
# Smoke widths (2 layers, d 64, 8 experts top-2, vocab 512), dropless
# routing, one window wave of 64 served tokens (CPU).  Seed 23: a sound run
# reads a widest gap of 0.039 and the fp8 control 2.72 (mean gap 0.0007 vs
# 0.115).  At two layers, top-2 routing and 64 tokens one bf16 router flip
# moves a token's logits by the control's own margin, so these widths show
# the check's wiring on a fixed seed, not its limit.  The cell's limit
# comes from chip readings at the cell's size (PERF.md).
SEED = 23
LIMIT = 0.5


def _smoke(monkeypatch, **changes):
    import dataclasses

    from repro import configs

    real = configs.smoke

    def smoke(arch):
        cfg = real(arch)
        return dataclasses.replace(cfg, **{k: v(cfg) for k, v in changes.items()})

    monkeypatch.setattr(configs, "smoke", smoke)
    return smoke(ARCH)


def _run(mcfg, seed=SEED, control=False, limit=LIMIT):
    ref = bench._module(os.path.join(BENCH, "configs", "granite-moe-1b-a400m.py"), "granite_ref_t")
    traffic = dict(TRAFFIC, widest_gap_limit=limit)
    ctx = SimpleNamespace(
        root=ROOT, cell={"name": "smoke-check"}, config=D.smoke_config(mcfg),
        traffic=traffic, seed=seed, seconds=0.01, trace=False, t_start=time.perf_counter(),
        compiles=COMPILES, peaks=PEAKS["TPU v5 lite"], smoke=True, reference=ref,
        control=control,
    )
    res = D.run(ctx)
    return bench.is_correct(res["check"]), res["check"], ctx


@pytest.fixture
def dropless(monkeypatch):
    return _smoke(monkeypatch, moe_capacity_factor=lambda c: c.moe_experts / c.moe_topk)


def test_reference_matches_the_program_exactly_in_float32(monkeypatch):
    """With the program computing in float32, prefill + cached decode with
    capacity-bounded routing serve exactly the reference's tokens."""
    import jax.numpy as jnp

    mcfg = _smoke(monkeypatch, compute_dtype=lambda c: jnp.float32)
    for seed in (1, 2**31 + 5):
        ok, check, ctx = _run(mcfg, seed=seed, limit=1e-5)
        info = ctx.info_check
        assert ok, (check["widest_gap"], info)
        assert info["ref_dropped_assignments"] > 0  # the capacity rule was exercised
        assert info["off_share"] == 0 and info["served_tokens_compared"] == 64


def _plant(monkeypatch, breaker):
    """Wrap the decode step serve() builds so that ``breaker`` alters what it returns."""
    from repro.launch import serve as S

    real = S.build_decode_step

    def build(cfg, ctx, opts):
        step = real(cfg, ctx, opts)

        def broken(params, cache, tokens):
            logits, new_cache = step(params, cache, tokens)
            return breaker(logits, cache, new_cache)

        return broken

    monkeypatch.setattr(S, "build_decode_step", build)


def test_sound_run_is_correct_and_control_is_not(dropless):
    ok, check, ctx = _run(dropless, control=True)
    assert ok, (check["widest_gap"], ctx.info_check)
    # the control, in the program's place, goes through the same judgement
    assert not bench.is_correct(ctx.control_check), ctx.info_check
    assert ctx.control_check["widest_gap"][0] > LIMIT


def test_altered_token_fails(monkeypatch, dropless):
    # the token a decode step produces is shifted to its neighbour id
    _plant(monkeypatch, lambda lg, old, new: (jnp_roll(lg), new))
    ok, check, _ = _run(dropless)
    assert not ok and check["widest_gap"][0] > LIMIT, check


def test_state_returned_unchanged_fails(monkeypatch, dropless):
    # the step returns the cache it was given: no K/V written, no position advanced
    _plant(monkeypatch, lambda lg, old, new: (lg, old))
    ok, check, _ = _run(dropless)
    assert not ok and check["widest_gap"][0] > LIMIT, check


def test_half_the_batch_left_out_fails(monkeypatch, dropless):
    # the second half of the batch gets the first half's logits
    def half(lg, old, new):
        n = lg.shape[0] // 2
        return lg.at[n:].set(lg[:n]), new

    _plant(monkeypatch, half)
    ok, check, _ = _run(dropless)
    assert not ok and check["widest_gap"][0] > LIMIT, check


def jnp_roll(lg):
    import jax.numpy as jnp

    return jnp.roll(lg, 1, axis=-1)
