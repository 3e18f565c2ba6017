"""Dry-run machinery: HLO parsing, roofline math, probe semantics, mini-mesh."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import roofline as R


def test_cost_analysis_counts_loop_bodies_once():
    """The documented XLA behavior probe-mode corrects for."""

    def f(x, n):
        return jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=n)[0]

    x = jnp.ones((256, 256))
    f4 = jax.jit(f, static_argnums=1).lower(x, 4).compile().cost_analysis()["flops"]
    f8 = jax.jit(f, static_argnums=1).lower(x, 8).compile().cost_analysis()["flops"]
    assert f4 == f8  # loop body counted once regardless of trip count
    # unrolled scan counts every iteration
    def fu(x, n):
        return jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=n, unroll=True)[0]

    u8 = jax.jit(fu, static_argnums=1).lower(x, 8).compile().cost_analysis()["flops"]
    assert u8 >= 7.5 * f4 / 8 * 8  # ≈ 8 bodies counted


def test_collective_bytes_parser():
    hlo = """
  %ag = bf16[16,4096]{1,0} all-gather(bf16[16,256]{1,0} %p), dims={1}
  %ar.1 = f32[1024]{0} all-reduce(f32[1024]{0} %x), to_apply=%add
  %ars = f32[512]{0} all-reduce-start(f32[512]{0} %y), to_apply=%add
  %rs = (f32[128]{0}, f32[128]{0}) reduce-scatter(f32[1024]{0} %a, f32[1024]{0} %b), dims={0}
  %cp = u8[64]{0} collective-permute(u8[64]{0} %z), source_target_pairs={{0,1}}
"""
    out = R.collective_bytes(hlo)
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["result_bytes"] == 16 * 4096 * 2
    assert out["all-reduce"]["count"] == 2
    assert out["all-reduce"]["result_bytes"] == 1024 * 4 + 512 * 4
    assert out["all-reduce"]["wire_bytes"] == 2 * (1024 * 4 + 512 * 4)
    assert out["reduce-scatter"]["result_bytes"] == 2 * 128 * 4
    assert out["collective-permute"]["result_bytes"] == 64


def test_roofline_terms_math():
    t = R.roofline_terms(197e12, 819e9, 50e9)  # exactly 1s each
    assert abs(t["compute_s"] - 1.0) < 1e-9
    assert abs(t["memory_s"] - 1.0) < 1e-9
    assert abs(t["collective_s"] - 1.0) < 1e-9
    t2 = R.roofline_terms(197e12, 0.0, 0.0)
    assert t2["dominant"] == "compute"
    assert t2["compute_fraction_of_bound"] == 1.0


def test_model_flops_modes():
    cfg = configs.get_config("gemma-2b")
    n = cfg.n_active_params()
    assert R.model_flops(cfg, "train", 4, 128) == 6.0 * n * 512
    assert R.model_flops(cfg, "prefill", 4, 128) == 2.0 * n * 512
    assert R.model_flops(cfg, "decode", 4, 128) == 2.0 * n * 4
    moe = configs.get_config("qwen3-moe-30b-a3b")
    assert moe.n_active_params() < 0.2 * moe.n_params()  # 3B active of 30B


def test_memory_floor_sane():
    cfg = configs.get_config("gemma-2b")
    f_train = R.analytic_memory_floor(cfg, "train", 256, 4096, 256, 1)
    f_dec = R.analytic_memory_floor(cfg, "decode", 128, 32768, 256, 1)
    assert f_train > f_dec  # training moves far more bytes
    assert 1e8 < f_dec < 1e12
    # decode must include weight reads: at least 2·Na/16 bytes
    assert f_dec > 2 * cfg.n_active_params() / 16


_PROBE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax
from repro import configs
from repro.launch.steps import StepOptions, make_cell
from repro.launch.dryrun import probe_costs

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 3)
configs.SHAPES["mini_train"] = configs.ShapeCell("mini_train", 64, 8, "train")
cfg = configs.smoke("gemma2-2b")  # period 2, smoke n_layers = 4 (2 periods)
probe = probe_costs(cfg, "mini_train", mesh, {}, 1)

# ground truth: full model with every scan unrolled, cost counted directly
full = make_cell(cfg, "mini_train", mesh, StepOptions(probe=True, microbatch=1))
ca = full.lower().compile().cost_analysis()
direct = float(ca["flops"])
extrap = probe["flops"]
rel = abs(extrap - direct) / direct
assert rel < 0.02, (extrap, direct, rel)
print("PROBE_EXTRAPOLATION_OK", extrap, direct)
"""


def test_probe_extrapolation_matches_unrolled():
    """C(1) + (NP−1)(C(2)−C(1)) == fully-unrolled cost (affine exactness)."""
    r = subprocess.run(
        [sys.executable, "-c", _PROBE_SCRIPT], capture_output=True, text=True,
        timeout=560, env={**os.environ, "PYTHONPATH": "src"},
    )
    assert "PROBE_EXTRAPOLATION_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-3000:]


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import dataclasses
from repro import configs
from repro.launch.steps import StepOptions, make_cell
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 3)
configs.SHAPES["mini"] = configs.ShapeCell("mini", 64, 8, "train")
configs.SHAPES["mini_dec"] = configs.ShapeCell("mini_dec", 64, 8, "decode")
for arch in ("jamba-v0.1-52b", "qwen3-moe-30b-a3b", "minicpm3-4b"):
    cfg = configs.smoke(arch)
    for shape in ("mini", "mini_dec"):
        cell = make_cell(cfg, shape, mesh, StepOptions(ce_chunk=32))
        cell.lower().compile()
print("MINI_MESH_OK")
"""


def test_mini_mesh_cells_compile():
    """Representative archs × (train, decode) lower+compile on a 3-axis mesh."""
    r = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT], capture_output=True, text=True,
        timeout=560, env={**os.environ, "PYTHONPATH": "src"},
    )
    assert "MINI_MESH_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-3000:]


def test_cell_applicability_table():
    cells = list(configs.all_cells())
    assert len(cells) == 48  # 12 archs x 4 shapes
    runnable = [c for c in cells if c[2]]
    # 9 documented skips (DESIGN.md §5); deepseek_v2_lite's is long_500k
    # (full attention: MLA is not sub-quadratic)
    assert len(runnable) == 39
    skipped = {(a, s) for a, s, ok, _ in cells if not ok}
    assert ("deepseek_v2_lite", "long_500k") in skipped
    assert ("deepseek_v2_lite", "decode_32k") not in skipped
    assert ("hubert_xlarge", "decode_32k") in skipped
    assert ("hubert_xlarge", "long_500k") in skipped
    assert ("gemma_2b", "long_500k") in skipped
    assert ("falcon_mamba_7b", "long_500k") not in skipped
    assert ("jamba_v01_52b", "long_500k") not in skipped
    assert ("granite_4_0_h_small", "long_500k") not in skipped  # hybrid: O(1) state
    assert ("h2o_danube3_4b", "long_500k") not in skipped
