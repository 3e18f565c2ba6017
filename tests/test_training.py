"""Training integration: convergence, exact restart, microbatching, DP compression."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.data.pipeline import synthetic_batch
from repro.launch.steps import StepOptions, build_train_step, make_shard_ctx, make_train_state
from repro.launch.train import train
from repro.optim.adamw import OptConfig


def _fixed_batch_steps(arch="gemma-2b", steps=40, lr=3e-3):
    cfg = configs.smoke(arch)
    opts = StepOptions(
        ce_chunk=512,
        opt=OptConfig(peak_lr=lr, warmup_steps=5, decay_steps=200, weight_decay=0.0),
    )
    ctx = make_shard_ctx(cfg, None, 4, opts)
    step_fn = jax.jit(build_train_step(cfg, ctx, opts))
    state = make_train_state(cfg, 0)
    batch = synthetic_batch(cfg, 4, 32, seed=0)
    losses = []
    for _ in range(steps):
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
    return losses


def test_overfits_fixed_batch():
    """Optimization sanity: loss on a memorized batch must fall sharply."""
    losses = _fixed_batch_steps()
    assert losses[0] > 5.5  # ~ln(512)
    assert losses[-1] < losses[0] * 0.5, losses[::8]


def test_microbatch_equivalence():
    """Grad accumulation (microbatch=2) ≈ single-shot on the same batch."""
    cfg = configs.smoke("gemma-2b")
    batch = synthetic_batch(cfg, 4, 32, seed=1)
    outs = {}
    for mb in (1, 2):
        opts = StepOptions(ce_chunk=512, microbatch=mb,
                           opt=OptConfig(peak_lr=1e-3, warmup_steps=1, weight_decay=0.0))
        ctx = make_shard_ctx(cfg, None, 4, opts)
        step_fn = jax.jit(build_train_step(cfg, ctx, opts))
        state = make_train_state(cfg, 0)
        state, m = step_fn(state, batch)
        outs[mb] = state["params"]["embed"]
    # bf16 grad-sum ordering differs; Adam amplifies near-zero-grad elements
    # up to a full lr (1e-3) step, so tolerate |delta| ~ lr on a few entries.
    np.testing.assert_allclose(
        np.asarray(outs[1], np.float32), np.asarray(outs[2], np.float32),
        rtol=1e-2, atol=2e-3,
    )


def test_restart_exact_resume(tmp_path):
    """Crash at step 12, resume from ckpt → same final loss as uninterrupted."""
    kw = dict(arch="gemma-2b", steps=20, global_batch=4, seq=32,
              ckpt_interval=5, log_every=100)
    full = train(ckpt_dir=str(tmp_path / "a"), **kw)

    with pytest.raises(RuntimeError):
        train(ckpt_dir=str(tmp_path / "b"), fail_at=12, **kw)
    resumed = train(ckpt_dir=str(tmp_path / "b"), **kw)
    assert resumed["history"][0]["step"] == 10  # resumed from step-10 ckpt
    np.testing.assert_allclose(
        full["final_loss"], resumed["final_loss"], rtol=1e-5
    )


def test_straggler_mitigation_hook(tmp_path):
    out = train(
        arch="gemma-2b", steps=30, global_batch=4, seq=32,
        inject_straggler_at=25, log_every=100,
    )
    assert out["monitor"]["stragglers"] >= 1


_DP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.data.pipeline import synthetic_batch
from repro.launch.steps import make_dp_train_step, make_train_state
from repro.optim.adamw import OptConfig
cfg = configs.smoke("gemma-2b")
mesh = jax.make_mesh((4,), ("data",))
batch = synthetic_batch(cfg, 8, 32, seed=0)
results = {}
for compress in (False, True):
    step_fn, init_err = make_dp_train_step(
        cfg, mesh, OptConfig(peak_lr=3e-3, warmup_steps=5, weight_decay=0.0),
        compress=compress)
    state = make_train_state(cfg, 0)
    err = init_err(state["params"])
    losses = []
    for _ in range(30):
        state, err, m = step_fn(state, err, batch)
        losses.append(float(m["loss"]))
    results[compress] = losses
l0, l1 = results[False], results[True]
assert l0[-1] < l0[0] * 0.6, ("uncompressed did not converge", l0[::6])
assert l1[-1] < l1[0] * 0.6, ("compressed did not converge", l1[::6])
assert abs(l1[-1] - l0[-1]) / l0[-1] < 0.35, (l0[-1], l1[-1])
print("DP_COMPRESS_OK", l0[-1], l1[-1])
"""


def test_dp_compressed_training_converges():
    r = subprocess.run(
        [sys.executable, "-c", _DP_SCRIPT], capture_output=True, text=True,
        timeout=560, env={**os.environ, "PYTHONPATH": "src"},
    )
    assert "DP_COMPRESS_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


def test_serve_driver_runs():
    from repro.launch.serve import serve

    out = serve(arch="gemma-2b", n_requests=4, batch=2, prompt_len=8, max_new=4)
    assert out["requests"] == 4
    assert out["tokens"] == 16
    assert all(len(s) > 0 for s in out["samples"])


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise a fixed dir in the checkout."""
    from repro.launch import compile_cache as CC

    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert CC.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = CC.enable_compile_cache()
            assert path == str(CC.CACHE_DIR) == jax.config.jax_compilation_cache_dir
            assert (CC.CACHE_DIR.parent / "pyproject.toml").is_file()
            assert CC.enable_compile_cache() == path  # fixed across calls
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
