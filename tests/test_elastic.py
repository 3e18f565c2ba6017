"""Elasticity: a run checkpointed at one mesh width continues at another."""
import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools, jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.checkpoint import ckpt as CK
from repro.data.pipeline import DataShard, SyntheticStream
from repro.launch import sharding as SH
from repro.launch.steps import StepOptions, build_train_step, make_shard_ctx, make_train_state
from repro.optim.adamw import OptConfig

AUTO = (jax.sharding.AxisType.Auto,) * 2

cfg = configs.smoke("gemma-2b")
opts = StepOptions(ce_chunk=512, opt=OptConfig(peak_lr=1e-3, warmup_steps=5))
GB, SEQ = 8, 32
stream = SyntheticStream(cfg, DataShard(0, 1, GB), SEQ, seed=3)

def run_steps(mesh, state, lo, hi):
    ctx = make_shard_ctx(cfg, mesh, GB, opts)
    step_fn = jax.jit(build_train_step(cfg, ctx, opts, microbatch=1))
    losses = []
    for s in range(lo, hi):
        batch = {k: jnp.asarray(v) for k, v in stream.batch_at(s).items()}
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
    return state, losses

# reference: uninterrupted single-device run
state0 = make_train_state(cfg, 0)
_, ref_losses = run_steps(None, make_train_state(cfg, 0), 0, 12)

# phase 1: mesh A = (4 data, 2 model)
mesh_a = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO)
sh_a = {
    "params": SH.param_shardings(cfg, jax.eval_shape(lambda: state0["params"]), mesh_a),
}
state = make_train_state(cfg, 0)
state, l_a = run_steps(mesh_a, state, 0, 6)
CK.save("/tmp/elastic_ck", 6, state)

# phase 2 ("after node loss"): mesh B = (2 data, 4 model), restored + resharded
mesh_b = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO)
target = jax.eval_shape(functools.partial(make_train_state, cfg))
shards_b = {
    "params": SH.param_shardings(cfg, target["params"], mesh_b),
    "m": SH.param_shardings(cfg, target["m"], mesh_b),
    "v": SH.param_shardings(cfg, target["v"], mesh_b),
    "step": NamedSharding(mesh_b, P()),
}
step_n, state_b = CK.load("/tmp/elastic_ck", target=target, shardings=shards_b)
assert step_n == 6
_, l_b = run_steps(mesh_b, state_b, 6, 12)

full = l_a + l_b
err = max(abs(x - y) for x, y in zip(full, ref_losses))
assert err < 5e-2, (err, full, ref_losses)
print("ELASTIC_OK", err)
"""


def test_elastic_mesh_rescale():
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        timeout=560, env={**os.environ, "PYTHONPATH": "src"},
    )
    assert "ELASTIC_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-3000:]
