"""Step builders: distributed train / prefill / decode with explicit shardings.

``make_cell`` is the single entry point both dryrun.py (AOT lower+compile on
ShapeDtypeStructs) and launch/train.py / launch/serve.py (real arrays) use —
the dry-run proves exactly the artifacts production executes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.data.pipeline import batch_spec as data_batch_spec
from repro.models import model as M
from repro.models.common import ModelConfig, init_params
from repro.models.model import ShardCtx
from repro.optim.adamw import OptConfig, apply_updates, init_opt_state
from .mesh import batch_axes as mesh_batch_axes, batch_shards, tp_size
from . import sharding as SH


@dataclasses.dataclass(frozen=True)
class StepOptions:
    remat: str = "block"  # none | block
    fsdp: bool = True
    ce_chunk: int = 1024
    microbatch: int = 0  # gradient-accumulation steps; 0 = auto (fit HBM)
    seq_shard: bool = False  # sequence-parallel hidden states
    donate: bool = True
    probe: bool = False  # unrolled cost-accounting compile (dryrun --probes)
    # bf16 params + sharded fp32 master inside opt state: halves the FSDP
    # weight-gather footprint (required to fit jamba-52B train; see §Perf)
    master_in_opt: bool = False
    mamba_tp: bool = True  # False: mamba layers pure-FSDP (no TP psums)
    opt: OptConfig = OptConfig()


def auto_microbatch(cfg: ModelConfig, global_batch: int, seq: int, dp: int) -> int:
    """Smallest power-of-two accumulation count that bounds the layer-scan
    carry chain (n_layers × B_loc/mb × S × d × 2B) near ~5 GiB/device,
    leaving headroom for the backward working set on a 16 GiB chip."""
    b_loc = max(global_batch // max(dp, 1), 1)
    carry = cfg.n_layers * b_loc * seq * cfg.d_model * 2
    budget = 5 * 1024**3
    mb = 1
    while carry / mb > budget and mb < b_loc:
        mb *= 2
    return mb


def make_shard_ctx(
    cfg: ModelConfig, mesh, global_batch: int, opts: StepOptions
) -> ShardCtx:
    if mesh is None:
        return ShardCtx(remat=opts.remat, unroll=opts.probe)
    dpa = mesh_batch_axes(mesh)
    dp = batch_shards(mesh)
    return ShardCtx(
        mesh=mesh,
        batch_axes=dpa,
        model_axis="model",
        batch_shardable=(global_batch % dp == 0 and global_batch >= dp),
        seq_shard=opts.seq_shard,
        remat=opts.remat,
        unroll=opts.probe,
    )


# ------------------------------------------------------------ pure step fns
def build_train_step(
    cfg: ModelConfig, ctx: ShardCtx, opts: StepOptions, microbatch: Optional[int] = None
) -> Callable:
    nm_cfg = microbatch if microbatch is not None else max(opts.microbatch, 1)

    def loss_fn(params, batch):
        return M.loss_and_metrics(cfg, params, batch, ctx, opts.ce_chunk)

    def train_step(state, batch):
        if nm_cfg > 1:
            nm = nm_cfg

            def split(name, x):
                if name == "pos3":  # (3, B, S): batch lives on axis 1
                    return x.reshape(
                        (3, nm, x.shape[1] // nm) + x.shape[2:]
                    ).swapaxes(0, 1)
                return x.reshape((nm, x.shape[0] // nm) + x.shape[1:])

            mb = {k: split(k, v) for k, v in batch.items()}

            def acc_body(carry, mbatch):
                gacc, lsum = carry
                (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    state["params"], mbatch
                )
                return (jax.tree.map(jnp.add, gacc, grads), lsum + loss), None

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            (gsum, lsum), _ = jax.lax.scan(
                acc_body, (zero, jnp.zeros(())), mb, unroll=ctx.scan_unroll
            )
            grads = jax.tree.map(lambda g: g / nm, gsum)
            metrics = {"loss": lsum / nm, "accuracy": jnp.zeros(()), "tokens": jnp.zeros(())}
        else:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state["params"], batch
            )
        master = state.get("master") or state["params"]
        new_master, opt_state, ostats = apply_updates(
            master, grads,
            {"m": state["m"], "v": state["v"], "step": state["step"]}, opts.opt,
        )
        out_state = {
            "params": new_master, "m": opt_state["m"], "v": opt_state["v"],
            "step": opt_state["step"],
        }
        if "master" in state:  # bf16 working params, fp32 sharded master
            out_state["master"] = new_master
            out_state["params"] = jax.tree.map(
                lambda q: q.astype(jnp.bfloat16), new_master
            )
        return out_state, dict(metrics, **ostats)

    return train_step


def build_prefill_step(cfg, ctx, opts, max_seq=None) -> Callable:
    def prefill_step(params, batch):
        with jax.named_scope("prefill"):  # the program's ops, by a stable name
            return M.prefill(cfg, params, batch, ctx, max_seq=max_seq)

    return prefill_step


def build_decode_step(cfg, ctx, opts) -> Callable:
    def decode_step(params, cache, tokens):
        with jax.named_scope("decode"):
            return M.decode_step(cfg, params, cache, tokens, ctx)

    return decode_step


def make_dp_train_step(
    cfg: ModelConfig, mesh, opt: OptConfig = OptConfig(),
    compress: bool = True, ce_chunk: int = 512,
):
    """Explicit data-parallel step via shard_map with (optionally int8-
    compressed, error-feedback) gradient all-reduce.

    This is the bandwidth-bound regime's distributed-optimization trick
    (optim/compression.py): gradients cross the slow inter-pod links at 1
    byte/element instead of 4.  Error-feedback state is per-device, stored
    with a leading device axis sharded over the mesh.

    Returns (jitted step, init_err_fn).  step(state, err, batch) ->
    (state, err, metrics).
    """
    from repro.optim.compression import compressed_psum

    axes = tuple(mesh.axis_names)
    ndev = 1
    for a in axes:
        ndev *= mesh.shape[a]

    def _local(state, err, batch):
        def loss_fn(p):
            return M.loss_and_metrics(cfg, p, batch, ShardCtx(), ce_chunk)[0]

        loss, grads = jax.value_and_grad(loss_fn)(state["params"])
        flat_g, tdef = jax.tree_util.tree_flatten(grads)
        flat_e = jax.tree_util.tree_leaves(err)
        new_g, new_e = [], []
        for g, e in zip(flat_g, flat_e):
            if compress:
                gm, en = compressed_psum(g, axes, e[0])
            else:
                gm = jax.lax.pmean(g, axes)
                en = e[0]
            new_g.append(gm)
            new_e.append(en[None])
        grads = tdef.unflatten(new_g)
        err = tdef.unflatten(new_e)
        new_params, opt_state, stats = apply_updates(
            state["params"], grads,
            {"m": state["m"], "v": state["v"], "step": state["step"]}, opt,
        )
        metrics = {"loss": jax.lax.pmean(loss, axes), **stats}
        state = {"params": new_params, "m": opt_state["m"], "v": opt_state["v"],
                 "step": opt_state["step"]}
        return state, err, metrics

    state_struct = jax.eval_shape(functools.partial(make_train_state, cfg))
    rep = jax.tree.map(lambda _: P(), state_struct)
    err_spec_leaf = P(axes)
    fn = shard_map(
        _local,
        mesh=mesh,
        in_specs=(rep, jax.tree.map(lambda _: err_spec_leaf, state_struct["params"]),
                  P(axes)),
        out_specs=(rep, jax.tree.map(lambda _: err_spec_leaf, state_struct["params"]),
                   jax.tree.map(lambda _: P(), {"loss": 0, "grad_norm": 0, "lr": 0})),
        check_vma=False,
    )

    def init_err(params):
        return jax.tree.map(
            lambda p: jnp.zeros((ndev,) + p.shape, jnp.float32), params
        )

    return jax.jit(fn), init_err


def make_train_state(cfg: ModelConfig, seed: int = 0, master_in_opt: bool = False):
    params = init_params(cfg, jax.random.key(seed))
    o = init_opt_state(params)
    state = {"params": params, "m": o["m"], "v": o["v"], "step": o["step"]}
    if master_in_opt:
        state["master"] = params  # fp32, stays sharded (never gathered)
        state["params"] = jax.tree.map(lambda q: q.astype(jnp.bfloat16), params)
    return state


# ------------------------------------------------------------------- cells
@dataclasses.dataclass
class Cell:
    """One (arch × shape × mesh) lowering unit."""

    cfg: ModelConfig
    shape: str
    mesh: Any
    mode: str
    fn: Callable  # pure step function
    args: Tuple[Any, ...]  # ShapeDtypeStructs (with shardings when meshed)
    donate: Tuple[int, ...]
    ctx: ShardCtx

    def jitted(self):
        return jax.jit(self.fn, donate_argnums=self.donate)

    def lower(self):
        return self.jitted().lower(*self.args)


def _attach(struct_tree, shardings_tree):
    """Attach shardings to ShapeDtypeStructs (AOT input stand-ins)."""
    if shardings_tree is None:
        return struct_tree
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        struct_tree,
        shardings_tree,
    )


def make_cell(
    arch: str, shape: str, mesh=None, opts: StepOptions = StepOptions()
) -> Cell:
    cfg = configs.get_config(arch) if isinstance(arch, str) else arch
    cell = configs.SHAPES[shape]
    B, S = cell.global_batch, cell.seq_len
    ctx = make_shard_ctx(cfg, mesh, B, opts)
    if opts.probe:
        opts = dataclasses.replace(opts, ce_chunk=S)

    if cell.mode == "train":
        dp = batch_shards(mesh) if mesh is not None else 1
        mb = opts.microbatch or auto_microbatch(cfg, B, S, dp)
        fn = build_train_step(cfg, ctx, opts, microbatch=mb)
        state = jax.eval_shape(
            functools.partial(make_train_state, cfg, master_in_opt=opts.master_in_opt)
        )
        batch = data_batch_spec(cfg, B, S)
        if mesh is not None:
            ps = lambda t: SH.param_shardings(cfg, t, mesh, opts.fsdp, opts.mamba_tp)
            st_sh = {
                "params": ps(state["params"]), "m": ps(state["m"]),
                "v": ps(state["v"]), "step": NamedSharding(mesh, P()),
            }
            if "master" in state:
                st_sh["master"] = ps(state["master"])
            state = _attach(state, st_sh)
            batch = _attach(batch, SH.batch_shardings(cfg, batch, mesh))
        args = (state, batch)
        donate = (0,) if opts.donate else ()
    elif cell.mode == "prefill":
        fn = build_prefill_step(cfg, ctx, opts)
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
        batch = data_batch_spec(cfg, B, S)
        batch.pop("labels", None)
        if mesh is not None:
            params = _attach(
                params, SH.param_shardings(cfg, params, mesh, opts.fsdp, opts.mamba_tp)
            )
            batch = _attach(batch, SH.batch_shardings(cfg, batch, mesh))
        args = (params, batch)
        donate = ()
    else:  # decode
        fn = build_decode_step(cfg, ctx, opts)
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
        cache = jax.eval_shape(functools.partial(M.init_cache, cfg, B, S))
        tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        if mesh is not None:
            params = _attach(
                params, SH.param_shardings(cfg, params, mesh, opts.fsdp, opts.mamba_tp)
            )
            cache = _attach(cache, SH.cache_shardings(cfg, cache, mesh))
            tokens = jax.ShapeDtypeStruct(
                tokens.shape, tokens.dtype,
                sharding=NamedSharding(mesh, SH.batch_pspec(cfg, "tokens", tokens.shape, mesh)),
            )
        args = (params, cache, tokens)
        donate = (1,) if opts.donate else ()
    return Cell(cfg, shape, mesh, cell.mode, fn, args, donate, ctx)
