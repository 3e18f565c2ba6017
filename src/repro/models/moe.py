"""Mixture-of-Experts with TPU-native expert parallelism.

Design: tokens are sharded over the batch axes and
*replicated* over the model axis; experts are sharded over the model axis.
Every (data, model) device therefore already holds the tokens its experts
need — dispatch is local (sort-based, capacity-bounded) and the ONLY
communication is one psum over the model axis to combine top-k expert
outputs.  No all-to-all: on a TPU torus this turns MoE routing into the same
collective pattern as a Megatron MLP, which is the kind of
communication-minimizing rethink Chimbuko's "analyze where produced"
principle suggests for data movement generally.

Two entry points share the same local math:
  * moe_block(..., ep=None)      — single-device (smoke tests, examples)
  * moe_block(..., ep=EPInfo)    — inside shard_map (launch/steps.py)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig


@dataclasses.dataclass(frozen=True)
class EPInfo:
    """Expert-parallel context: which experts this shard owns."""

    axis: str  # mesh axis name experts are sharded over
    n_shards: int


def _positions_in_run(sorted_ids: jnp.ndarray) -> jnp.ndarray:
    """Occurrence index within runs of equal values (sorted input)."""
    idx = jnp.arange(sorted_ids.shape[0])
    change = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]]
    )
    run_start = jax.lax.cummax(jnp.where(change, idx, 0))
    return idx - run_start


def moe_block(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,  # (B, S, D) tokens local to this shard (replicated over EP axis)
    cfg: ModelConfig,
    ep: Optional[EPInfo] = None,
) -> jnp.ndarray:
    """Top-k routed expert MLP with capacity-based sort dispatch, plus the
    shared experts where the config has them.

    The router scores all ``moe_experts``; this device computes the part of
    the result its held experts give (``cfg.moe_n_held`` from
    ``cfg.moe_held_offset``, split again over ``ep``'s shards), and the
    shared experts' part once."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    with jax.named_scope("routed_experts"):
        out = _routed(p, xt, cfg, ep)
    if cfg.moe_shared_dff:
        with jax.named_scope("shared_expert"):
            h = jax.nn.silu(xt @ p["shared_gate"]) * (xt @ p["shared_up"])
            out = out + h @ p["shared_down"]
    return out.reshape(B, S, D)


def _routed(p, xt, cfg: ModelConfig, ep: Optional[EPInfo]) -> jnp.ndarray:
    N, D = xt.shape
    E, k = cfg.moe_experts, cfg.moe_topk

    # --- routing (replicated over the EP axis: cheap, avoids a broadcast) ---
    logits = (xt @ p["router"]).astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)  # (N, k)
    if cfg.moe_norm_topk:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    # --- local expert ownership ------------------------------------------
    e_loc, off = cfg.n_experts_held, cfg.moe_held_offset
    if ep is not None:
        e_loc //= ep.n_shards
        off += jax.lax.axis_index(ep.axis) * e_loc
    w_gate, w_up, w_down = p["moe_gate"], p["moe_up"], p["moe_down"]
    # Capacity: expected load × factor, floored so tiny decode batches
    # (N ~ a few tokens) stay effectively dropless.
    C = max(math.ceil(k * N / E * cfg.moe_capacity_factor), min(N, 16))

    # --- sort-based dispatch ----------------------------------------------
    flat_ids = ids.reshape(-1)  # (N*k,)
    flat_tok = jnp.repeat(jnp.arange(N), k)
    flat_w = weights.reshape(-1)
    order = jnp.argsort(flat_ids)
    s_ids = flat_ids[order]
    s_tok = flat_tok[order]
    s_w = flat_w[order]
    pos = _positions_in_run(s_ids)
    local_e = s_ids - off
    owned = (local_e >= 0) & (local_e < e_loc) & (pos < C)
    slot = jnp.where(owned, local_e * C + pos, e_loc * C)  # OOB -> dropped
    buf = jnp.zeros((e_loc * C, D), xt.dtype).at[slot].set(
        xt[s_tok] * owned[:, None].astype(xt.dtype), mode="drop"
    )
    buf = buf.reshape(e_loc, C, D)

    # --- expert FFN (batched einsum over local experts) --------------------
    if cfg.activation == "geglu":
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, w_gate), approximate=True)
    else:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w_up)
    y_buf = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(e_loc * C, D)

    # --- combine: gather back, weight, scatter-add over tokens -------------
    contrib = jnp.take(y_buf, jnp.where(owned, slot, e_loc * C), axis=0,
                       mode="fill", fill_value=0.0)
    contrib = contrib * (s_w * owned)[:, None].astype(xt.dtype)
    out = jnp.zeros((N, D), xt.dtype).at[s_tok].add(contrib)
    if ep is not None:
        out = jax.lax.psum(out, ep.axis)
    return out


def moe_aux_loss(
    p: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: ModelConfig
) -> jnp.ndarray:
    """Load-balancing auxiliary loss (Switch-style): E · Σ_e f_e · P_e."""
    N = x.shape[0] * x.shape[1]
    logits = (x.reshape(N, -1) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, ids = jax.lax.top_k(probs, cfg.moe_topk)
    f = jnp.zeros(cfg.moe_experts).at[ids.reshape(-1)].add(1.0) / (N * cfg.moe_topk)
    P = probs.mean(0)
    return cfg.moe_experts * jnp.sum(f * P)
