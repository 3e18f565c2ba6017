"""Model assembly: every arch is stacks of layer periods, each stack one
lax.scan over its periods.

One layer body (``_layer``) serves every mode; the modes differ only in the
mixer they hand it:

  forward / loss_and_metrics  — the sequence pass, its scans carrying no cache
  prefill                     — the same sequence pass, its scans' ys the
                                layers' decode caches
  decode_step                 — one token against the cache, through each
                                mixer's decode form; the scan reads the
                                cache, and one row per layer is written
                                after it

The cache is stacked over periods per layout position, so decode is also a
single lax.scan (compile-size friendly at 512 devices).  Ring buffers handle
SWA windows; MLA caches the compressed latent (its whole point); Mamba-1
and Mamba-2 keep O(1) state, which their sequence mixers return.  The
cache's format lives here alone: ``_layer_cache`` makes its leaves and
``cache_bytes_by_kind`` sizes them.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from . import layers as L
from .common import MAMBA, MAMBA2, MLA, MOE, NONE, SWA, LayerSpec, ModelConfig
from .mamba import (mamba2_decode, mamba2_sequence, mamba_decode, mamba_mixer_seq_parallel,
                    mamba_sequence)
from .moe import EPInfo, moe_block


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """How the current step is distributed (None mesh = single device)."""

    mesh: Optional[Any] = None
    batch_axes: Tuple[str, ...] = ()
    model_axis: Optional[str] = "model"
    batch_shardable: bool = True  # False for global_batch=1 cells
    seq_shard: bool = False  # sequence-parallel activations (small-head archs)
    remat: str = "none"  # none | block
    # probe mode (dryrun cost accounting): unroll every scan so XLA
    # cost_analysis — which counts loop bodies ONCE — sees all the work.
    unroll: bool = False

    @property
    def scan_unroll(self):
        return True if self.unroll else 1

    @property
    def token_pspec(self) -> P:
        b = self.batch_axes if (self.mesh is not None and self.batch_shardable) else None
        return P(b)

    def constrain(self, x: jnp.ndarray, spec: P) -> jnp.ndarray:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec)
        )

    def hidden_spec(self) -> P:
        b = self.batch_axes if self.batch_shardable else None
        s = self.model_axis if self.seq_shard else None
        return P(b, s, None)

    def ep_info(self, cfg: ModelConfig) -> Optional[EPInfo]:
        if self.mesh is None or self.model_axis is None:
            return None
        n = self.mesh.shape[self.model_axis]
        if cfg.n_experts_held % n != 0:
            return None
        return EPInfo(axis=self.model_axis, n_shards=n)


# ---------------------------------------------------------------- embedding
def embed_tokens(cfg: ModelConfig, params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    if cfg.modality == "audio_stub":
        x = batch["embeds"].astype(cfg.compute_dtype)
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(cfg.compute_dtype)
        if cfg.modality == "vision_stub" and "visual_embeds" in batch:
            vis = batch["visual_embeds"].astype(cfg.compute_dtype)
            n_vis = vis.shape[1]
            x = jnp.concatenate([vis, x[:, n_vis:]], axis=1)
    if cfg.emb_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.compute_dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, cfg.compute_dtype)
    return x


def _positions(cfg: ModelConfig, batch, B: int, S: int, offset=0) -> jnp.ndarray:
    if cfg.pos == "mrope":
        if "pos3" in batch:
            return batch["pos3"]
        return L.mrope_text_positions(B, S, offset)
    return L.text_positions(B, S, offset)


def _rope_cos_sin(cfg: ModelConfig, positions):
    """cos/sin over the rotated dims: a head's, or MLA's ``qk_rope_dim``;
    YaRN's frequencies and cos/sin scale where ``cfg.yarn_factor`` > 1."""
    dim = cfg.qk_rope_dim if cfg.mixer_has(MLA) else cfg.qk_dim
    if cfg.pos == "mrope":
        return L.mrope_cos_sin(positions, dim, cfg.mrope_sections, cfg.rope_theta)
    if cfg.pos == "none":
        return None, None
    if cfg.yarn_factor > 1:
        f = cfg.yarn_factor
        inv_freq = L.yarn_inv_freq(dim, cfg.rope_theta, f, cfg.yarn_original_max_pos,
                                   cfg.yarn_beta_fast, cfg.yarn_beta_slow)
        scale = L.yarn_mscale(f, cfg.yarn_mscale) / L.yarn_mscale(f, cfg.yarn_mscale_all_dim)
        return L.rope_cos_sin(positions, dim, inv_freq=inv_freq, scale=scale)
    return L.rope_cos_sin(positions, dim, cfg.rope_theta)


def _mla_scale(cfg: ModelConfig) -> float:
    """MLA's softmax scale, 1/sqrt(qk head dim), times YaRN's m² where the
    config gives ``yarn_mscale_all_dim``."""
    m = L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) if cfg.yarn_mscale_all_dim else 1.0
    return m * m / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)


def _mla_q(cfg: ModelConfig, p, h):
    """MLA queries (B, S, H, nope + rope): through the q latent, or a plain
    ``wq`` where the config has no q compression."""
    if cfg.q_lora_rank:
        q = L.rms_norm(h @ p["wdq"], p["q_ln"], cfg.norm_eps) @ p["wuq"]
    else:
        q = h @ p["wq"]
    return q.reshape(h.shape[0], h.shape[1], cfg.n_heads, -1)


def unembed(cfg: ModelConfig, params, x: jnp.ndarray) -> jnp.ndarray:
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T.astype(cfg.compute_dtype)
    else:
        logits = x @ params["unembed"].astype(cfg.compute_dtype)
    logits = logits.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    logits = L.softcap(logits, cfg.logit_softcap)
    if cfg.vocab_padded != cfg.vocab:  # mask the padding columns exactly
        pad_ok = jnp.arange(cfg.vocab_padded) < cfg.vocab
        logits = jnp.where(pad_ok, logits, L.NEG_INF)
    return logits


# ----------------------------------------------------------------- blocks
_F32_KEYS = frozenset({"A_log"})  # kept f32: used only inside f32 math


def _cast_block_params(p: Dict[str, jnp.ndarray], dtype) -> Dict[str, jnp.ndarray]:
    """bf16 compute casts of the fp32 master weights (mixed precision)."""
    return {k: (v if k in _F32_KEYS else v.astype(dtype)) for k, v in p.items()}


def compute_params(cfg: ModelConfig, params):
    """The compute-dtype copy of what ``prefill`` and ``decode_step`` cast:
    the layers as ``_cast_block_params`` casts them, and the embedding and
    unembedding, which are cast right after every read.  Every other leaf
    is kept as it is.  On this copy the steps' casts are no-ops, so a
    server that never changes its weights casts them once, not per step.
    Where every such weight is already in the compute dtype, ``params``
    itself: no second copy."""
    copy = jax.eval_shape(functools.partial(_compute_copy, cfg), params)
    if all(c.dtype == p.dtype for c, p in zip(jax.tree.leaves(copy), jax.tree.leaves(params))):
        return params
    return _compute_copy(cfg, params)


@functools.partial(jax.jit, static_argnums=0)
def _compute_copy(cfg: ModelConfig, params):
    out = dict(params)
    out["layers"] = [_cast_block_params(p, cfg.compute_dtype) for p in params["layers"]]
    if "lead" in params:
        out["lead"] = [_cast_block_params(p, cfg.compute_dtype) for p in params["lead"]]
    for k in ("embed", "unembed"):
        if k in params:
            out[k] = params[k].astype(cfg.compute_dtype)
    return out


def _attention_seq_parallel(
    q, k, v, ctx: ShardCtx, *, causal, window, cap, scale=None
) -> jnp.ndarray:
    """Context-parallel attention: queries stay sequence-sharded over the
    model axis, K/V are all-gathered (tiny vs. S² scores), each shard
    computes its causal slice with a global query offset.

    Replaces XLA's default for unshardable-head archs — contraction
    sharding over head_dim, which all-reduces fp32 (Sq, Sk) score tensors
    (2–3 GB/layer at train_4k)."""
    B, S, H, hd = q.shape
    tp = ctx.mesh.shape[ctx.model_axis]
    S_loc = S // tp
    b = ctx.batch_axes if ctx.batch_shardable else None
    m_ax = ctx.model_axis

    # probe mode: single-block chunks -> the internal scans have length 1,
    # so cost_analysis counts the attention exactly without unrolling
    cq = S_loc if ctx.unroll else min(512, S_loc)
    ck = S if ctx.unroll else min(1024, S)

    def f(qr, kr, vr):
        kf = jax.lax.all_gather(kr, m_ax, axis=1, tiled=True)
        vf = jax.lax.all_gather(vr, m_ax, axis=1, tiled=True)
        off = jax.lax.axis_index(m_ax) * S_loc
        return L.attention_chunked(
            qr, kf, vf, causal=causal, window=window, cap=cap, scale=scale,
            q_offset=off, chunk_q=cq, chunk_k=ck,
        )

    fn = shard_map(
        f,
        mesh=ctx.mesh,
        in_specs=(
            P(b, m_ax, None, None), P(b, m_ax, None, None), P(b, m_ax, None, None),
        ),
        out_specs=P(b, m_ax, None, None),
        check_vma=False,
    )
    return fn(q, k, v)


def _use_seq_parallel(ctx: ShardCtx, S: int) -> bool:
    return (
        ctx.seq_shard
        and ctx.mesh is not None
        and ctx.model_axis in getattr(ctx.mesh, "axis_names", ())
        and S % ctx.mesh.shape[ctx.model_axis] == 0
        and S >= ctx.mesh.shape[ctx.model_axis] * 16
    )


def _attn_seq(cfg, spec, p, h, cos, sin, ctx: ShardCtx):
    """GQA over a sequence: (output, (k, v)), K/V roped, one entry per position."""
    B, S, D = h.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ p["wq"]).reshape(B, S, H, hd)
    k = (h @ p["wk"]).reshape(B, S, KV, hd)
    v = (h @ p["wv"]).reshape(B, S, KV, hd)
    if cos is not None:
        q, k = L.apply_rope(q, cos, sin), L.apply_rope(k, cos, sin)
    window = cfg.window if spec.mixer == SWA else 0
    scale = cfg.attn_scale or None  # None: 1/sqrt(head_dim)
    if _use_seq_parallel(ctx, S):
        out = _attention_seq_parallel(
            q, k, v, ctx, causal=cfg.causal, window=window, cap=cfg.attn_softcap,
            scale=scale,
        )
        return out.reshape(B, S, H * hd) @ p["wo"], (k, v)
    if ctx.mesh is not None and ctx.model_axis and not ctx.seq_shard:
        tp = ctx.mesh.shape[ctx.model_axis]
        b = ctx.batch_axes if ctx.batch_shardable else None
        if H % tp == 0:
            q = ctx.constrain(q, P(b, None, ctx.model_axis, None))
        if KV % tp == 0:
            k = ctx.constrain(k, P(b, None, ctx.model_axis, None))
            v = ctx.constrain(v, P(b, None, ctx.model_axis, None))
    out = L.attention(
        q, k, v, causal=cfg.causal, window=window, cap=cfg.attn_softcap, scale=scale,
        direct_threshold=(1 << 30) if ctx.unroll else 1024,
    )
    return out.reshape(B, S, H * hd) @ p["wo"], (k, v)


def _mla_seq(cfg, p, h, cos, sin, ctx: ShardCtx):
    """MLA over a sequence: (output, (latent, roped shared key)), one entry
    per position."""
    B, S, D = h.shape
    H = cfg.n_heads
    nope, rope, vh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q = _mla_q(cfg, p, h)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    dkv = h @ p["wdkv"]  # (B,S,kvr+rope)
    ckv = L.rms_norm(dkv[..., : cfg.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
    k_rope = dkv[..., cfg.kv_lora_rank :].reshape(B, S, 1, rope)
    if cos is not None:
        q_rope = L.apply_rope(q_rope, cos, sin)
        k_rope = L.apply_rope(k_rope, cos, sin)
    # The latent part: keys and values expanded from the latent, attended.
    with jax.named_scope("latent_attention"):
        k_nope = (ckv @ p["wuk"]).reshape(B, S, H, nope)
        v = (ckv @ p["wuv"]).reshape(B, S, H, vh)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        scale = _mla_scale(cfg)
        if _use_seq_parallel(ctx, S):
            out = _attention_seq_parallel(
                q, k, v, ctx, causal=cfg.causal, window=0, cap=cfg.attn_softcap,
                scale=scale,
            )
        else:
            out = L.attention(q, k, v, causal=cfg.causal, window=0, cap=cfg.attn_softcap,
                              scale=scale,
                              direct_threshold=(1 << 30) if ctx.unroll else 1024)
    return out.reshape(B, S, H * vh) @ p["wo"], (ckv, k_rope[:, :, 0, :])


def _seq_mixer(cfg, spec, p, h, cos, sin, ctx: ShardCtx, with_cache: bool):
    """The mixer of the sequence pass: (output, the layer's decode cache where
    ``with_cache``, else None)."""
    S = h.shape[1]
    if spec.mixer == MAMBA:
        if _use_seq_parallel(ctx, S):
            S_loc = S // ctx.mesh.shape[ctx.model_axis]
            out, state = mamba_mixer_seq_parallel(
                p, h, cfg, ctx, chunk=(S_loc if ctx.unroll else min(128, S_loc))
            )
        else:
            out, state = mamba_sequence(p, h, cfg, chunk=(S if ctx.unroll else 128))
        return out, (state if with_cache else None)
    if spec.mixer == MAMBA2:
        out, state = mamba2_sequence(p, h, cfg, unroll=ctx.scan_unroll)
        return out, (state if with_cache else None)
    if spec.mixer == MLA:
        out, (ckv, krope) = _mla_seq(cfg, p, h, cos, sin, ctx)
        return out, ({"ckv": ckv, "krope": krope} if with_cache else None)
    out, (k, v) = _attn_seq(cfg, spec, p, h, cos, sin, ctx)
    if not with_cache:
        return out, None
    with jax.named_scope("kv_update"):
        if spec.mixer == SWA:
            w = min(cfg.window, S)
            k, v = k[:, -w:], v[:, -w:]
            kpos = jnp.arange(S - w, S, dtype=jnp.int32)
        else:
            kpos = jnp.arange(S, dtype=jnp.int32)
    return out, {"k": k, "v": v, "kpos": kpos}


_MOE_KEYS = ("router", "moe_gate", "moe_up", "moe_down",
             "shared_gate", "shared_up", "shared_down")


def _mlp_apply(cfg, spec, p, h, ctx: ShardCtx):
    with jax.named_scope("moe" if spec.mlp == MOE else "mlp"):
        if spec.mlp == MOE:
            ep = ctx.ep_info(cfg)
            sub = {k2: p[k2] for k2 in _MOE_KEYS if k2 in p}
            if ep is not None:
                fn = shard_map(
                    lambda pr, xr: moe_block(pr, xr, cfg, ep),
                    mesh=ctx.mesh,
                    in_specs=(
                        {k2: P(ctx.model_axis) if k2.startswith("moe_") else P()
                         for k2 in sub},
                        P(*ctx.token_pspec, None, None),
                    ),
                    out_specs=P(*ctx.token_pspec, None, None),
                )
                return fn(sub, h)
            return moe_block(sub, h, cfg, None)
        return L.mlp(p, h, cfg.activation)


def _residual(cfg: ModelConfig, h):
    """A residual branch's output as it joins the stream: times
    ``cfg.residual_multiplier`` where the config has one."""
    if cfg.residual_multiplier != 1.0:
        h = h * jnp.asarray(cfg.residual_multiplier, h.dtype)
    return h


def _layer(cfg, spec: LayerSpec, p, x, mix, ctx: ShardCtx, *, constrain: bool = True):
    """One layer in every mode, on weights in the compute dtype: pre-norm,
    the mode's mixer ``mix`` (normed hidden states -> (output, the layer's
    cache)) under ``attention``, sandwich norm where the config has it,
    residual; then the MLP or MoE the same way.  ``constrain`` pins the
    residual stream's sharding after each residual, as the sequence pass
    does; decode leaves it to XLA.  Returns (hidden states, the layer's cache)."""
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    with jax.named_scope("attention"):
        h, cache = mix(h)
    if cfg.sandwich_norm:
        h = L.rms_norm(h, p["post_ln1"], cfg.norm_eps)
    x = x + _residual(cfg, h)
    if constrain:
        x = ctx.constrain(x, ctx.hidden_spec())
    if spec.mlp != NONE:
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        h = _mlp_apply(cfg, spec, p, h, ctx)
        if cfg.sandwich_norm:
            h = L.rms_norm(h, p["post_ln2"], cfg.norm_eps)
        x = x + _residual(cfg, h)
        if constrain:
            x = ctx.constrain(x, ctx.hidden_spec())
    return x, cache


# ------------------------------------------------------------ sequence pass
def _stacks(cfg: ModelConfig):
    """(key, layout) of each scanned stack of layers, in order: the leading
    dense layers where the config has them, then the periods.  Params and
    caches hold a stack's per-position entries under its key."""
    lead = [("lead", (cfg.lead_spec,))] if cfg.first_k_dense else []
    return lead + [("layers", cfg.layout)]


def _sequence_pass(cfg: ModelConfig, params, batch, ctx: ShardCtx, with_cache: bool,
                   max_seq: Optional[int] = None):
    """Embedding, then one scan over each stack's periods: (final hidden
    states, the decode cache or None).  With ``with_cache`` (static) the
    scans' ys are the layers' caches, grown to ``max_seq`` decode slots;
    without it they carry nothing."""
    with jax.named_scope("embed"):
        x = embed_tokens(cfg, params, batch)
    B, S, _ = x.shape
    positions = _positions(cfg, batch, B, S)
    cos, sin = _rope_cos_sin(cfg, positions)
    x = ctx.constrain(x, ctx.hidden_spec())

    def layer(p, xc, spec):
        with jax.named_scope("cast_params"):
            p = _cast_block_params(p, cfg.compute_dtype)
        mix = functools.partial(_seq_mixer, cfg, spec, p, cos=cos, sin=sin, ctx=ctx,
                                with_cache=with_cache)
        return _layer(cfg, spec, p, xc, mix, ctx)

    cache = {"pos": jnp.asarray(S, jnp.int32)} if with_cache else None
    for key, layout in _stacks(cfg):
        def body(xc, period_params, layout=layout):
            caches = []
            for i, spec in enumerate(layout):
                f = functools.partial(layer, spec=spec)
                if ctx.remat == "block" and len(layout) > 1:
                    # nested remat: multi-layer periods (jamba: 8 layers) would
                    # otherwise hold the whole period's intermediates in the
                    # backward working set (measured 25 GiB of temps at 52B)
                    f = jax.checkpoint(f)
                xc, c = f(period_params[i], xc)
                caches.append(c)
            return xc, caches

        if ctx.remat == "block":
            body = jax.checkpoint(body)
        x, caches = jax.lax.scan(body, x, params[key], unroll=ctx.scan_unroll)
        if with_cache:
            if max_seq is not None and max_seq != S:
                with jax.named_scope("kv_update"):
                    caches = _expand_prefill_cache(cfg, layout, caches, S, max_seq)
            cache[key] = caches
    return x, cache


def forward(cfg: ModelConfig, params, batch, ctx: ShardCtx = ShardCtx()) -> jnp.ndarray:
    x, _ = _sequence_pass(cfg, params, batch, ctx, with_cache=False)
    logits = unembed(cfg, params, x)
    return logits[..., : cfg.vocab]  # crop padding (API surface only)


def loss_and_metrics(
    cfg: ModelConfig, params, batch, ctx: ShardCtx = ShardCtx(), ce_chunk: int = 1024
):
    """Next-token CE with sequence-chunked unembedding.

    Full logits of a 256k-vocab model are (B·S·V) — tens of GB per device at
    train_4k.  Chunking the unembed+CE over the sequence (with remat) keeps
    live logits at (B, chunk, V); the backward pass recomputes each chunk's
    logits from the final hidden states.
    """
    x, _ = _sequence_pass(cfg, params, batch, ctx, with_cache=False)
    B, S, _ = x.shape
    labels = batch["labels"]
    cs = min(ce_chunk, S)
    if S % cs != 0:
        cs = S  # fall back to unchunked
    nc = S // cs
    xr = x.reshape(B, nc, cs, -1).transpose(1, 0, 2, 3)
    lr = labels.reshape(B, nc, cs).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk(carry, inp):
        xc, lc = inp
        logits = unembed(cfg, params, xc)
        logp = jax.nn.log_softmax(logits, axis=-1)
        tl = -jnp.take_along_axis(logp, lc[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        hit = ((logits.argmax(-1) == lc) * mask).sum()
        lsum, msum, hsum = carry
        return (lsum + (tl * mask).sum(), msum + mask.sum(), hsum + hit), None

    (lsum, msum, hits), _ = jax.lax.scan(
        chunk, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())), (xr, lr),
        unroll=ctx.scan_unroll,
    )
    loss = lsum / jnp.maximum(msum, 1.0)
    return loss, {"loss": loss, "accuracy": hits / jnp.maximum(msum, 1.0), "tokens": msum}


def prefill(
    cfg: ModelConfig, params, batch, ctx: ShardCtx = ShardCtx(),
    max_seq: Optional[int] = None,
):
    """Sequence pass returning (last-position logits, populated cache), its
    ops scoped like ``decode_step``'s."""
    x, cache = _sequence_pass(cfg, params, batch, ctx, with_cache=True, max_seq=max_seq)
    with jax.named_scope("lm_head"):
        logits = unembed(cfg, params, x[:, -1:])
    return logits, cache


# ------------------------------------------------------------------- cache
def _layer_cache(cfg: ModelConfig, spec: LayerSpec, n: int, batch: int, max_seq: int):
    """Zeroed decode cache of one layout position, stacked over ``n`` layers."""
    dt = cfg.compute_dtype
    if spec.mixer == MAMBA:
        return {
            "h": jnp.zeros((n, batch, cfg.d_inner, cfg.ssm_d_state), jnp.float32),
            "conv": jnp.zeros((n, batch, cfg.ssm_d_conv - 1, cfg.d_inner), dt),
        }
    if spec.mixer == MAMBA2:
        return {
            "h": jnp.zeros((n, batch, cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_d_state),
                           jnp.float32),
            "conv": jnp.zeros((n, batch, cfg.ssm_d_conv - 1, cfg.ssm_conv_dim), dt),
        }
    if spec.mixer == MLA:
        return {
            "ckv": jnp.zeros((n, batch, max_seq, cfg.kv_lora_rank), dt),
            "krope": jnp.zeros((n, batch, max_seq, cfg.qk_rope_dim), dt),
        }
    Sc = min(max_seq, cfg.window) if spec.mixer == SWA else max_seq
    return {
        "k": jnp.zeros((n, batch, Sc, cfg.n_kv_heads, cfg.head_dim), dt),
        "v": jnp.zeros((n, batch, Sc, cfg.n_kv_heads, cfg.head_dim), dt),
        "kpos": jnp.full((n, Sc), -1, jnp.int32),
    }


# The leaves ``_layer_cache`` makes, by kind: MLA's latent, attention's K/V
# (and their positions), a state-space layer's state.
_CACHE_KINDS = {"ckv": "latent", "krope": "latent", "k": "kv", "v": "kv", "kpos": "kv",
                "h": "state", "conv": "state"}
# The sequence axis of each positional (latent, kv) leaf, stacked over layers:
# where a decode step writes its row.
_ROW_AXIS = {"ckv": 2, "krope": 2, "k": 2, "v": 2, "kpos": 1}


def cache_bytes_by_kind(cache) -> Dict[str, int]:
    """Bytes of a decode cache (arrays or shapes) by kind: latent, kv, state."""
    out = {kind: 0 for kind in ("latent", "kv", "state")}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        kind = _CACHE_KINDS.get(path[-1].key)  # every cache leaf is a dict entry
        if kind:
            out[kind] += leaf.size * leaf.dtype.itemsize
    return out


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Dict[str, Any]:
    """Zeroed decode cache; stacked over periods per layout position, and
    over the leading dense layers under ``lead``."""
    out = {"pos": jnp.zeros((), jnp.int32),
           "layers": [_layer_cache(cfg, s, cfg.n_periods, batch, max_seq) for s in cfg.layout]}
    if cfg.first_k_dense:
        out["lead"] = [_layer_cache(cfg, cfg.lead_spec, cfg.first_k_dense, batch, max_seq)]
    return out


def _attn_decode(cfg, spec, p, h, cache, pos, cos, sin, ctx):
    """One-token attention against the layer's (possibly ring-buffered)
    cache slice, read in place: the slot the new row takes is masked, a
    ring's stale entry with it, and the new k/v, rounded to the cache dtype,
    are a second block combined by the online-softmax rule.  Returns
    (output, the layer's new row of each cache leaf)."""
    B = h.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ p["wq"]).reshape(B, 1, H, hd)
    k = (h @ p["wk"]).reshape(B, 1, KV, hd)
    v = (h @ p["wv"]).reshape(B, 1, KV, hd)
    if cos is not None:
        q, k = L.apply_rope(q, cos, sin), L.apply_rope(k, cos, sin)
    Sc = cache["k"].shape[1]  # cache slice inside scan: (B, Sc, KV, hd)
    slot = pos % Sc  # ring for SWA; plain index otherwise (pos < Sc)
    row = {"k": k[:, 0].astype(cache["k"].dtype), "v": v[:, 0].astype(cache["v"].dtype),
           "kpos": pos}
    kpos = jnp.where(jnp.arange(Sc) == slot, -1, cache["kpos"])
    attend = functools.partial(
        L.attention_partial, q, causal=True, window=cfg.window if spec.mixer == SWA else 0,
        cap=cfg.attn_softcap, scale=cfg.attn_scale or 1.0 / math.sqrt(hd),
        qpos=jnp.full((1, 1), pos),
    )
    acc, m, l = attend(cache["k"], cache["v"], kpos=kpos[None, :])
    acc_new, m_new, l_new = attend(row["k"][:, None], row["v"][:, None],
                                   kpos=jnp.full((1, 1), pos))
    m_all = jnp.maximum(m, m_new)
    r, r_new = jnp.exp(m - m_all), jnp.exp(m_new - m_all)
    acc = acc * r[..., None] + acc_new * r_new[..., None]
    l = l * r + l_new * r_new
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,KV,G,1,hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, 1, H * hd).astype(h.dtype)
    return out @ p["wo"], row


def _mla_decode(cfg, spec, p, h, cache, pos, cos, sin, ctx):
    """Absorbed-matrix MLA decode on the compressed latent cache, read in
    place: slots at and past ``pos`` are masked, and the new latent and
    roped key, rounded to the cache dtype, join as one more score and value
    term.  Returns (output, the layer's new row of each cache leaf)."""
    B = h.shape[0]
    H = cfg.n_heads
    nope, rope, vh, kvr = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    q = _mla_q(cfg, p, h)  # (B,1,H,nope+rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    dkv = h @ p["wdkv"]
    ckv_new = L.rms_norm(dkv[..., :kvr], p["kv_ln"], cfg.norm_eps)  # (B,1,kvr)
    krope_new = dkv[..., kvr:].reshape(B, 1, 1, rope)
    if cos is not None:
        q_rope = L.apply_rope(q_rope, cos, sin)
        krope_new = L.apply_rope(krope_new, cos, sin)
    row = {"ckv": ckv_new[:, 0].astype(cache["ckv"].dtype),
           "krope": krope_new[:, 0, 0].astype(cache["krope"].dtype)}
    # absorb W_uk into q:  q_eff (B,1,H,kvr)
    wuk = p["wuk"].reshape(kvr, H, nope)
    q_eff = jnp.einsum("bqhn,khn->bqhk", q_nope, wuk)
    # The latent part: scores, softmax and values over the latent cache.
    with jax.named_scope("latent_attention"):
        q_eff, q_rope = q_eff.astype(jnp.float32), q_rope.astype(jnp.float32)
        ckv = cache["ckv"].astype(jnp.float32)
        ckv_row = row["ckv"].astype(jnp.float32)
        scores = jnp.einsum("bqhk,bsk->bhqs", q_eff, ckv)
        scores += jnp.einsum("bqhr,bsr->bhqs", q_rope, cache["krope"].astype(jnp.float32))
        score_new = jnp.einsum("bqhk,bk->bhq", q_eff, ckv_row)
        score_new += jnp.einsum("bqhr,br->bhq", q_rope, row["krope"].astype(jnp.float32))
        scores = jnp.concatenate([scores, score_new[..., None]], axis=-1)
        scores *= _mla_scale(cfg)
        scores = L.softcap(scores, cfg.attn_softcap)
        Sc = ckv.shape[1]
        s = jnp.arange(Sc + 1)[None, None, None, :]
        scores = jnp.where((s < pos) | (s == Sc), scores, L.NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        lat = jnp.einsum("bhqs,bsk->bqhk", probs[..., :Sc], ckv)  # (B,1,H,kvr)
        lat += jnp.einsum("bhq,bk->bqhk", probs[..., Sc], ckv_row)
    wuv = p["wuv"].reshape(kvr, H, vh)
    out = jnp.einsum("bqhk,khv->bqhv", lat, wuv).reshape(B, 1, H * vh).astype(h.dtype)
    return out @ p["wo"], row


def _decode_mixer(cfg, spec, p, h, cache, pos, cos, sin, ctx):
    """The mixer of a decode step: (output, the layer's new cache rows, or
    a state layer's whole new state)."""
    if spec.mixer == MAMBA:
        return mamba_decode(p, h, cache, cfg)
    if spec.mixer == MAMBA2:
        return mamba2_decode(p, h, cache, cfg)
    if spec.mixer == MLA:
        return _mla_decode(cfg, spec, p, h, cache, pos, cos, sin, ctx)
    return _attn_decode(cfg, spec, p, h, cache, pos, cos, sin, ctx)


def decode_step(
    cfg: ModelConfig, params, cache, tokens: jnp.ndarray, ctx: ShardCtx = ShardCtx()
):
    """One decode step: tokens (B, 1) -> (logits (B, 1, V), new cache).

    Its ops carry the name scopes ``embed``, ``cast_params``, ``attention``
    (with ``kv_update``, ``latent_attention`` in MLA and ``ssm_state`` in
    Mamba-2), ``moe`` (with
    ``routed_experts`` and ``shared_expert``) or ``mlp``, and ``lm_head``:
    stable names for a profiler trace's per-op reduction."""
    pos = cache["pos"]
    with jax.named_scope("embed"):
        x = embed_tokens(cfg, params, {"tokens": tokens})
    B = x.shape[0]
    positions = (
        jnp.broadcast_to(pos, (3, B, 1)) if cfg.pos == "mrope"
        else jnp.full((B, 1), pos)
    )
    cos, sin = _rope_cos_sin(cfg, positions)
    new = {"pos": pos + 1}
    for key, layout in _stacks(cfg):
        # The compute-dtype copies of every layer's weights, made before the
        # layer scan: XLA hoists a cast out of the scan, and the cast it
        # hoists keeps no name scope.  On ``compute_params``' copy there is
        # nothing to cast.
        with jax.named_scope("cast_params"):
            layers = [_cast_block_params(p, cfg.compute_dtype) for p in params[key]]

        def body(xc, slices, layout=layout):
            period_params, period_cache = slices
            rows = []
            for i, spec in enumerate(layout):
                mix = functools.partial(_decode_mixer, cfg, spec, period_params[i],
                                        cache=period_cache[i], pos=pos, cos=cos, sin=sin,
                                        ctx=ctx)
                xc, r = _layer(cfg, spec, period_params[i], xc, mix, ctx, constrain=False)
                rows.append(r)
            return xc, rows

        # The scan reads the cache and returns rows: written inside it, the
        # stack or each layer's slice would be copied whole every step.
        x, rows = jax.lax.scan(body, x, (layers, cache[key]), unroll=ctx.scan_unroll)
        with jax.named_scope("kv_update"):
            new[key] = [_write_rows(c, r, pos) for c, r in zip(cache[key], rows)]
    with jax.named_scope("lm_head"):
        logits = unembed(cfg, params, x)
    return logits, new


def _write_rows(layer_cache, rows, pos):
    """A layout position's cache after a decode step, from its layer scan's
    ys: a positional leaf (latent, kv) gets every layer's new row written in
    place at slot ``pos % Sc``; a state leaf is the scan's whole new state."""
    out = {}
    for name, leaf in layer_cache.items():
        if _CACHE_KINDS[name] == "state":
            out[name] = rows[name]
            continue
        axis = _ROW_AXIS[name]
        out[name] = jax.lax.dynamic_update_index_in_dim(leaf, rows[name],
                                                        pos % leaf.shape[axis], axis)
    return out


def _expand_prefill_cache(cfg: ModelConfig, layout, layer_caches, S: int, max_seq: int):
    """Grow prefill caches to max_seq decode slots, ring-aligned for SWA."""
    out = []
    for spec, c in zip(layout, layer_caches):
        if spec.mixer in (MAMBA, MAMBA2):
            out.append(c)
            continue
        if spec.mixer == MLA:
            pad = max_seq - c["ckv"].shape[2]
            if pad > 0:
                c = {
                    "ckv": jnp.pad(c["ckv"], ((0, 0), (0, 0), (0, pad), (0, 0))),
                    "krope": jnp.pad(c["krope"], ((0, 0), (0, 0), (0, pad), (0, 0))),
                }
            out.append(c)
            continue
        w = c["k"].shape[2]  # stored length after prefill
        Sc = min(max_seq, cfg.window) if spec.mixer == SWA else max_seq
        if Sc == w:
            if S > w:  # ring-align: position p must live in slot p % w
                sh = S % w
                c = {
                    "k": jnp.roll(c["k"], sh, axis=2),
                    "v": jnp.roll(c["v"], sh, axis=2),
                    "kpos": jnp.roll(c["kpos"], sh, axis=1),
                }
        else:
            assert Sc > w, (Sc, w)
            NP, B = c["k"].shape[0], c["k"].shape[1]
            KV, hd = c["k"].shape[3], c["k"].shape[4]
            k = jnp.zeros((NP, B, Sc, KV, hd), c["k"].dtype)
            v = jnp.zeros((NP, B, Sc, KV, hd), c["v"].dtype)
            kpos = jnp.full((NP, Sc), -1, jnp.int32)
            off = S - w  # slots == positions (no wrap: S <= Sc here)
            c = {
                "k": jax.lax.dynamic_update_slice(k, c["k"], (0, 0, off, 0, 0)),
                "v": jax.lax.dynamic_update_slice(v, c["v"], (0, 0, off, 0, 0)),
                "kpos": jax.lax.dynamic_update_slice(kpos, c["kpos"], (0, off)),
            }
        out.append(c)
    return out

