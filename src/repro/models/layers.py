"""Shared neural layers: norms, RoPE/M-RoPE, attention (direct + chunked).

Attention supports GQA/MQA grouping, causal & bidirectional, sliding-window,
and logit softcapping — covering gemma(2), danube (SWA), hubert (encoder),
qwen* and jamba's attention layers.  Two execution paths:

  * direct   — one einsum; used for short sequences and decode.
  * chunked  — flash-style online-softmax double scan over (q, kv) blocks;
               the pure-XLA analogue of kernels/flash_attention.py, needed so
               32k/500k-token cells compile without materializing S² scores.

kernels/ is not on the model path: no model mode calls its Pallas kernels.
tests/test_kernels.py checks kernels/flash_attention.py against kernels/ref.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return jnp.tanh(x / cap) * cap if cap > 0 else x


# ------------------------------------------------------------------- RoPE
def rope_cos_sin(
    positions: jnp.ndarray, dim: int, theta: float = 10000.0,
    inv_freq: Optional[jnp.ndarray] = None, scale: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions (...,) -> cos/sin (..., dim//2), θ^(−2i/dim) unless
    ``inv_freq`` is given, both times ``scale``."""
    half = dim // 2
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1·mscale·ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(
    beta_fast: float, beta_slow: float, dim: int, theta: float, original_max_pos: int
) -> Tuple[int, int]:
    """The pair indices between which YaRN ramps from the plain to the
    interpolated frequency (DeepSeek's ``yarn_find_correction_range``)."""

    def pair(rotations):
        return dim * math.log(original_max_pos / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    return max(math.floor(pair(beta_fast)), 0), min(math.ceil(pair(beta_slow)), dim - 1)


def yarn_inv_freq(
    dim: int, theta: float, factor: float, original_max_pos: int,
    beta_fast: float, beta_slow: float,
) -> jnp.ndarray:
    """YaRN inverse frequencies over ``dim`` rope dims: θ^(−2i/dim) below
    the correction range, θ^(−2i/dim)/factor above it, a linear ramp
    between."""
    half = dim // 2
    lo, hi = yarn_correction_range(beta_fast, beta_slow, dim, theta, original_max_pos)
    hi = hi + 0.001 if lo == hi else hi
    extra = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo) / (hi - lo), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """x (B, S, H, hd); cos/sin (B, S, hd//2) — rotate-half convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def mrope_cos_sin(
    pos3: jnp.ndarray, dim: int, sections: Tuple[int, int, int], theta: float
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Qwen2-VL multimodal RoPE: pos3 (3, B, S); sections are pair counts
    per (temporal, height, width) summing to dim//2."""
    half = dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos3.astype(jnp.float32)[..., None] * freqs  # (3, B, S, half)
    sec_id = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=half
    )
    ang = jnp.take_along_axis(ang, sec_id[None, None, None, :].astype(jnp.int32), axis=0)[0]
    return jnp.cos(ang), jnp.sin(ang)


def text_positions(batch: int, seq: int, offset=0) -> jnp.ndarray:
    return jnp.arange(seq, dtype=jnp.int32)[None, :] + jnp.zeros((batch, 1), jnp.int32) + offset


def mrope_text_positions(batch: int, seq: int, offset=0) -> jnp.ndarray:
    """Degenerate (t=h=w) M-RoPE positions for text-only streams."""
    p = text_positions(batch, seq, offset)
    return jnp.broadcast_to(p[None], (3, batch, seq))


# -------------------------------------------------------------- attention
def _mask_bias(
    qpos: jnp.ndarray,
    kpos: jnp.ndarray,
    causal: bool,
    window: int,
    kv_len: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """(…, Sq, Sk) additive bias from query/key absolute positions."""
    q = qpos[..., :, None]
    k = kpos[..., None, :]
    ok = k >= 0  # kpos = -1 marks unwritten cache slots
    ok = jnp.broadcast_to(ok, jnp.broadcast_shapes(q.shape, k.shape))
    if causal:
        ok = ok & (k <= q)
    if window > 0:
        ok &= (q - k) < window
    if kv_len is not None:
        ok &= k < kv_len
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray, scale: float) -> jnp.ndarray:
    """q (B,Sq,H,hd) k (B,Sk,KV,hd) -> scores (B,KV,G,Sq,Sk)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    return jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)) * scale


def attention_direct(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    cap: float = 0.0,
    scale: Optional[float] = None,
    qpos: Optional[jnp.ndarray] = None,
    kpos: Optional[jnp.ndarray] = None,
    kv_len: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Materialized-scores attention. q (B,Sq,H,hd), k/v (B,Sk,KV,hd)."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if qpos is None:
        qpos = jnp.arange(Sq)[None]
    if kpos is None:
        kpos = jnp.arange(Sk)[None]
    s = _gqa_scores(q, k, scale)  # (B,KV,G,Sq,Sk) fp32
    s = softcap(s, cap)
    s = s + _mask_bias(qpos, kpos, causal, window, kv_len)[:, None, None]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)  # v dim ≠ qk dim in MLA


def attention_partial(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool,
    window: int,
    cap: float,
    scale: float,
    qpos: jnp.ndarray,
    kpos: jnp.ndarray,
    kv_len: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Unnormalized attention over a KV shard: returns (acc, m, l).

    Used by GQA decode (models/model.py ``_attn_decode``) and the chunked
    path below, which combines blocks as
    out = Σ_blocks acc·e^{m−m*} / Σ_blocks l·e^{m−m*}.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    s = _gqa_scores(q, k, scale)
    s = softcap(s, cap)
    s = s + _mask_bias(qpos, kpos, causal, window, kv_len)[:, None, None]
    m = jnp.max(s, axis=-1)  # (B,KV,G,Sq)
    p = jnp.exp(s - m[..., None])
    # rows that saw only masked keys: zero contribution
    dead = m <= NEG_INF / 2
    p = jnp.where(dead[..., None], 0.0, p)
    m = jnp.where(dead, NEG_INF, m)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    return acc, m, l


def attention_chunked(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    cap: float = 0.0,
    scale: Optional[float] = None,
    q_offset: jnp.ndarray | int = 0,
    k_offset: jnp.ndarray | int = 0,
    chunk_q: int = 512,
    chunk_k: int = 1024,
) -> jnp.ndarray:
    """Flash-style online-softmax attention, O(S·chunk) live memory.

    Double lax.scan over query and key blocks with a rematerialized inner
    body — the XLA-portable twin of kernels/flash_attention.py.
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    cq = min(chunk_q, Sq)
    ck = min(chunk_k, Sk)
    assert Sq % cq == 0 and Sk % ck == 0, (Sq, cq, Sk, ck)
    nq, nk = Sq // cq, Sk // ck
    G = H // KV

    qr = q.reshape(B, nq, cq, H, hd).transpose(1, 0, 2, 3, 4)
    kr = k.reshape(B, nk, ck, KV, hd).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(B, nk, ck, KV, v.shape[-1]).transpose(1, 0, 2, 3, 4)  # MLA: v dim ≠ qk dim

    @jax.checkpoint
    def kv_step(carry, inp):
        m, l, acc, qb, qp = carry
        kb, vb, kp = inp
        a, mb, lb = attention_partial(
            qb, kb, vb, causal=causal, window=window, cap=cap, scale=scale,
            qpos=qp, kpos=kp,
        )
        m_new = jnp.maximum(m, mb)
        r_old = jnp.exp(m - m_new)
        r_new = jnp.exp(mb - m_new)
        acc = acc * r_old[..., None] + a * r_new[..., None]
        l = l * r_old + lb * r_new
        return (m_new, l, acc, qb, qp), None

    def q_step(_, inp):
        qi, qb = inp
        qp = (jnp.arange(cq) + qi * cq + q_offset)[None]
        m0 = jnp.full((B, KV, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, cq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, cq, v.shape[-1]), jnp.float32)
        kps = (
            jnp.arange(nk)[:, None] * ck + jnp.arange(ck)[None, :] + k_offset
        )[:, None, :]  # (nk, 1, ck)
        (m, l, acc, _, _), _ = jax.lax.scan(
            kv_step, (m0, l0, a0, qb, qp), (kr, vr, kps)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.transpose(0, 3, 1, 2, 4).reshape(B, cq, H, v.shape[-1]).astype(q.dtype)

    _, blocks = jax.lax.scan(q_step, None, (jnp.arange(nq), qr))
    return blocks.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, v.shape[-1])


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    cap: float = 0.0,
    scale: Optional[float] = None,
    direct_threshold: int = 1024,
    chunk_q: int = 512,
    chunk_k: int = 1024,
) -> jnp.ndarray:
    """Dispatch: direct einsum for short S, chunked flash-style for long.

    The threshold keeps materialized (…, Sq, Sk) scores ≤ ~direct² per
    (batch, head); above it the online-softmax path caps live memory at
    (…, chunk_q, chunk_k) — at train_4k a 256-vocab-head-replicated arch
    would otherwise stage ~17 GiB of fp32 scores per device (measured)."""
    Sq, Sk = q.shape[1], k.shape[1]
    if max(Sq, Sk) <= direct_threshold or Sq % min(chunk_q, Sq) or Sk % min(chunk_k, Sk):
        return attention_direct(
            q, k, v, causal=causal, window=window, cap=cap, scale=scale
        )
    return attention_chunked(
        q, k, v, causal=causal, window=window, cap=cap, scale=scale,
        chunk_q=chunk_q, chunk_k=chunk_k,
    )


# --------------------------------------------------------------------- MLP
def mlp(p, x, activation: str) -> jnp.ndarray:
    if activation == "silu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif activation == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"], approximate=True) * (x @ p["w_up"])
    else:  # plain dense gelu (hubert)
        h = jax.nn.gelu(x @ p["w_up"], approximate=True)
    return h @ p["w_down"]
