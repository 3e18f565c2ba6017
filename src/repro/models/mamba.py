"""Selective SSM blocks: Mamba-1 (falcon-mamba, jamba's mamba layers) and
Mamba-2's SSD (granite-4.0-h's mamba layers).

Training path: chunked *parallel* associative scan — within a chunk the
linear recurrence h_t = a_t h_{t-1} + b_t is evaluated with
``lax.associative_scan`` (log-depth, TPU-friendly), chunks are threaded
sequentially with only the boundary state carried (so backward memory is
O(S/Lc · B · d_inner · d_state) instead of O(S · ...)).  The Pallas kernel
in kernels/mamba_scan.py is not on this path: no model mode calls it.

Mamba-2 (``mamba2_sequence``): heads of a scalar decay each, B and C shared
by a group's heads, scanned chunk by chunk: within a chunk the quadratic
(attention-like) form, then the chunk's state, passed on to the next one.
A chunk's (heads, Lc, Lc) decay matrix is built inside the scan body, never
for the whole sequence.

Both sequence mixers return the layer's decode state with their output:
the SSM's last state and the last ``d_conv - 1`` inputs of the causal conv.

Decode path: O(1) single-step state update (the reason falcon-mamba/jamba
run the long_500k cell).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from .common import ModelConfig
from .layers import rms_norm


def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv. x (B,S,di), w (K,di), b (di,)."""
    K = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for j in range(K):  # K is tiny (4): unrolled taps
        out = out + pad[:, j : j + x.shape[1]] * w[j]
    return out + b


def conv_tail(x: jnp.ndarray, K: int) -> jnp.ndarray:
    """The last K-1 inputs of a causal conv over x (B,S,C), zeros before
    the sequence: the decode state's conv window after the sequence."""
    return jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))[:, -(K - 1):]


def _ssm_scan_chunked(
    a: jnp.ndarray,  # (B, S, di, st)  decay  exp(dt*A)
    b: jnp.ndarray,  # (B, S, di, st)  input  dt*B*x
    C: jnp.ndarray,  # (B, S, st)
    h0: Optional[jnp.ndarray] = None,  # (B, di, st)
    chunk: int = 128,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,S,di), h_last (B,di,st)). y_t = C_t · h_t."""
    B, S, di, st = a.shape
    Lc = min(chunk, S)
    assert S % Lc == 0, (S, Lc)
    nc = S // Lc
    ar = a.reshape(B, nc, Lc, di, st).transpose(1, 0, 2, 3, 4)
    br = b.reshape(B, nc, Lc, di, st).transpose(1, 0, 2, 3, 4)
    Cr = C.reshape(B, nc, Lc, st).transpose(1, 0, 2, 3)
    if h0 is None:
        h0 = jnp.zeros((B, di, st), a.dtype)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    @jax.checkpoint
    def chunk_body(h, inp):
        ac, bc, cc = inp  # (B, Lc, di, st), (B, Lc, st)
        A_cum, B_cum = jax.lax.associative_scan(combine, (ac, bc), axis=1)
        hs = A_cum * h[:, None] + B_cum  # (B, Lc, di, st)
        y = jnp.einsum("blds,bls->bld", hs, cc)
        return hs[:, -1], y

    h_last, ys = jax.lax.scan(chunk_body, h0, (ar, br, Cr))
    y = ys.transpose(1, 0, 2, 3).reshape(B, S, di)
    return y, h_last


def _ssm_scan_fused(
    dt: jnp.ndarray,  # (B, S, di)
    x: jnp.ndarray,  # (B, S, di)  post-conv activations
    Bm: jnp.ndarray,  # (B, S, st)
    Cm: jnp.ndarray,  # (B, S, st)
    A: jnp.ndarray,  # (di, st)
    h0: Optional[jnp.ndarray] = None,
    chunk: int = 128,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked scan with the (B,S,di,st) decay/drive tensors built INSIDE the
    rematted chunk body — never materialized for the full sequence (a 4k×8k
    mamba layer would otherwise stage ~2 GiB/device per tensor; measured as a
    97 GiB/device dry-run before this restructuring)."""
    B, S, di = dt.shape
    st = Bm.shape[-1]
    Lc = min(chunk, S)
    assert S % Lc == 0, (S, Lc)
    nc = S // Lc
    r = lambda t: t.reshape((B, nc, Lc) + t.shape[2:]).transpose(1, 0, 2, 3)
    if h0 is None:
        h0 = jnp.zeros((B, di, st), jnp.float32)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    @jax.checkpoint
    def chunk_body(h, inp):
        dtc, xc, bc, cc = inp  # (B,Lc,di), (B,Lc,di), (B,Lc,st), (B,Lc,st)
        a = jnp.exp(dtc.astype(jnp.float32)[..., None] * A)  # (B,Lc,di,st)
        b = (dtc * xc).astype(jnp.float32)[..., None] * bc.astype(jnp.float32)[:, :, None, :]
        A_cum, B_cum = jax.lax.associative_scan(combine, (a, b), axis=1)
        hs = A_cum * h[:, None] + B_cum
        y = jnp.einsum("blds,bls->bld", hs, cc.astype(jnp.float32))
        return hs[:, -1], y

    h_last, ys = jax.lax.scan(chunk_body, h0, (r(dt), r(x), r(Bm), r(Cm)))
    return ys.transpose(1, 0, 2, 3).reshape(B, S, di), h_last


def _h0_correction(
    dt: jnp.ndarray,  # (B, L, di)
    Cm: jnp.ndarray,  # (B, L, st)
    A: jnp.ndarray,  # (di, st)
    h_in: jnp.ndarray,  # (B, di, st)
    chunk: int = 128,
) -> jnp.ndarray:
    """y contribution of an incoming state: C_t · (A_cum_t · h_in), where
    A_cum_t = exp(A · cumsum(Δt)) — closed form because a_t = exp(Δt_t·A)."""
    B, L, di = dt.shape
    csum = jnp.cumsum(dt.astype(jnp.float32), axis=1)  # (B, L, di)
    Lc = min(chunk, L)
    nc = L // Lc

    # statically-unrolled chunk loop: a lax.scan here breaks grad
    # transposition inside shard_map (Manual-mesh broadcast_in_dim bug)
    @jax.checkpoint
    def body(c_chunk, C_chunk):
        acum = jnp.exp(c_chunk[..., None] * A)  # (B, Lc, di, st)
        return jnp.einsum("blds,bds,bls->bld", acum, h_in, C_chunk.astype(jnp.float32))

    ys = [
        body(csum[:, i * Lc : (i + 1) * Lc], Cm[:, i * Lc : (i + 1) * Lc])
        for i in range(nc)
    ]
    return jnp.concatenate(ys, axis=1)


def mamba_mixer_seq_parallel(
    p: Dict[str, jnp.ndarray],
    u: jnp.ndarray,  # (B, S, D) sequence-sharded over the model axis
    cfg: ModelConfig,
    ctx,  # ShardCtx
    chunk: int = 128,
) -> jnp.ndarray:
    """Sequence-parallel mamba: each model shard scans its S/tp slice; the
    cross-shard handoff is exact and cheap because chunk decay products have
    the closed form  Π_t exp(Δt_t·A) = exp(A·ΣΔt):

      1. halo exchange (K−1 tokens) for the causal conv  (ppermute, ~KB)
      2. local chunked scan from h₀ = 0                   (no comms)
      3. all-gather per-shard (exp(A·ΣΔt), h_last)        (~MBs)
      4. closed-form prefix combine + C_t·A_cum_t·h_in    (local)

    Returns (output, {"h": the last state, "conv": the conv tail}).

    Replaces the 2-psum/layer TP formulation whose (B,S,D) fp32 all-reduces
    dominate falcon-mamba's collective term."""
    import jax.numpy as _jnp
    from jax.sharding import PartitionSpec as P

    B, S, D = u.shape
    di, st, dr, K = cfg.d_inner, cfg.ssm_d_state, cfg.dt_rank, cfg.ssm_d_conv
    m_ax = ctx.model_axis
    tp = ctx.mesh.shape[m_ax]
    b = ctx.batch_axes if ctx.batch_shardable else None

    # projections under pjit: weights FSDP-gathered, activations stay
    # sequence-sharded (no TP on d_inner here).
    xz = u @ p["in_proj"]
    x, z = jnp.split(xz, 2, axis=-1)
    tail = conv_tail(x, K)

    def halo_conv(xr, cw, cb):
        left = jax.lax.ppermute(
            xr[:, -(K - 1) :], m_ax, [(i, i + 1) for i in range(tp - 1)]
        )
        xc = jnp.concatenate([left, xr], axis=1)
        out = jnp.zeros_like(xr)
        for j in range(K):
            out = out + xc[:, j : j + xr.shape[1]] * cw[j]
        return out + cb

    x = shard_map(
        halo_conv, mesh=ctx.mesh,
        in_specs=(P(b, m_ax, None), P(), P()), out_specs=P(b, m_ax, None),
        check_vma=False,
    )(x, p["conv_w"], p["conv_b"])
    x = jax.nn.silu(x)
    dbl = x @ p["x_proj"]
    dt, Bm, Cm = jnp.split(dbl, [dr, dr + st], axis=-1)
    dt = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    def sharded_scan(dtr, xr, Bmr, Cmr, A):
        i = jax.lax.axis_index(m_ax)
        y0, h_last = _ssm_scan_fused(dtr, xr, Bmr, Cmr, A, chunk=chunk)
        a_prod = jnp.exp(dtr.astype(jnp.float32).sum(axis=1)[..., None] * A)
        pair = jnp.stack([a_prod, h_last])  # (2, B_loc, di, st)
        allp = jax.lax.all_gather(pair, m_ax)  # (tp, 2, ...)
        # prefix combine, oldest -> newest (static tp-step unroll):
        #   h_in(i) = Σ_{j<i} (Π_{j<k<i} a_prod_k) · h_last_j
        h_in = jnp.zeros_like(h_last)
        for j in range(tp):
            take = (jnp.asarray(j) < i).astype(jnp.float32)
            aj = jnp.where(take > 0, allp[j, 0], jnp.ones_like(allp[j, 0]))
            h_in = h_in * aj + allp[j, 1] * take
        y_fix = _h0_correction(dtr, Cmr, A, h_in, chunk=chunk)
        # the whole sequence's last state: the combine over every shard
        h_out = jnp.zeros_like(h_last)
        for j in range(tp):
            h_out = h_out * allp[j, 0] + allp[j, 1]
        return (y0 + y_fix).astype(u.dtype), h_out

    y, h_out = shard_map(
        sharded_scan, mesh=ctx.mesh,
        in_specs=(P(b, m_ax, None),) * 4 + (P(),),
        out_specs=(P(b, m_ax, None), P(b, None, None)),
        check_vma=False,
    )(dt, x, Bm, Cm, A)
    y = y + x * p["D"]
    y = y * jax.nn.silu(z)
    return y @ p["out_proj"], {"h": h_out, "conv": tail}


def mamba_sequence(
    p: Dict[str, jnp.ndarray],
    u: jnp.ndarray,  # (B, S, d_model)
    cfg: ModelConfig,
    chunk: int = 128,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full-sequence mamba mixer (training / prefill): (output, {"h": the
    last state, "conv": the conv tail})."""
    di, st, dr = cfg.d_inner, cfg.ssm_d_state, cfg.dt_rank
    xz = u @ p["in_proj"]  # (B,S,2di)
    x, z = jnp.split(xz, 2, axis=-1)
    tail = conv_tail(x, cfg.ssm_d_conv)
    x = jax.nn.silu(causal_conv1d(x, p["conv_w"], p["conv_b"]))
    dbl = x @ p["x_proj"]  # (B,S,dr+2st)
    dt, Bm, Cm = jnp.split(dbl, [dr, dr + st], axis=-1)
    dt = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])  # (B,S,di)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (di, st)
    y, h_last = _ssm_scan_fused(dt, x, Bm, Cm, A, chunk=chunk)
    y = y.astype(u.dtype) + x * p["D"]
    y = y * jax.nn.silu(z)
    return y @ p["out_proj"], {"h": h_last, "conv": tail}


def mamba_decode(
    p: Dict[str, jnp.ndarray],
    u: jnp.ndarray,  # (B, 1, d_model)
    state: Dict[str, jnp.ndarray],  # {"h": (B,di,st), "conv": (B,K-1,di)}
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-token state update — O(1) in context length."""
    di, st, dr = cfg.d_inner, cfg.ssm_d_state, cfg.dt_rank
    K = cfg.ssm_d_conv
    xz = u[:, 0] @ p["in_proj"]  # (B, 2di)
    x, z = jnp.split(xz, 2, axis=-1)
    conv_in = jnp.concatenate([state["conv"], x[:, None]], axis=1)  # (B,K,di)
    x = jax.nn.silu(jnp.einsum("bkd,kd->bd", conv_in, p["conv_w"]) + p["conv_b"])
    new_conv = conv_in[:, 1:]
    dbl = x @ p["x_proj"]
    dt, Bm, Cm = jnp.split(dbl, [dr, dr + st], axis=-1)
    dt = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])  # (B,di)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    a = jnp.exp(dt.astype(jnp.float32)[..., None] * A)  # (B,di,st)
    b = (dt * x).astype(jnp.float32)[..., None] * Bm.astype(jnp.float32)[:, None, :]
    h = a * state["h"] + b
    y = jnp.einsum("bds,bs->bd", h, Cm.astype(jnp.float32)).astype(u.dtype)
    y = y + x * p["D"]
    y = y * jax.nn.silu(z)
    out = (y @ p["out_proj"])[:, None]
    return out, {"h": h, "conv": new_conv}


# -------------------------------------------------------------------- Mamba-2
def ssd_scan(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H) float32, after the softplus
    A: jnp.ndarray,  # (H,) float32, negative
    Bm: jnp.ndarray,  # (B, S, G, N)
    Cm: jnp.ndarray,  # (B, S, G, N)
    chunk: int,
    unroll=1,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mamba-2's SSD over a sequence, from a zero state, one chunk of
    ``chunk`` positions per step of a scan:

        h_t = exp(dt_t·A)·h_{t−1} + dt_t·(x_t ⊗ B_t),   y_t = h_t·C_t

    Within a chunk y_l = Σ_{s≤l} (C_l·B_s)·exp(cum_l − cum_s)·dt_s·x_s plus
    exp(cum_l)·(C_l·h_in), cum the chunk's running sum of dt·A; the chunk
    hands on h = exp(cum_last)·h_in + Σ_s exp(cum_last − cum_s)·dt_s·x_s ⊗ B_s.
    A sequence that is no multiple of ``chunk`` is padded at its end with
    dt = 0 steps, which pass the state on unchanged.  Returns (y (B,S,H,P)
    in x's dtype, the last state (B,H,P,N) in float32)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    J = H // G  # heads per group
    Lc = min(chunk, S)
    pad = -S % Lc
    if pad:
        x, dt, Bm, Cm = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                         for t in (x, dt, Bm, Cm))
    nc = (S + pad) // Lc

    def chunks(t):
        return t.reshape((B, nc, Lc) + t.shape[2:]).swapaxes(0, 1)

    causal = jnp.tril(jnp.ones((Lc, Lc), bool))
    f32 = jnp.float32

    def body(h, inp):
        xc, dtc, bc, cc = inp  # (B,Lc,H,P), (B,Lc,H), (B,Lc,G,N) x2
        bc, cc = bc.astype(f32), cc.astype(f32)
        xdt = (xc.astype(f32) * dtc[..., None]).reshape(B, Lc, G, J, P)
        cum = jnp.cumsum(dtc * A, axis=1).swapaxes(1, 2).reshape(B, G, J, Lc)
        seg = jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf)
        cb = jnp.einsum("blgn,bsgn->bgls", cc, bc)
        y = jnp.einsum("bgjls,bsgjp->blgjp", cb[:, :, None] * jnp.exp(seg), xdt)
        hg = h.reshape(B, G, J, P, N)
        y_in = jnp.einsum("blgn,bgjpn->blgjp", cc, hg)
        y = y + y_in * jnp.exp(cum).transpose(0, 3, 1, 2)[..., None]
        to_end = jnp.exp(cum[..., -1:] - cum)  # (B,G,J,Lc)
        h = (hg * jnp.exp(cum[..., -1])[..., None, None]
             + jnp.einsum("bsgn,bgjs,bsgjp->bgjpn", bc, to_end, xdt))
        return h.reshape(B, H, P, N), y.reshape(B, Lc, H, P).astype(x.dtype)

    h_last, ys = jax.lax.scan(body, jnp.zeros((B, H, P, N), f32),
                              (chunks(x), chunks(dt), chunks(Bm), chunks(Cm)), unroll=unroll)
    return ys.swapaxes(0, 1).reshape(B, nc * Lc, H, P)[:, :S], h_last


def _mamba2_out(p, y, x, z, cfg: ModelConfig, dtype):
    """y + D·x per head, the gated RMSNorm rms(y·silu(z)) over d_inner, out_proj."""
    y = y + x * p["D"][:, None]
    y = y.reshape(y.shape[:-2] + (cfg.d_inner,)).astype(jnp.float32)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)), p["ssm_norm"], cfg.norm_eps)
    return y.astype(dtype) @ p["out_proj"]


def _mamba2_split(cfg: ModelConfig, zxbcdt):
    di = cfg.d_inner
    return jnp.split(zxbcdt, [di, di + cfg.ssm_conv_dim], axis=-1)  # z, x B C, dt


def mamba2_sequence(
    p: Dict[str, jnp.ndarray],
    u: jnp.ndarray,  # (B, S, d_model)
    cfg: ModelConfig,
    unroll=1,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full-sequence Mamba-2 mixer: in_proj to [z | x B C | dt], causal
    depthwise conv and SiLU over x B C, dt = softplus(dt + dt_bias), the SSD
    (scope ``ssm_scan``), + D·x, gated RMSNorm, out_proj.  Returns (output,
    {"h": the last state, "conv": the conv tail})."""
    B, S, _ = u.shape
    H, P, G, N = cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_n_groups, cfg.ssm_d_state
    z, xbc, dt = _mamba2_split(cfg, u @ p["in_proj"])
    tail = conv_tail(xbc, cfg.ssm_d_conv)
    xbc = jax.nn.silu(causal_conv1d(xbc, p["conv_w"], p["conv_b"]))
    x, Bm, Cm = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + G * N], axis=-1)
    x = x.reshape(B, S, H, P)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    with jax.named_scope("ssm_scan"):
        y, h = ssd_scan(x, dt, A, Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N),
                        cfg.ssm_chunk, unroll)
    return _mamba2_out(p, y, x, z, cfg, u.dtype), {"h": h, "conv": tail}


def mamba2_decode(
    p: Dict[str, jnp.ndarray],
    u: jnp.ndarray,  # (B, 1, d_model)
    state: Dict[str, jnp.ndarray],  # {"h": (B,H,P,N) f32, "conv": (B,K-1,conv_dim)}
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One token through Mamba-2: the conv window, the state's decay, update
    and readout (scope ``ssm_state``) in one step.  Returns (output, the new
    state)."""
    B = u.shape[0]
    H, P, G, N = cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_n_groups, cfg.ssm_d_state
    z, xbc, dt = _mamba2_split(cfg, u[:, 0] @ p["in_proj"])
    with jax.named_scope("ssm_state"):
        window = jnp.concatenate([state["conv"], xbc[:, None]], axis=1)  # (B,K,conv_dim)
        xbc = jax.nn.silu(jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"])
        x, Bm, Cm = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + G * N], axis=-1)
        x = x.reshape(B, H, P)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        xdt = (x.astype(jnp.float32) * dt[..., None]).reshape(B, G, H // G, P)
        Bg = Bm.reshape(B, G, N).astype(jnp.float32)
        Cg = Cm.reshape(B, G, N).astype(jnp.float32)
        h = (state["h"] * jnp.exp(dt * A)[..., None, None]
             + (xdt[..., None] * Bg[:, :, None, None, :]).reshape(B, H, P, N))
        y = jnp.einsum("bgjpn,bgn->bgjp", h.reshape(B, G, H // G, P, N), Cg)
    out = _mamba2_out(p, y.reshape(B, H, P).astype(u.dtype), x, z, cfg, u.dtype)
    return out[:, None], {"h": h, "conv": window[:, 1:]}
