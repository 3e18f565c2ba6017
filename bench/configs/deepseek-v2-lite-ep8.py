"""Plain reference of one chip's share of DeepSeek-V2-Lite as the program
serves it (``deepseek-v2-lite-ep8.json``).

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no cache, no absorption of the latent projections, no sort-based
dispatch.  It imports nothing of the program and takes nothing the
program made.  A 16,512-position wave is computed a batch row and a block
of queries at a time, so that it fits one chip; a block's keys end with
its segment of 4,096 positions, and each expert computes the tokens
routed to it, so that the masked and unrouted work is mostly skipped.

The weights come from the seed by the recipe the program's
``init_params`` documents (threefry keys split in the same order, normal
draws times 1/sqrt(fan_in) rounded to the configuration's bfloat16, the
embedding scaled by 1/sqrt(d), norm weights all ones; expert e of a layer
drawn from its key folded with the global id e), regenerated here bit for
bit, and read in float32.

Semantics, as the configuration file states them (``h`` is the RMSNorm
of the residual stream, eps ``rms_norm_eps``):

* MLA without q compression: q = h W_q per head [nope | rope];
  [c_kv | k_pe] = h W_kv_a, c_kv RMS-normed; k_nope = c_kv W_uk,
  v = c_kv W_uv per head; rotate-half rope on q_pe and on k_pe (one per
  token, every head's) with YaRN's frequencies over the
  ``qk_rope_head_dim`` dims; causal softmax of q·k times
  (1/sqrt(nope + rope))·m², m = 0.1·mscale_all_dim·ln(factor) + 1;
* the first ``first_k_dense_replace`` layers: a SwiGLU of
  ``intermediate_size``;
* the others: softmax router over ``program.router_experts`` outputs, the
  top ``num_experts_per_tok`` weights, not renormalised, times
  ``routed_scaling_factor``; of those only the experts held here,
  ``program.held_offset`` + [0, ``n_routed_experts``), contribute (what
  the rest would add lies on other chips); plus the shared experts, one
  SwiGLU of ``n_shared_experts`` x ``moe_intermediate_size``;
* capacity (``program.moe_capacity_factor``): in a prefill of N prompt
  tokens an expert keeps only the first C = max(ceil(k·N/E·factor),
  min(N, 16)) tokens routed to it (E the router's outputs), in
  batch-major token order; a dropped assignment contributes nothing.  A
  decode step routes ``batch`` tokens with C >= batch, so decode
  positions never drop;
* untied head over the first ``vocab_size`` rows.

Departures from the published model (the program's, so the reference's
too): rope rotates halves of the rope columns where the published code
rotates interleaved pairs; capacity-bounded prefill routing.

``precision="fp8"`` is the control: every matmul operand is rounded
through float8_e4m3fn with a per-tensor scale, one step below the
configuration's bfloat16 compute.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
Q_BLOCK = 512  # queries per block of the attention
SEGMENT = 8  # query blocks that share a key range: keys up to the segment's end


class _Dims:
    """The shapes the reference reads from the configuration file."""

    def __init__(self, c):
        self.d, self.H = c["hidden_size"], c["num_attention_heads"]
        self.nope, self.rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
        self.vh, self.kvr = c["v_head_dim"], c["kv_lora_rank"]
        self.f, self.fe = c["intermediate_size"], c["moe_intermediate_size"]
        self.fs = c["n_shared_experts"] * c["moe_intermediate_size"]
        self.E, self.k = c["program"]["router_experts"], c["num_experts_per_tok"]
        self.held, self.off = c["n_routed_experts"], c["program"]["held_offset"]
        self.L, self.lead = c["num_hidden_layers"], c["first_k_dense_replace"]
        self.rows, self.V = c["program"]["embedding_rows"], c["vocab_size"]


def capacity(c: dict, n_tokens: int) -> int:
    k, E = c["num_experts_per_tok"], c["program"]["router_experts"]
    return max(math.ceil(k * n_tokens / E * c["program"]["moe_capacity_factor"]),
               min(n_tokens, 16))


def init_weights(c: dict, seed: int):
    """(embedding, head, leading layers, MoE layers), as the program draws
    them: each a dict of arrays stacked over its layers."""
    m = _Dims(c)
    dt = jnp.dtype(c["program"]["param_dtype"])

    def normal(key, shape, std=None):
        return (jax.random.normal(key, shape) * (std or 1.0 / math.sqrt(shape[-2]))).astype(dt)

    def attention(ks):
        return {"wq": normal(ks[0], (m.d, m.H * (m.nope + m.rope))),
                "wkv_a": normal(ks[1], (m.d, m.kvr + m.rope)),
                "wuk": normal(ks[2], (m.kvr, m.H * m.nope)),
                "wuv": normal(ks[3], (m.kvr, m.H * m.vh)),
                "wo": normal(ks[4], (m.H * m.vh, m.d))}

    def dense(k):
        ks = jax.random.split(k, 24)
        return dict(attention(ks), gate=normal(ks[5], (m.d, m.f)), up=normal(ks[6], (m.d, m.f)),
                    down=normal(ks[7], (m.f, m.d)))

    def moe(k):
        ks = jax.random.split(k, 24)
        ids = jnp.arange(m.off, m.off + m.held)

        def experts(key, shape):
            return jax.vmap(lambda i: normal(jax.random.fold_in(key, i), shape))(ids)

        return dict(attention(ks), router=normal(ks[5], (m.d, m.E)),
                    gate=experts(ks[6], (m.d, m.fe)), up=experts(ks[7], (m.d, m.fe)),
                    down=experts(ks[8], (m.fe, m.d)),
                    s_gate=normal(ks[9], (m.d, m.fs)), s_up=normal(ks[10], (m.d, m.fs)),
                    s_down=normal(ks[11], (m.fs, m.d)))

    # Op by op, as the program draws them: one jitted call would fuse the
    # scaling into the draw and round some weights differently.
    keys = jax.random.split(jax.random.key(seed), 4)  # one layer kind in the period, plus 3
    embed = normal(keys[3], (m.rows, m.d), 1.0 / math.sqrt(m.d))
    head = normal(keys[2], (m.d, m.rows))
    lead = jax.vmap(dense)(jax.random.split(keys[1], m.lead))
    layers = jax.vmap(moe)(jax.random.split(keys[0], m.L - m.lead))
    return embed, head, lead, layers


def _q8(x):
    scale = jnp.max(jnp.abs(x)) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _ops(precision: str):
    q = _q8 if precision == "fp8" else (lambda x: x)

    def mm(a, b):
        return jnp.matmul(q(a), q(b), precision=HI)

    def es(spec, a, b):
        return jnp.einsum(spec, q(a), q(b), precision=HI)

    return mm, es


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(c: dict):
    """(inverse frequencies over the rope dims, cos/sin scale, softmax scale)."""
    rs, dim, theta = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def pair(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(pair(rs["beta_fast"])), 0)
    hi = min(math.ceil(pair(rs["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    base = theta ** (-2.0 * i / dim)
    inv_freq = base / factor * ramp + base * (1.0 - ramp)
    cos_scale = _yarn_mscale(factor, rs["mscale"]) / _yarn_mscale(factor, rs["mscale_all_dim"])
    m = _yarn_mscale(factor, rs["mscale_all_dim"])
    return inv_freq, cos_scale, m * m / math.sqrt(c["qk_nope_head_dim"] + dim)


def _rope(x, inv_freq, cos_scale):
    """Rotate-half rope of x (..., L, heads, dim) at positions 0..L-1."""
    L, half = x.shape[-3], x.shape[-1] // 2
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    cos = (jnp.cos(ang) * cos_scale)[:, None, :]
    sin = (jnp.sin(ang) * cos_scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(c, precision, x, w):
    """x + MLA(RMSNorm(x)) for one batch row x (L, d)."""
    mm, es = _ops(precision)
    m = _Dims(c)
    inv_freq, cos_scale, scale = yarn(c)
    L = x.shape[0]
    h = _rms(x, c["rms_norm_eps"])
    q = mm(h, w["wq"]).reshape(L, m.H, m.nope + m.rope)
    kv_a = mm(h, w["wkv_a"])
    ckv = _rms(kv_a[:, : m.kvr], c["rms_norm_eps"])
    k_pe = _rope(kv_a[:, None, m.kvr :], inv_freq, cos_scale)  # (L, 1, rope)
    q = jnp.concatenate([q[..., : m.nope], _rope(q[..., m.nope :], inv_freq, cos_scale)], -1)
    k = jnp.concatenate([mm(ckv, w["wuk"]).reshape(L, m.H, m.nope),
                         jnp.broadcast_to(k_pe, (L, m.H, m.rope))], -1)
    v = mm(ckv, w["wuv"]).reshape(L, m.H, m.vh)

    n_blocks = -(-L // Q_BLOCK)
    qb = jnp.pad(q, ((0, n_blocks * Q_BLOCK - L), (0, 0), (0, 0)))
    qb = qb.reshape(n_blocks, Q_BLOCK, m.H, m.nope + m.rope)

    def block(args, end):
        i, qi = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = es("qhd,khd->hqk", qi, k[:end]) * scale
        s = jnp.where(jnp.arange(end)[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        return es("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v[:end])

    att = [jax.lax.map(lambda a, end=min((b + SEGMENT) * Q_BLOCK, L): block(a, end),
                       (jnp.arange(b, min(b + SEGMENT, n_blocks)), qb[b : b + SEGMENT]))
           for b in range(0, n_blocks, SEGMENT)]
    att = jnp.concatenate(att).reshape(-1, m.H * m.vh)[:L]
    return x + mm(att, w["wo"])


def _swiglu(mm, h, g, u, d):
    return mm(jax.nn.silu(mm(h, g)) * mm(h, u), d)


def _dense_layer(c, precision, x, w):
    mm, _ = _ops(precision)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)

    def row(xr):
        xr = _attention(c, precision, xr, w)
        return xr + _swiglu(mm, _rms(xr, c["rms_norm_eps"]), w["gate"], w["up"], w["down"])

    return jax.lax.map(row, x)


def _moe_layer(c, precision, n_prompt, cap, x, w):
    """One MoE layer over (B, L, d); returns (x, dropped held assignments)."""
    mm, _ = _ops(precision)
    m = _Dims(c)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    B, L, d = x.shape
    x = jax.lax.map(lambda xr: _attention(c, precision, xr, w), x)

    h = _rms(x, c["rms_norm_eps"]).reshape(B * L, d)
    probs = jax.nn.softmax(mm(h, w["router"]), axis=-1)
    top_w, ids = jax.lax.top_k(probs, m.k)
    top_w = top_w * c["routed_scaling_factor"]
    assign = jax.nn.one_hot(ids, m.E, dtype=jnp.float32)  # (N, k, E)
    routed = assign.sum(1)  # (N, E), 0/1
    in_prompt = (jnp.arange(B * L) % L < n_prompt)[:, None]
    seen = jnp.cumsum(routed * in_prompt, axis=0)  # prompt tokens so far per expert
    keep = jnp.where(in_prompt, seen <= cap, True)
    coef = (jnp.einsum("nk,nke->ne", top_w, assign) * keep)[:, m.off : m.off + m.held]
    dropped = jnp.sum((routed * ~keep)[:, m.off : m.off + m.held])

    # Each held expert on the tokens routed to it: at most ``cap`` prompt
    # tokens and every decode token; padding points at a zero row N.
    N, K = B * L, min(B * L, cap + B * (L - n_prompt))
    hp = jnp.concatenate([h, jnp.zeros((1, d))])

    def expert(out, we):
        g, u, dn, ce = we
        ce = jnp.concatenate([ce, jnp.zeros((1,))])
        idx = jnp.nonzero(ce, size=K, fill_value=N)[0]
        return out.at[idx].add(ce[idx][:, None] * _swiglu(mm, hp[idx], g, u, dn)), None

    routed_out, _ = jax.lax.scan(expert, jnp.zeros_like(hp),
                                 (w["gate"], w["up"], w["down"], coef.T))
    shared = jax.lax.map(lambda hr: _swiglu(mm, hr, w["s_gate"], w["s_up"], w["s_down"]),
                         h.reshape(B, L, d))
    return x + routed_out[:N].reshape(B, L, d) + shared, dropped


_DENSE = jax.jit(_dense_layer, static_argnums=(0, 1))
_MOE = jax.jit(_moe_layer, static_argnums=(0, 1, 2, 3))


def _head(c, precision, x, head):
    mm, _ = _ops(precision)
    return mm(_rms(x, c["rms_norm_eps"]), head[:, : c["vocab_size"]].astype(jnp.float32))


_HEAD = jax.jit(_head, static_argnums=(0, 1))


class _Key(dict):
    """A hashable view of the configuration, for jit's static arguments."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def _frozen(c):
    return _Key({k: (_Key(v) if isinstance(v, dict) else
                     tuple(v) if isinstance(v, list) else v) for k, v in c.items()})


def logits(c: dict, weights, tokens: np.ndarray, n_prompt: int, precision: str = "f32"):
    """Logits over ``vocab_size`` at positions n_prompt-1 .. L-1 of a wave.

    ``tokens`` (B, L) holds each request's prompt followed by its served
    tokens but the last; the wave is run together because prefill routing
    is capacity-bounded over the whole batch.  Returns (logits (B, T, V)
    as numpy float32, dropped prompt assignments to the held experts).
    """
    cf = _frozen(c)
    embed, head, lead, layers = weights
    B, L = tokens.shape
    cap = capacity(c, B * n_prompt)
    x = jnp.take(embed, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    for i in range(c["first_k_dense_replace"]):
        x = _DENSE(cf, precision, x, jax.tree.map(lambda a: a[i], lead))
    dropped = 0.0
    for i in range(c["num_hidden_layers"] - c["first_k_dense_replace"]):
        x, drop = _MOE(cf, precision, n_prompt, cap, x, jax.tree.map(lambda a: a[i], layers))
        dropped += float(drop)
    out = _HEAD(cf, precision, x[:, n_prompt - 1 :], head)
    return np.asarray(out, np.float32), int(dropped)
