"""Plain reference of one chip's share of Granite-4.0-H-Small as the
program serves it (``granite-4.0-h-small-ep8.json``).

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no cache, no chunked scan, no sort-based dispatch.  It imports
nothing of the program and takes nothing the program made.  A Mamba-2
layer is its recurrence, one position at a time, over a block of
``SEQ_BLOCK`` sequences; attention is computed a sequence and a block of
queries at a time; each expert computes the tokens routed to it.

The weights come from the seed by the recipe the program's
``init_params`` documents (threefry keys split in the same order, normal
draws times 1/sqrt(fan_in) rounded to the configuration's bfloat16, the
embedding scaled by 1/(sqrt(d)·embedding_multiplier), so that the
embedded input has the scale 1/sqrt(d); norm weights all ones; expert e of a layer
drawn from its key folded with the global id e; Mamba-2's conv bias,
dt_bias, A_log and D by the published recipe), regenerated here bit for
bit, held in bfloat16 and read in float32 a layer at a time.

Semantics, as the configuration file states them (``h`` is the RMSNorm
of the residual stream, eps ``rms_norm_eps``; every residual branch
joins the stream times ``residual_multiplier``):

* embeddings times ``embedding_multiplier``;
* a ``mamba`` layer: [z | xBC | dt] = h W_in; xBC = SiLU(causal
  depthwise conv of ``mamba_d_conv`` taps + bias); x, B, C = xBC split
  (``mamba_n_heads`` heads of ``mamba_d_head``, B and C of
  ``mamba_d_state`` shared by each of ``mamba_n_groups`` groups' heads);
  dt = softplus(dt + dt_bias), A = −exp(A_log);
  h_t = exp(dt_t·A)·h_{t−1} + dt_t·(x_t ⊗ B_t), y_t = h_t·C_t + D·x_t,
  h_0 = 0, in float32; out = RMSNorm(y·SiLU(z)) W_out;
* an ``attention`` layer: causal GQA without positions, softmax scale
  ``attention_multiplier``;
* every layer's MoE: softmax over the router's
  ``program.router_experts`` outputs, the top ``num_experts_per_tok``
  weights renormalised to sum 1 (a softmax over the top logits); of those
  only the experts held here, ``program.held_offset`` + [0,
  ``num_local_experts``), contribute (what the rest would add lies on
  other chips); plus the shared SwiGLU of ``shared_intermediate_size``;
* capacity (``program.moe_capacity_factor``): in a prefill of N prompt
  tokens an expert keeps only the first C = max(ceil(k·N/E·factor),
  min(N, 16)) tokens routed to it (E the router's outputs), in
  batch-major token order; in a decode step of ``batch`` tokens the same
  rule over that step's tokens, in batch order; a dropped assignment
  contributes nothing;
* logits = RMSNorm(x) Eᵀ over the tied embedding's ``vocab_size`` rows,
  over ``logits_scaling``.

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
SEQ_BLOCK = 4  # sequences per recurrence of a Mamba-2 layer
HEAD_ROWS = 1024  # positions per block of the head


class _Dims:
    """The shapes the reference reads from the configuration file."""

    def __init__(self, c):
        self.d, self.H, self.KV = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
        self.hd = self.d // self.H
        self.SH, self.SP = c["mamba_n_heads"], c["mamba_d_head"]
        self.G, self.N, self.K = c["mamba_n_groups"], c["mamba_d_state"], c["mamba_d_conv"]
        self.di = c["mamba_expand"] * self.d
        self.cd = self.di + 2 * self.G * self.N
        self.fe, self.fs = c["intermediate_size"], c["shared_intermediate_size"]
        self.E, self.k = c["program"]["router_experts"], c["num_experts_per_tok"]
        self.held, self.off = c["num_local_experts"], c["program"]["held_offset"]
        self.L = c["num_hidden_layers"]
        self.types = c["layer_types"][: self.L]
        self.rows, self.V = c["program"]["embedding_rows"], c["vocab_size"]


def capacity(c: dict, n_tokens: int) -> int:
    k, E = c["num_experts_per_tok"], c["program"]["router_experts"]
    return max(math.ceil(k * n_tokens / E * c["program"]["moe_capacity_factor"]),
               min(n_tokens, 16))


def init_weights(c: dict, seed: int):
    """(embedding, [each layer's weights]), as the program draws them."""
    m = _Dims(c)
    dt = jnp.dtype(c["program"]["param_dtype"])

    def normal(key, shape, std=None):
        return (jax.random.normal(key, shape) * (std or 1.0 / math.sqrt(shape[-2]))).astype(dt)

    def mamba(ks):
        bound = 1.0 / math.sqrt(m.K)
        dt0 = jnp.exp(jax.random.uniform(ks[3], (m.SH,), minval=math.log(1e-3),
                                         maxval=math.log(1e-1)))
        return {"in_proj": normal(ks[0], (m.d, m.di + m.cd + m.SH)),
                "conv_w": normal(ks[1], (m.K, m.cd), bound),
                "conv_b": jax.random.uniform(ks[2], (m.cd,), minval=-bound,
                                             maxval=bound).astype(dt),
                "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dt),
                "A_log": jnp.log(jnp.arange(1, m.SH + 1, dtype=jnp.float32)).astype(dt),
                "D": jnp.ones((m.SH,), dt), "norm": jnp.ones((m.di,), dt),
                "out_proj": normal(ks[4], (m.di, m.d))}

    def attention(ks):
        return {"wq": normal(ks[0], (m.d, m.H * m.hd)), "wk": normal(ks[1], (m.d, m.KV * m.hd)),
                "wv": normal(ks[2], (m.d, m.KV * m.hd)), "wo": normal(ks[3], (m.H * m.hd, m.d))}

    def layer(kind, key):
        ks = jax.random.split(key, 24)
        w, n = (mamba(ks), 5) if kind == "mamba" else (attention(ks), 4)
        ids = jnp.arange(m.off, m.off + m.held)

        def experts(k, shape):
            return jax.vmap(lambda i: normal(jax.random.fold_in(k, i), shape))(ids)

        return dict(w, router=normal(ks[n], (m.d, m.E)),
                    gate=experts(ks[n + 1], (m.d, m.fe)), up=experts(ks[n + 2], (m.d, m.fe)),
                    down=experts(ks[n + 3], (m.fe, m.d)),
                    s_gate=normal(ks[n + 4], (m.d, m.fs)), s_up=normal(ks[n + 5], (m.d, m.fs)),
                    s_down=normal(ks[n + 6], (m.fs, m.d)))

    # Op by op, as the program draws them: one jitted call would fuse the
    # scaling into the draw and round some weights differently.  Layer
    # j·period + i is period j's draw under a vmap over position i's key
    # split per period, as the program stacks a position's layers.
    period = c["program"]["period"]
    keys = jax.random.split(jax.random.key(seed), period + 3)
    embed = normal(keys[-1], (m.rows, m.d), 1.0 / (math.sqrt(m.d) * c["embedding_multiplier"]))
    stacks = [jax.vmap(lambda k, t=m.types[i]: layer(t, k))(
        jax.random.split(keys[i], m.L // period)) for i in range(period)]
    layers = [jax.tree.map(lambda a: a[j], stacks[i])
              for j in range(m.L // period) for i in range(period)]
    return embed, layers


def _q8(x):
    scale = jnp.max(jnp.abs(x)) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(precision: str):
    q = _q8 if precision == "fp8" else (lambda x: x)
    return lambda a, b: jnp.matmul(q(a), q(b), precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _mamba_block(c, precision, u, w):
    """The Mamba-2 mixer of a block of sequences u (b, L, d): its recurrence
    one position at a time."""
    mm, m = _mm(precision), _Dims(c)
    b, L, _ = u.shape
    zxbcdt = mm(u, w["in_proj"])
    z, xbc, dt = zxbcdt[..., : m.di], zxbcdt[..., m.di : m.di + m.cd], zxbcdt[..., m.di + m.cd :]
    pad = jnp.pad(xbc, ((0, 0), (m.K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, j : j + L] * w["conv_w"][j] for j in range(m.K)) + w["conv_b"])
    x = xbc[..., : m.di].reshape(b, L, m.G, m.SH // m.G, m.SP)
    Bm = xbc[..., m.di : m.di + m.G * m.N].reshape(b, L, m.G, m.N)
    Cm = xbc[..., m.di + m.G * m.N :].reshape(b, L, m.G, m.N)
    dt = jax.nn.softplus(dt + w["dt_bias"]).reshape(b, L, m.G, m.SH // m.G)
    A = -jnp.exp(w["A_log"]).reshape(m.G, m.SH // m.G)

    def step(h, inp):  # h (b, G, J, P, N)
        xt, Bt, Ct, dtt = inp
        h = (jnp.exp(dtt * A)[..., None, None] * h
             + (dtt[..., None] * xt)[..., None] * Bt[:, :, None, None, :])
        return h, jnp.einsum("bgjpn,bgn->bgjp", h, Ct, precision=HI)

    h0 = jnp.zeros((b, m.G, m.SH // m.G, m.SP, m.N))
    _, y = jax.lax.scan(step, h0, tuple(t.swapaxes(0, 1) for t in (x, Bm, Cm, dt)))
    y = y.swapaxes(0, 1) + w["D"].reshape(m.G, m.SH // m.G)[..., None] * x
    y = y.reshape(b, L, m.di) * jax.nn.silu(z)
    return mm(_rms(y, c["rms_norm_eps"]) * w["norm"], w["out_proj"])


def _attention_row(c, precision, u, w):
    """Causal GQA without positions over one sequence u (L, d)."""
    mm, m = _mm(precision), _Dims(c)
    L = u.shape[0]
    q = mm(u, w["wq"]).reshape(L, m.KV, m.H // m.KV, m.hd)
    k = mm(u, w["wk"]).reshape(L, m.KV, m.hd)
    v = mm(u, w["wv"]).reshape(L, m.KV, m.hd)
    n_blocks = -(-L // Q_BLOCK)
    qb = jnp.pad(q, ((0, n_blocks * Q_BLOCK - L),) + ((0, 0),) * 3)
    qb = qb.reshape((n_blocks, Q_BLOCK) + q.shape[1:])
    q8 = _q8 if precision == "fp8" else (lambda a: a)

    def block(args):
        i, qi = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", q8(qi), q8(k), precision=HI) * c["attention_multiplier"]
        s = jnp.where(jnp.arange(L)[None, None, None, :] <= qpos[None, None, :, None], s, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", q8(jax.nn.softmax(s, axis=-1)), q8(v), precision=HI)

    att = jax.lax.map(block, (jnp.arange(n_blocks), qb)).reshape(-1, m.H * m.hd)[:L]
    return mm(att, w["wo"])


def _mixer(c, precision, kind, x, w):
    """x + residual_multiplier x the layer's mixer of RMSNorm(x), x (B, L, d)."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    h = _rms(x, c["rms_norm_eps"])
    B, L, d = x.shape
    if kind == "mamba":
        blocks = h.reshape(B // SEQ_BLOCK, SEQ_BLOCK, L, d)
        out = jax.lax.map(lambda u: _mamba_block(c, precision, u, w), blocks).reshape(B, L, d)
    else:
        out = jax.lax.map(lambda u: _attention_row(c, precision, u, w), h)
    return x + c["residual_multiplier"] * out


def _swiglu(mm, h, g, u, d):
    return mm(jax.nn.silu(mm(h, g)) * mm(h, u), d)


def _moe(c, precision, n_prompt, x, w):
    """x + residual_multiplier x (held routed experts + shared expert) of
    RMSNorm(x), over (B, L, d); returns (x, dropped held assignments)."""
    mm, m = _mm(precision), _Dims(c)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    B, L, d = x.shape
    h = _rms(x, c["rms_norm_eps"]).reshape(B * L, d)
    probs = jax.nn.softmax(mm(h, w["router"]), axis=-1)
    top_w, ids = jax.lax.top_k(probs, m.k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    assign = jax.nn.one_hot(ids, m.E, dtype=jnp.float32)  # (N, k, E)
    routed = assign.sum(1).reshape(B, L, m.E)  # 0/1
    # Prompt tokens count in batch-major order over the whole prefill; a
    # decode position's tokens in batch order over that step.
    seen_prompt = jnp.cumsum(routed[:, :n_prompt].reshape(-1, m.E), axis=0).reshape(B, n_prompt, m.E)
    seen_step = jnp.cumsum(routed[:, n_prompt:], axis=0)
    keep = jnp.concatenate([seen_prompt <= capacity(c, B * n_prompt),
                            seen_step <= capacity(c, B)], axis=1).reshape(B * L, m.E)
    routed = routed.reshape(B * L, m.E)
    coef = (jnp.einsum("nk,nke->ne", top_w, assign) * keep)[:, m.off : m.off + m.held]
    dropped = jnp.sum((routed * ~keep)[:, m.off : m.off + m.held])

    # Each held expert on the tokens routed to it: at most capacity(prompt)
    # prompt tokens and B per decode position; padding points at a zero row N.
    N = B * L
    K = min(N, capacity(c, B * n_prompt) + min(B, capacity(c, B)) * (L - n_prompt))
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
    out = routed_out[:N].reshape(B, L, d) + shared
    return x + c["residual_multiplier"] * out, dropped


_MIXER = jax.jit(_mixer, static_argnums=(0, 1, 2))
_MOE = jax.jit(_moe, static_argnums=(0, 1, 2))


def _head(c, precision, x, embed):
    mm = _mm(precision)
    e = embed[: c["vocab_size"]].astype(jnp.float32)
    return mm(_rms(x, c["rms_norm_eps"]), e.T) / c["logits_scaling"]


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
    tokens but the last; the wave is run together because routing is
    capacity-bounded over its tokens.  Returns (logits (B, T, V) as numpy
    float32, dropped assignments to the held experts)."""
    cf = _frozen(c)
    embed, layers = weights
    B, L = tokens.shape
    assert B % SEQ_BLOCK == 0, (B, SEQ_BLOCK)
    x = jnp.take(embed, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    x = x * c["embedding_multiplier"]
    moe_keys = ("router", "gate", "up", "down", "s_gate", "s_up", "s_down")
    dropped = 0.0
    for kind, w in zip(_Dims(c).types, layers):
        x = _MIXER(cf, precision, kind, x, {k: v for k, v in w.items() if k not in moe_keys})
        x, drop = _MOE(cf, precision, n_prompt, x, {k: w[k] for k in moe_keys})
        dropped += float(drop)
    x = x[:, n_prompt - 1 :]
    T = x.shape[1]
    rows = x.reshape(B * T, -1)
    out = [np.asarray(_HEAD(cf, precision, rows[i : i + HEAD_ROWS], embed), np.float32)
           for i in range(0, B * T, HEAD_ROWS)]
    return np.concatenate(out).reshape(B, T, -1), int(dropped)
