"""Plain reference of granite-moe-1b-a400m as the program serves it.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no cache, no sort-based dispatch.  It imports nothing of the
program and takes nothing the program made.

The weights come from the seed by the same recipe the program's
``init_params`` documents (threefry keys split in the same order, normal
draws times 1/sqrt(fan_in), the embedding's padded rows included, norm
weights all ones), regenerated here bit for bit.

Semantics, as the configuration file states them:

* pre-norm decoder, RMSNorm (eps ``rms_norm_eps``, unit weights);
* rotate-half RoPE (``rope_theta``) on q and k; causal GQA attention,
  scale 1/sqrt(head_dim); query head h reads K/V head h // (H / KV);
* MoE: softmax router over ``num_local_experts``, the top
  ``num_experts_per_tok`` weights renormalised to sum 1, SwiGLU experts;
* capacity (``program.moe_capacity_factor``): in a prefill of N prompt
  tokens, expert e keeps only the first C = max(ceil(k·N/E·factor),
  min(N, 16)) tokens routed to it, in batch-major token order; a dropped
  assignment contributes nothing and the other weights are not
  renormalised.  A decode step routes ``batch`` tokens with C >= batch,
  so decode positions never drop;
* tied LM head over the first ``vocab_size`` rows of the embedding.

Departures from the published granite-3.0 model (the program's, so the
reference's too): no embedding/attention/residual multipliers and no
logits scaling; capacity-bounded prefill routing where the published
model is dropless.

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


def capacity(c: dict, n_tokens: int) -> int:
    k, E = c["num_experts_per_tok"], c["num_local_experts"]
    factor = c["program"]["moe_capacity_factor"]
    return max(math.ceil(k * n_tokens / E * factor), min(n_tokens, 16))


def init_weights(c: dict, seed: int):
    """(embedding rows x d, stacked per-layer dict), as the program draws them."""
    d, H, KV = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, f, E = c["head_dim"], c["intermediate_size"], c["num_local_experts"]
    L, rows = c["num_hidden_layers"], c["program"]["embedding_rows"]

    # Op by op, as the program draws them: one jitted call would fuse the
    # scaling into the draw and round some weights differently.
    def gen(key):
        keys = jax.random.split(key, 4)  # one layer kind in the period, plus 3
        embed = jax.random.normal(keys[-1], (rows, d)) * (1.0 / math.sqrt(d))

        def layer(k):
            ks = jax.random.split(k, 24)

            def draw(i, shape):
                return jax.random.normal(ks[i], shape) * (1.0 / math.sqrt(shape[-2]))

            return {
                "wq": draw(0, (d, H * hd)), "wk": draw(1, (d, KV * hd)),
                "wv": draw(2, (d, KV * hd)), "wo": draw(3, (H * hd, d)),
                "router": draw(4, (d, E)), "gate": draw(5, (E, d, f)),
                "up": draw(6, (E, d, f)), "down": draw(7, (E, f, d)),
            }

        return embed, jax.vmap(layer)(jax.random.split(keys[0], L))

    return gen(jax.random.key(seed))


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


def _rope(x, theta):
    L, hd = x.shape[-3], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(c, precision, n_prompt, cap, x, w):
    """One decoder layer over (B, L, d); returns (x, dropped assignments)."""
    mm, es = _ops(precision)
    B, L, d = x.shape
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    E, k, eps = c["num_local_experts"], c["num_experts_per_tok"], c["rms_norm_eps"]
    G = H // KV

    h = _rms(x, eps)
    q = _rope(mm(h, w["wq"]).reshape(B, L, H, hd), c["rope_theta"])
    kk = _rope(mm(h, w["wk"]).reshape(B, L, KV, hd), c["rope_theta"])
    v = mm(h, w["wv"]).reshape(B, L, KV, hd)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def attend(row):
        qr, kr, vr = row
        kr, vr = jnp.repeat(kr, G, axis=1), jnp.repeat(vr, G, axis=1)
        s = es("qhd,khd->hqk", qr, kr) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return es("hqk,khd->qhd", p, vr)

    att = jax.lax.map(attend, (q, kk, v))
    x = x + mm(att.reshape(B, L, H * hd), w["wo"])

    h = _rms(x, eps).reshape(B * L, d)
    probs = jax.nn.softmax(mm(h, w["router"]), axis=-1)
    top_w, ids = jax.lax.top_k(probs, k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    assign = jax.nn.one_hot(ids, E, dtype=jnp.float32)  # (N, k, E)
    routed = assign.sum(1)  # (N, E), 0/1
    in_prompt = (jnp.arange(B * L) % L < n_prompt)[:, None]
    seen = jnp.cumsum(routed * in_prompt, axis=0)  # prompt tokens so far per expert
    keep = jnp.where(in_prompt, seen <= cap, True)
    coef = jnp.einsum("nk,nke->ne", top_w, assign) * keep
    dropped = jnp.sum(routed * ~keep)

    def expert(acc, we):
        g, u, dn, ce = we
        y = mm(jax.nn.silu(mm(h, g)) * mm(h, u), dn)
        return acc + ce[:, None] * y, None

    moe, _ = jax.lax.scan(expert, jnp.zeros_like(h), (w["gate"], w["up"], w["down"], coef.T))
    return x + moe.reshape(B, L, d), dropped


_LAYER = jax.jit(_layer, static_argnums=(0, 1, 2, 3))


def _head(c, precision, x, embed):
    mm, _ = _ops(precision)
    return mm(_rms(x, c["rms_norm_eps"]), embed[: c["vocab_size"]].T)


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
    as numpy float32, dropped prompt assignments).
    """
    cf = _frozen(c)
    embed, layers = weights
    B, L = tokens.shape
    cap = capacity(c, B * n_prompt)
    x = jnp.take(embed, jnp.asarray(tokens), axis=0)
    dropped = 0.0
    for i in range(c["num_hidden_layers"]):
        w = {name: arr[i] for name, arr in layers.items()}
        x, drop = _LAYER(cf, precision, n_prompt, cap, x, w)
        dropped += float(drop)
    out = _HEAD(cf, precision, x[:, n_prompt - 1 :], embed)
    return np.asarray(out, np.float32), int(dropped)
