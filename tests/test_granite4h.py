"""Granite-4.0-H's mechanisms at smoke widths on the CPU: Mamba-2's chunked
SSD and its one-step decode, the state cache, NoPE attention with its own
softmax scale, granite's multipliers, and a MoE layer that holds a share of
72 experts beside a shared expert.

Prefill-then-decode logits are compared with the plain float32 reference
of the benchmark (``bench/configs/granite-4.0-h-small-ep8.py``, loaded by
path), whose Mamba-2 layer is the recurrence one position at a time, on
seeded random weights drawn by the program's recipe.
"""
import dataclasses
import functools
import importlib.util
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import layers as L
from repro.models import model as M
from repro.models.common import MOE, NONE, init_params
from repro.models.mamba import ssd_scan
from repro.models.moe import moe_block

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = "granite_4_0_h_small_ep8"


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *rel.split("/")))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reference():
    return _load("bench/configs/granite-4.0-h-small-ep8.py", "granite4h_reference")


def _ref_config(cfg) -> dict:
    """The reference's configuration dict (granite-4.0-h keys) of ``cfg``."""
    return _load("bench/drivers/serve_hybrid.py", "granite4h_driver").smoke_config(cfg)


# ------------------------------------------------------------------- the SSD
def _ssd_sequential(x, dt, A, Bm, Cm):
    """h_t = exp(dt_t·A)·h_{t−1} + dt_t·(x_t ⊗ B_t), y_t = h_t·C_t, in numpy."""
    x, dt, A, Bm, Cm = (np.asarray(t, np.float64) for t in (x, dt, A, Bm, Cm))
    B, S, H, P = x.shape
    G, N = Bm.shape[2:]
    g = np.arange(H) // (H // G)  # each head's group
    h = np.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        h = (np.exp(dt[:, t] * A)[..., None, None] * h
             + (dt[:, t, :, None] * x[:, t])[..., None] * Bm[:, t, g][:, :, None, :])
        ys.append(np.einsum("bhpn,bhn->bhp", h, Cm[:, t, g]))
    return np.stack(ys, 1), h


@pytest.mark.parametrize("S, chunk, G", [
    (37, 8, 1),  # 5 chunks, the last padded with dt = 0 steps
    (32, 8, 2),  # 4 whole chunks, two groups of heads sharing B and C
])
def test_chunked_ssd_matches_the_sequential_recurrence(S, chunk, G):
    B, H, P, N = 2, 4, 8, 6
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 1.0)
    A = -jnp.arange(1, H + 1, dtype=jnp.float32) / 2
    Bm = jax.random.normal(ks[2], (B, S, G, N))
    Cm = jax.random.normal(ks[3], (B, S, G, N))
    y, h = jax.jit(functools.partial(ssd_scan, chunk=chunk))(x, dt, A, Bm, Cm)
    want_y, want_h = _ssd_sequential(x, dt, A, Bm, Cm)
    # float32 sums of O(10) terms over a chunk against float64 steps
    np.testing.assert_allclose(np.asarray(y), want_y, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), want_h, atol=1e-4, rtol=1e-4)


# ------------------------------------------------- program against reference
def _prefill_then_decode(cfg, params, tokens, n_prompt):
    """Program logits at positions n_prompt-1 .. L-1: a prefill of the
    prompt, then one decode step per further token through the cache."""
    L_ = tokens.shape[1]
    prefill = jax.jit(lambda p, t: M.prefill(cfg, p, {"tokens": t}, max_seq=L_))
    decode = jax.jit(lambda p, c, t: M.decode_step(cfg, p, c, t))
    logits, cache = prefill(params, jnp.asarray(tokens[:, :n_prompt]))
    out = [logits[:, -1]]
    for t in range(n_prompt, L_):
        logits, cache = decode(params, cache, jnp.asarray(tokens[:, t : t + 1]))
        out.append(logits[:, 0])
    return np.asarray(jnp.stack(out, 1), np.float32)[..., : cfg.vocab]


@pytest.mark.parametrize("dtype, atol", [
    # float32 throughout: the chunked SSD and the reference's recurrence sum
    # in different orders, over 20 layers of O(1) activations and logits of
    # std ~0.005 (the tied head over logits_scaling 16): float32 rounding,
    # well under 1e-6.
    ("float32", 1e-6),
    # bfloat16 weights and compute: every matmul output and the residual
    # stream are rounded to 8 bits of mantissa (relative 2^-9) through 20
    # layers (40 residual branches), and a rounded router score can swap a
    # token's top-2 experts.  On logits of std 0.0052 the widest of these
    # 18,432 moved 0.0050 at this seed; fp8 operands (2^-4) in the
    # reference move it 0.0196, so 0.008 leaves room on both sides.
    ("bfloat16", 0.008),
])
def test_prefill_then_decode_matches_reference(dtype, atol):
    dt = jnp.dtype(dtype)
    # 2 of 8 experts held at offset 2; dropless capacity: the test is of the
    # mixers, the cache and the held share, not of the drop policy.
    cfg = dataclasses.replace(configs.smoke(ARCH), param_dtype=dt, compute_dtype=dt,
                              moe_n_held=2, moe_held_offset=2, moe_capacity_factor=4.0)
    ref = _reference()
    rc = _ref_config(cfg)
    params = init_params(cfg, jax.random.key(3))
    tokens = np.random.default_rng(4).integers(0, cfg.vocab, (4, 24)).astype(np.int32)
    n_prompt = 16  # two SSD chunks of 8
    want, dropped = ref.logits(rc, ref.init_weights(rc, 3), tokens, n_prompt)
    assert dropped == 0
    got = _prefill_then_decode(cfg, params, tokens, n_prompt)
    assert got.shape == want.shape == (4, 9, cfg.vocab)
    np.testing.assert_allclose(got, want, atol=atol, rtol=0)


def test_held_shares_plus_shared_expert_once_equal_the_uncut_layer():
    """The deployment's split at smoke widths: 72 router outputs, top-10, 8
    ranks of 9 held experts each.  The routed parts of every rank, plus the
    shared expert counted once, give the layer of the whole model; each
    share's experts are drawn from the whole's keys."""
    base = dataclasses.replace(
        configs.smoke("granite_4_0_h_small"), moe_experts=72, moe_n_held=72, moe_topk=10,
        param_dtype=jnp.float32, compute_dtype=jnp.float32, moe_capacity_factor=1.0)
    x = jax.random.normal(jax.random.key(9), (2, 24, base.d_model), jnp.float32)

    def layer(cfg):  # the first layer's MoE weights, period 0
        return {k: v[0] for k, v in init_params(cfg, jax.random.key(5))["layers"][0].items()}

    whole = moe_block(layer(base), x, base)
    total = jnp.zeros_like(whole)
    for i in range(8):
        cfg = dataclasses.replace(base, moe_n_held=9, moe_held_offset=9 * i)
        p = layer(cfg)
        if i:  # the shared expert, which every rank computes alike, once
            p["shared_down"] = jnp.zeros_like(p["shared_down"])
        total = total + moe_block(p, x, cfg)
    # capacity 1.0 drops some assignments: shares drop exactly the whole's
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-5, rtol=1e-5)


def test_multipliers_and_attention_scale_by_hand():
    cfg = dataclasses.replace(configs.smoke(ARCH), compute_dtype=jnp.float32,
                              param_dtype=jnp.float32)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling,
            cfg.attn_scale) == (12.0, 0.22, 16.0, 0.0078125)
    params = init_params(cfg, jax.random.key(1))
    emb = np.asarray(params["embed"])
    # the draw divides the multiplier out: embedded rows have std 1/sqrt(d)
    assert np.std(emb) * 12 == pytest.approx(1 / math.sqrt(cfg.d_model), rel=0.05)
    tok = jnp.array([[3, 7]])
    np.testing.assert_allclose(np.asarray(M.embed_tokens(cfg, params, {"tokens": tok})),
                               12.0 * emb[[3, 7]][None], rtol=1e-6)
    x = jax.random.normal(jax.random.key(2), (1, 2, cfg.d_model))
    normed = np.asarray(L.rms_norm(x, params["final_ln"], cfg.norm_eps))
    np.testing.assert_allclose(np.asarray(M.unembed(cfg, params, x)),
                               normed @ emb.T / 16.0, rtol=1e-5, atol=1e-7)
    h = jnp.ones((2, 3))
    np.testing.assert_allclose(np.asarray(M._residual(cfg, h)), 0.22 * np.ones((2, 3)))
    # attention: softmax(q·k × 0.0078125) v over causal keys, no positions
    spec = cfg.layout[5]
    assert spec.mixer == "full" and cfg.pos == "none"
    p = {k: v[0] for k, v in params["layers"][5].items()}
    hs = jax.random.normal(jax.random.key(3), (1, 6, cfg.d_model))
    out, _ = M._attn_seq(cfg, spec, p, hs, None, None, M.ShardCtx())
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = (hs @ p["wq"]).reshape(6, H, hd)
    k = np.repeat(np.asarray(hs @ p["wk"]).reshape(6, KV, hd), H // KV, axis=1)
    v = np.repeat(np.asarray(hs @ p["wv"]).reshape(6, KV, hd), H // KV, axis=1)
    s = np.einsum("qhd,khd->hqk", q, k) * 0.0078125
    s = np.where(np.tril(np.ones((6, 6), bool)), s, -np.inf)
    a = np.exp(s - s.max(-1, keepdims=True))
    a /= a.sum(-1, keepdims=True)
    want = np.einsum("hqk,khd->qhd", a, v).reshape(6, H * hd) @ np.asarray(p["wo"])
    np.testing.assert_allclose(np.asarray(out[0]), want, rtol=1e-5, atol=1e-5)
    # every other config keeps 1/sqrt(head_dim) and no multiplier
    other = configs.get_config("granite_moe_1b_a400m")
    assert (other.attn_scale, other.embedding_multiplier, other.residual_multiplier,
            other.logits_scaling) == (0.0, 1.0, 1.0, 1.0)


# ------------------------------------------------------------ trace and cache
def test_programs_carry_the_ssm_scopes():
    from repro.launch.steps import (StepOptions, build_decode_step, build_prefill_step,
                                    make_shard_ctx)

    cfg = configs.smoke(ARCH)
    opts = StepOptions()
    ctx = make_shard_ctx(cfg, None, 2, opts)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    prefill = build_prefill_step(cfg, ctx, opts, max_seq=20)
    prompts = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    cache = jax.eval_shape(prefill, params, prompts)[1]
    lowered = {"prefill": jax.jit(prefill).lower(params, prompts),
               "decode": jax.jit(build_decode_step(cfg, ctx, opts)).lower(
                   params, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32))}
    names = {k: re.findall(r'op_name="([^"]+)"', low.as_text(dialect="hlo", debug_info=True))
             for k, low in lowered.items()}
    # the SSD of the sequence pass and decode's state step, inside attention
    assert any("attention/ssm_scan" in n for n in names["prefill"])
    assert not any("ssm_state" in n for n in names["prefill"])
    assert any("attention/ssm_state" in n for n in names["decode"])
    assert not any("ssm_scan" in n for n in names["decode"])
    for scope in ("moe", "routed_experts", "shared_expert", "lm_head"):
        assert any(scope in n.split("/") for n in names["decode"]), scope


def test_serve_reports_state_cache_bytes():
    """serve() sets repro_serve_cache_bytes{kind=state} from the cache's
    shapes: 9 Mamba-2 layers of a float32 state and a bfloat16 conv tail."""
    from repro.launch.serve import serve
    from repro.telemetry.registry import get_registry

    cfg = configs.smoke(ARCH)
    serve(arch=ARCH, n_requests=2, batch=2, prompt_len=8, max_new=2)
    series = {json.loads(k)[0][1]: v
              for k, v in get_registry().snapshot()["repro_serve_cache_bytes"]["series"].items()}
    n_mamba = 9 * cfg.n_periods
    state = n_mamba * 2 * (cfg.ssm_n_heads * cfg.ssm_head_dim * cfg.ssm_d_state * 4
                           + (cfg.ssm_d_conv - 1) * cfg.ssm_conv_dim * 2)
    assert series["state"] == state and series["latent"] == 0 and series["kv"] > 0
    # the cell's shapes: 16 sessions of 2,048 + 512 positions
    full = configs.get_config(ARCH)
    kinds = M.cache_bytes_by_kind(jax.eval_shape(lambda: M.init_cache(full, 16, 2560)))
    assert kinds["state"] == 16 * 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2) == 611_278_848
    assert kinds["kv"] == 16 * 2560 * 2 * 8 * 128 * 2 + 2560 * 4


# --------------------------------------------- the benchmarked configs pinned
def _layer_before(cfg, spec, p, x, mix, ctx):
    """``_layer`` as it stood before granite's residual multiplier."""
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    with jax.named_scope("attention"):
        h, cache = mix(h)
    x = ctx.constrain(x + h, ctx.hidden_spec())
    if spec.mlp != NONE:
        h = M._mlp_apply(cfg, spec, p, L.rms_norm(x, p["ln2"], cfg.norm_eps), ctx)
        x = ctx.constrain(x + h, ctx.hidden_spec())
    return x, cache


@pytest.mark.parametrize("arch, stack", [("granite_moe_1b_a400m", "layers"),
                                         ("deepseek_v2_lite_ep8", "layers"),
                                         ("deepseek_v2_lite_ep8", "lead")])
def test_benchmarked_layer_outputs_bitwise_unchanged(arch, stack):
    """The chat and dsv2 cells' layers, jitted, give bit for bit what they
    gave before the multipliers and the attention scale were configurable."""
    cfg = configs.smoke(arch)
    spec = cfg.lead_spec if stack == "lead" else cfg.layout[0]
    assert stack == "lead" or spec.mlp == MOE
    cp = M.compute_params(cfg, init_params(cfg, jax.random.key(2)))
    p = {k: v[0] for k, v in cp[stack][0].items()}
    x = jax.random.normal(jax.random.key(8), (2, 16, cfg.d_model)).astype(cfg.compute_dtype)
    ctx = M.ShardCtx()
    cos, sin = M._rope_cos_sin(cfg, M._positions(cfg, {}, 2, 16))

    def run(layer):
        def f(p, x):
            mix = functools.partial(M._seq_mixer, cfg, spec, p, cos=cos, sin=sin, ctx=ctx,
                                    with_cache=True)
            return layer(cfg, spec, p, x, mix, ctx)

        return jax.jit(f)(p, x)

    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a, np.float32),
                                                            np.asarray(b, np.float32)),
                 run(M._layer), run(_layer_before))
    tok = jnp.array([[1, 5, 9]])
    e = np.asarray(M.embed_tokens(cfg, cp, {"tokens": tok}), np.float32)
    np.testing.assert_array_equal(e, np.asarray(cp["embed"][tok], np.float32))
