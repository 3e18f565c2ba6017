"""DeepSeek-V2-Lite's mechanisms at smoke widths on the CPU: MLA without q
compression and its latent cache, YaRN rope, a leading dense layer, shared
experts, and an expert layer that holds a share of the experts.

Prefill-then-decode logits are compared with the plain float32 reference
of the benchmark (``bench/configs/deepseek-v2-lite-ep8.py``, loaded by
path), on seeded random weights drawn by the program's recipe.
"""
import dataclasses
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
from repro.models.common import init_params
from repro.models.moe import _positions_in_run, moe_block

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "bench", "configs", "deepseek-v2-lite-ep8.py")
    spec = importlib.util.spec_from_file_location("dsv2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ref_config(cfg) -> dict:
    """The reference's configuration dict (DeepSeek-V2 keys) of ``cfg``."""
    return {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_hidden_layers": cfg.n_layers, "first_k_dense_replace": cfg.first_k_dense,
        "num_attention_heads": cfg.n_heads, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_dim, "qk_rope_head_dim": cfg.qk_rope_dim,
        "v_head_dim": cfg.v_head_dim, "n_routed_experts": cfg.n_experts_held,
        "num_experts_per_tok": cfg.moe_topk, "moe_intermediate_size": cfg.moe_dff,
        "n_shared_experts": cfg.moe_shared_dff // cfg.moe_dff,
        "routed_scaling_factor": 1, "vocab_size": cfg.vocab,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "rope_scaling": {"factor": cfg.yarn_factor,
                         "original_max_position_embeddings": cfg.yarn_original_max_pos,
                         "beta_fast": cfg.yarn_beta_fast, "beta_slow": cfg.yarn_beta_slow,
                         "mscale": cfg.yarn_mscale, "mscale_all_dim": cfg.yarn_mscale_all_dim},
        "program": {"param_dtype": jnp.dtype(cfg.param_dtype).name,
                    "router_experts": cfg.moe_experts, "held_offset": cfg.moe_held_offset,
                    "moe_capacity_factor": cfg.moe_capacity_factor,
                    "embedding_rows": cfg.vocab_padded},
    }


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
    # float32 throughout: the program's absorbed decode and the reference's
    # expanded attention sum in different orders, so agreement is to float32
    # rounding over 3 layers of O(1) activations, well under 1e-4.
    ("float32", 1e-4),
    # bfloat16 weights and compute: every matmul output is rounded to 8 bits
    # of mantissa (relative 2^-9), ~20 roundings deep, on logits up to ~4:
    # the widest of these 9,216 logits moved 0.08 at these seeds; 0.15
    # leaves room for summation order, where fp8's 2^-4 would move ~0.5.
    ("bfloat16", 0.15),
])
def test_prefill_then_decode_matches_reference(dtype, atol):
    dt = jnp.dtype(dtype)
    # 2 of 8 experts held at offset 2; dropless capacity: the test is of the
    # attention, the cache and the held share, not of the drop policy.
    cfg = dataclasses.replace(configs.smoke("deepseek_v2_lite_ep8"), param_dtype=dt,
                              compute_dtype=dt, moe_n_held=2, moe_held_offset=2,
                              moe_capacity_factor=4.0)
    ref = _reference()
    rc = _ref_config(cfg)
    params = init_params(cfg, jax.random.key(3))
    tokens = np.random.default_rng(4).integers(0, cfg.vocab, (2, 24)).astype(np.int32)
    n_prompt = 16
    want, dropped = ref.logits(rc, ref.init_weights(rc, 3), tokens, n_prompt)
    assert dropped == 0
    got = _prefill_then_decode(cfg, params, tokens, n_prompt)
    assert got.shape == want.shape == (2, 9, cfg.vocab)
    np.testing.assert_allclose(got, want, atol=atol, rtol=0)


def test_held_shares_plus_shared_expert_once_equal_the_uncut_layer():
    """An 8-way split of 16 experts, 2 held per share: the routed parts of
    every share, plus the shared expert counted once, give the layer of the
    whole model.  Each share's experts are drawn from the whole's keys."""
    base = dataclasses.replace(
        configs.smoke("deepseek_v2_lite"), moe_experts=16, moe_n_held=16, moe_topk=4,
        param_dtype=jnp.float32, compute_dtype=jnp.float32, moe_capacity_factor=1.0)
    x = jax.random.normal(jax.random.key(9), (2, 24, base.d_model), jnp.float32)

    def layer(cfg):
        return init_params(cfg, jax.random.key(5))["layers"][0]

    def one(tree):  # the first MoE layer of the stack
        return {k: v[0] for k, v in tree.items()}

    whole = moe_block(one(layer(base)), x, base)
    total = jnp.zeros_like(whole)
    for i in range(8):
        cfg = dataclasses.replace(base, moe_n_held=2, moe_held_offset=2 * i)
        p = one(layer(cfg))
        if i:  # the shared expert, which every rank computes alike, once
            p["shared_down"] = jnp.zeros_like(p["shared_down"])
        total = total + moe_block(p, x, cfg)
    # capacity 1.0 drops some assignments: shares drop exactly the whole's
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-5, rtol=1e-5)


def test_yarn_frequencies_and_softmax_scale_by_hand():
    cfg = configs.get_config("deepseek_v2_lite")
    # yarn_find_correction_range(32, 1, 64, 10000, 4096):
    # 64·ln(4096 / (32·2π)) / (2 ln 10⁴) = 10.47 -> 10; with 1 rotation 22.51 -> 23
    assert L.yarn_correction_range(32, 1, 64, 10000.0, 4096) == (10, 23)
    inv = np.asarray(L.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32, 1))
    base = [10000.0 ** (-2 * i / 64) for i in range(32)]
    for i in (0, 5, 10):  # below the range: the plain frequency
        assert inv[i] == pytest.approx(base[i], rel=1e-6)
    for i in (23, 24, 31):  # above it: interpolated by the factor
        assert inv[i] == pytest.approx(base[i] / 40, rel=1e-6)
    ramp = (16 - 10) / 13  # between: the linear blend
    assert inv[16] == pytest.approx(base[16] / 40 * ramp + base[16] * (1 - ramp), rel=1e-6)
    # m = 0.1·0.707·ln 40 + 1 = 1.26080; softmax scale (1/√192)·m²
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.260804, abs=1e-6)
    assert M._mla_scale(cfg) == pytest.approx(m * m / math.sqrt(192), rel=1e-12)
    # cos/sin scale mscale(40, 0.707) / mscale(40, 0.707) = 1, over the 64 rope dims
    cos, sin = M._rope_cos_sin(cfg, jnp.arange(5)[None])
    assert cos.shape == (1, 5, 32)
    np.testing.assert_allclose(np.asarray(cos[0, 3]), np.cos(3 * inv), rtol=1e-5)


def test_mla_rope_frequencies_span_the_rope_dims():
    """MLA rotates qk_rope_dim dims: pair i turns at θ^(−2i/qk_rope_dim),
    not at the first pairs of a table over the whole qk head."""
    cfg = configs.get_config("minicpm3_4b")  # rope 32 of a 96-dim qk head
    cos, _ = M._rope_cos_sin(cfg, jnp.array([[1]]))
    want = np.cos(10000.0 ** (-2 * np.arange(16) / 32))
    np.testing.assert_allclose(np.asarray(cos[0, 0]), want, rtol=1e-6)


def _moe_block_before_shares(p, x, cfg):
    """The single-device MoE layer as it stood before held shares, shared
    experts and unnormalised routing: the oracle that granite's is unchanged."""
    B, S, D = x.shape
    E, k = cfg.moe_experts, cfg.moe_topk
    N = B * S
    xt = x.reshape(N, D)
    logits = (xt @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    C = max(math.ceil(k * N / E * cfg.moe_capacity_factor), min(N, 16))
    flat_ids = ids.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(N), k)
    flat_w = weights.reshape(-1)
    order = jnp.argsort(flat_ids)
    s_ids, s_tok, s_w = flat_ids[order], flat_tok[order], flat_w[order]
    pos = _positions_in_run(s_ids)
    owned = (s_ids >= 0) & (s_ids < E) & (pos < C)
    slot = jnp.where(owned, s_ids * C + pos, E * C)
    buf = jnp.zeros((E * C, D), x.dtype).at[slot].set(
        xt[s_tok] * owned[:, None].astype(x.dtype), mode="drop").reshape(E, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["moe_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["moe_up"])
    y_buf = jnp.einsum("ecf,efd->ecd", h, p["moe_down"]).reshape(E * C, D)
    contrib = jnp.take(y_buf, jnp.where(owned, slot, E * C), axis=0, mode="fill",
                       fill_value=0.0)
    contrib = contrib * (s_w * owned)[:, None].astype(x.dtype)
    return jnp.zeros((N, D), x.dtype).at[s_tok].add(contrib).reshape(B, S, D)


@pytest.mark.parametrize("jit", [False, True])
def test_granite_moe_block_bitwise_unchanged(jit):
    cfg = configs.smoke("granite_moe_1b_a400m")
    p = {k: v[0].astype(cfg.compute_dtype)
         for k, v in init_params(cfg, jax.random.key(2))["layers"][0].items()}
    x = jax.random.normal(jax.random.key(8), (4, 32, cfg.d_model)).astype(cfg.compute_dtype)
    new, old = (lambda p, x: moe_block(p, x, cfg)), (lambda p, x: _moe_block_before_shares(p, x, cfg))
    if jit:
        new, old = jax.jit(new), jax.jit(old)
    np.testing.assert_array_equal(np.asarray(new(p, x), np.float32),
                                  np.asarray(old(p, x), np.float32))


def test_programs_carry_the_mla_and_moe_scopes():
    from repro.launch.steps import (StepOptions, build_decode_step, build_prefill_step,
                                    make_shard_ctx)

    cfg = configs.smoke("deepseek_v2_lite_ep8")
    opts = StepOptions()
    ctx = make_shard_ctx(cfg, None, 2, opts)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    prefill = build_prefill_step(cfg, ctx, opts, max_seq=12)
    prompts = {"tokens": jax.ShapeDtypeStruct((2, 8), jnp.int32)}
    cache = jax.eval_shape(prefill, params, prompts)[1]
    for lowered in (jax.jit(prefill).lower(params, prompts),
                    jax.jit(build_decode_step(cfg, ctx, opts)).lower(
                        params, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32))):
        names = re.findall(r'op_name="([^"]+)"',
                           lowered.as_text(dialect="hlo", debug_info=True))
        scopes = {part for name in names for part in name.split("/")}
        for scope in ("attention", "latent_attention", "moe", "routed_experts",
                      "shared_expert", "mlp", "lm_head"):
            assert scope in scopes, scope
        # the latent part sits inside attention
        assert any("attention/latent_attention" in n for n in names)


def test_serve_reports_latent_cache_bytes_and_makes_no_weight_copy():
    """serve() sets repro_serve_cache_bytes from the cache's shapes, and on
    weights already in the compute dtype copies nothing."""
    from repro.launch.serve import serve
    from repro.telemetry.registry import get_registry

    cfg = configs.smoke("deepseek_v2_lite_ep8")
    params = init_params(cfg, jax.random.key(0))
    assert M.compute_params(cfg, params) is params
    serve(arch="deepseek_v2_lite_ep8", n_requests=2, batch=2, prompt_len=8, max_new=2)
    snap = get_registry().snapshot()
    series = {json.loads(k)[0][1]: v
              for k, v in snap["repro_serve_cache_bytes"]["series"].items()}
    latent = cfg.n_layers * 2 * 10 * (cfg.kv_lora_rank + cfg.qk_rope_dim) * 2  # bf16
    assert series == {"latent": latent, "kv": 0, "state": 0}
    assert snap["repro_serve_compute_param_bytes"]["series"]["[]"] == 0
    granite = configs.smoke("granite_moe_1b_a400m")
    kinds = M.cache_bytes_by_kind(jax.eval_shape(lambda: M.init_cache(granite, 2, 10)))
    assert kinds["latent"] == 0 and kinds["kv"] > 0
