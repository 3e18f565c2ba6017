"""Per-architecture smoke tests (reduced configs) + cache-consistency checks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.data.pipeline import synthetic_batch
from repro.models import model as M
from repro.models.common import init_params
from repro.models.moe import moe_block


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = configs.smoke(arch)
    B, S = 2, 32
    params = init_params(cfg, jax.random.key(0))
    batch = synthetic_batch(cfg, B, S, seed=1)

    logits = M.forward(cfg, params, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"

    def loss_fn(p):
        return M.loss_and_metrics(cfg, p, batch)[0]

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
    leaves = jax.tree.leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in leaves), f"{arch}: bad grads"
    # at least one non-zero gradient per layer position
    gnorm = sum(float(jnp.abs(g).sum()) for g in leaves)
    assert gnorm > 0


@pytest.mark.parametrize(
    "arch",
    ["gemma2-2b", "minicpm3-4b", "falcon-mamba-7b", "jamba-v0.1-52b", "qwen2-vl-2b",
     "h2o-danube-3-4b", "granite-moe-1b-a400m", "deepseek-v2-lite", "deepseek-v2-lite-ep8",
     "granite-4.0-h-small-ep8"],
)
def test_decode_matches_forward(arch):
    """prefill(S-1) + decode(last) must equal full forward's last logits.

    f32 compute isolates cache/masking logic from bf16 reordering noise
    (absorbed-MLA and chunked-scan reorder reductions materially in bf16).
    Ample MoE capacity isolates it from drop-policy differences (a 15-token
    prefill and a 16-token forward legitimately drop different tokens).
    """
    cfg = dataclasses.replace(
        configs.smoke(arch), compute_dtype=jnp.float32, moe_capacity_factor=16.0
    )
    B, S = 2, 16  # S < smoke window (32): ring buffer not wrapped here
    params = init_params(cfg, jax.random.key(1))
    batch = synthetic_batch(cfg, B, S, seed=2)
    if cfg.modality == "vision_stub":
        batch.pop("pos3")  # use text-degenerate M-RoPE so decode can continue it
        batch.pop("visual_embeds")
    full = M.forward(cfg, params, batch)

    pre_batch = {k: v[:, : S - 1] if v.ndim >= 2 and v.shape[1] == S else v
                 for k, v in batch.items() if k != "labels"}
    _, cache = M.prefill(cfg, params, pre_batch, max_seq=S)
    logits, cache = M.decode_step(cfg, params, cache, batch["tokens"][:, S - 1 :])
    np.testing.assert_allclose(
        np.asarray(logits[:, 0], np.float32),
        np.asarray(full[:, -1], np.float32),
        rtol=1e-4, atol=1e-3,
    )


@pytest.mark.parametrize("arch", ["granite_moe_1b_a400m", "falcon_mamba_7b",
                                  "granite_4_0_h_small_ep8"])
def test_compute_params_serve_bitwise(arch):
    """prefill and decode_step, compiled on compute_params' copy, give the
    same logits, caches and greedy tokens, bit for bit, as on the float32
    masters: every matmul reads the same compute-dtype weights."""
    cfg = configs.smoke(arch)
    B, S, steps = 2, 8, 3
    params = init_params(cfg, jax.random.key(4))
    cparams = M.compute_params(cfg, params)
    assert cparams["embed"].dtype == cfg.compute_dtype
    assert cparams["final_ln"].dtype == params["final_ln"].dtype
    tokens = synthetic_batch(cfg, B, S, seed=5)["tokens"]
    prefill = jax.jit(lambda p, t: M.prefill(cfg, p, {"tokens": t}, max_seq=S + steps))
    decode = jax.jit(lambda p, c, t: M.decode_step(cfg, p, c, t))

    def run(p):
        logits, cache = prefill(p, tokens)
        outs = [(logits, cache)]
        for _ in range(steps):
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            logits, cache = decode(p, cache, tok)
            outs.append((tok, logits, cache))
        return outs

    for got, want in zip(run(cparams), run(params)):
        jax.tree.map(np.testing.assert_array_equal, got, want)


def test_swa_ring_buffer_consistency():
    """Decode past the window: ring buffer must equal windowed reference."""
    cfg = configs.smoke("h2o-danube-3-4b")
    cfg = dataclasses.replace(cfg, window=8, compute_dtype=jnp.float32)
    B, S = 1, 24
    params = init_params(cfg, jax.random.key(3))
    batch = synthetic_batch(cfg, B, S, seed=3)
    full = M.forward(cfg, params, batch)  # SWA masking inside
    # decode token-by-token from scratch
    cache = M.init_cache(cfg, B, max_seq=S)
    outs = []
    for t in range(S):
        logits, cache = M.decode_step(cfg, params, cache, batch["tokens"][:, t : t + 1])
        outs.append(np.asarray(logits[:, 0], np.float32))
    dec = np.stack(outs, axis=1)
    np.testing.assert_allclose(dec, np.asarray(full, np.float32), rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("arch,window,S", [("granite-moe-1b-a400m", 0, 8),
                                            ("h2o-danube-3-4b", 8, 12),  # past the wrap
                                            ("minicpm3-4b", 0, 8)])
def test_decode_never_reads_the_slot_it_writes(arch, window, S):
    """The cache slot a decode step writes (``pos``; ``pos % window`` in a
    ring) is never read as a key: filled with large garbage and an
    attendable position, it gives the same logits and new cache, bit for
    bit, as a zeroed, unwritten slot."""
    cfg = dataclasses.replace(configs.smoke(arch), compute_dtype=jnp.float32)
    if window:
        cfg = dataclasses.replace(cfg, window=window)
    B, pos = 2, S - 1
    params = init_params(cfg, jax.random.key(6))
    tokens = synthetic_batch(cfg, B, S, seed=6)["tokens"]
    _, cache = M.prefill(cfg, params, {"tokens": tokens[:, :pos]}, max_seq=16)

    def fill(value, kpos):
        def one(path, leaf):
            name = path[-1].key
            if name == "pos":
                return leaf
            axis = M._ROW_AXIS[name]
            at = [slice(None)] * leaf.ndim
            at[axis] = pos % leaf.shape[axis]
            return leaf.at[tuple(at)].set(kpos if name == "kpos" else value)
        return jax.tree_util.tree_map_with_path(one, cache)

    got = M.decode_step(cfg, params, fill(1e4, pos - 1), tokens[:, pos:])
    want = M.decode_step(cfg, params, fill(0.0, -1), tokens[:, pos:])
    jax.tree.map(np.testing.assert_array_equal, got, want)


def test_moe_matches_dense_routing_reference():
    """Sort-based capacity dispatch == naive per-token loop (ample capacity)."""
    cfg = dataclasses.replace(
        configs.smoke("granite-moe-1b-a400m"), moe_capacity_factor=8.0
    )
    from repro.models.common import init_layer_params

    p = init_layer_params(cfg, cfg.layout[0], jax.random.key(4))
    sub = {k: p[k] for k in ("router", "moe_gate", "moe_up", "moe_down")}
    x = jax.random.normal(jax.random.key(5), (2, 8, cfg.d_model), jnp.float32)
    out = moe_block(sub, x, cfg, None)

    # naive reference
    xt = np.asarray(x.reshape(-1, cfg.d_model), np.float64)
    router = np.asarray(sub["router"], np.float64)
    logits = xt @ router
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    ref = np.zeros_like(xt)
    for i in range(xt.shape[0]):
        top = np.argsort(probs[i])[::-1][: cfg.moe_topk]
        w = probs[i, top] / probs[i, top].sum()
        for e, we in zip(top, w):
            g = xt[i] @ np.asarray(sub["moe_gate"][e], np.float64)
            u = xt[i] @ np.asarray(sub["moe_up"][e], np.float64)
            h = (g / (1 + np.exp(-g))) * u
            ref[i] += we * (h @ np.asarray(sub["moe_down"][e], np.float64))
    np.testing.assert_allclose(
        np.asarray(out.reshape(-1, cfg.d_model), np.float64), ref, rtol=2e-3, atol=2e-3
    )


def test_mamba_scan_matches_sequential():
    """Chunked associative scan == naive per-step recurrence."""
    from repro.models.mamba import _ssm_scan_chunked

    rng = np.random.default_rng(0)
    B, S, di, st = 2, 16, 4, 3
    a = np.exp(-rng.uniform(0.1, 1.0, (B, S, di, st))).astype(np.float32)
    b = rng.normal(0, 1, (B, S, di, st)).astype(np.float32)
    C = rng.normal(0, 1, (B, S, st)).astype(np.float32)
    y, h_last = _ssm_scan_chunked(jnp.asarray(a), jnp.asarray(b), jnp.asarray(C), chunk=4)
    h = np.zeros((B, di, st), np.float64)
    ys = np.zeros((B, S, di))
    for t in range(S):
        h = a[:, t] * h + b[:, t]
        ys[:, t] = np.einsum("bds,bs->bd", h, C[:, t])
    np.testing.assert_allclose(np.asarray(y), ys, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_last), h, rtol=1e-4, atol=1e-4)


def test_attention_chunked_matches_direct():
    from repro.models import layers as L

    rng = np.random.default_rng(1)
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(0, 1, (B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)), jnp.float32)
    for causal, window, cap in [(True, 0, 0.0), (True, 16, 0.0), (False, 0, 0.0), (True, 0, 30.0)]:
        direct = L.attention_direct(q, k, v, causal=causal, window=window, cap=cap)
        chunked = L.attention_chunked(
            q, k, v, causal=causal, window=window, cap=cap, chunk_q=16, chunk_k=16
        )
        np.testing.assert_allclose(
            np.asarray(chunked), np.asarray(direct), rtol=2e-5, atol=2e-5,
            err_msg=f"causal={causal} window={window} cap={cap}",
        )


def test_param_count_analytic_vs_actual():
    for arch in ("gemma-2b", "granite-moe-1b-a400m", "falcon-mamba-7b",
                 "granite-4.0-h-small-ep8"):
        cfg = configs.smoke(arch)
        params = init_params(cfg, jax.random.key(0))
        actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        assert actual == cfg.n_params(), (arch, actual, cfg.n_params())


def test_full_config_param_counts():
    """Full (published) configs land near their nameplate sizes."""
    expect = {
        "falcon-mamba-7b": (6.5e9, 8.5e9),
        "qwen3-moe-30b-a3b": (28e9, 32e9),
        "jamba-v0.1-52b": (49e9, 56e9),
        "granite-moe-1b-a400m": (1.0e9, 1.7e9),
        "gemma2-2b": (2.2e9, 3.5e9),
        "gemma-2b": (2.0e9, 3.0e9),
        "minicpm3-4b": (3.5e9, 5.0e9),
        "h2o-danube-3-4b": (3.5e9, 4.6e9),
        "hubert-xlarge": (0.8e9, 1.3e9),
        "qwen2-vl-2b": (1.2e9, 2.3e9),
        "granite-4.0-h-small": (31e9, 33e9),  # "32B-A9B"
    }
    for arch, (lo, hi) in expect.items():
        n = configs.get_config(arch).n_params()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"
