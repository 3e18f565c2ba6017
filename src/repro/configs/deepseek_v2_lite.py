"""deepseek-v2-lite [moe]: 27L d2048 16H MLA (no q compression, kv_lora 512,
nope 128 + rope 64, v 128), YaRN rope (factor 40 over 4,096 positions), a
leading dense SwiGLU of 10,944, then 26 MoE layers of 64 routed experts of
1,408 (top-6 softmax, greedy, not renormalised, scale 1) and 2 shared
experts (one SwiGLU of 2,816), vocab 102,400, untied head, RMSNorm 1e-6.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; hf]

Layer equations, with h the RMSNorm of the residual stream x:

* MLA without q compression (H 16 heads, nope 128, rope 64, v 128):
  q = h W_q, split per head into q_nope (128) and q_pe (64);
  [c_kv | k_pe] = h W_kv_a, c_kv = RMSNorm(c_kv) (512), k_pe (64) shared
  by every head; [k_nope | v] = c_kv W_kv_b per head (128 + 128);
  q_pe and k_pe rotated by the rope below; scores = q·k over 192 dims
  times (1/sqrt(192))·m², causal softmax, then the heads' values through
  W_o.  m = 0.1·mscale_all_dim·ln(factor) + 1 = 0.1·0.707·ln 40 + 1.
* YaRN over the 64 rope dims: pair i has θ^(−2i/64) (θ 10,000) below
  the correction range, θ^(−2i/64)/40 above it, and a linear ramp
  between, the range from ``yarn_find_correction_range(32, 1, 64, 10000,
  4096)`` = (10, 23); cos/sin scale mscale(40, 0.707)/mscale(40, 0.707)
  = 1.  The pair layout is rotate-half on the columns as produced; the
  published code rotates interleaved pairs, which on seeded random
  weights is a fixed permutation of the 64 rope columns of W_q and W_kv_a.
* Layer 0 (``first_k_dense_replace`` 1): x += SwiGLU_10944(h).
* Layers 1..26: x += Σ_{e in top6} softmax(h W_router)_e · expert_e(h)
  + shared(h), with expert_e a SwiGLU of 1,408 and shared a SwiGLU of
  2,816 (the two shared experts side by side); the six weights are not
  renormalised and the routed scale is 1.
* Logits = RMSNorm(x) W_head, W_head untied from the embedding.
"""
import jax.numpy as jnp

from repro.models.common import MLA, MOE, LayerSpec, ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        head_dim=128,
        d_ff=10944,
        vocab=102400,
        layout=(LayerSpec(MLA, MOE),),
        first_k_dense=1,
        rope_theta=10000.0,
        yarn_factor=40.0,
        yarn_original_max_pos=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        q_lora_rank=0,
        kv_lora_rank=512,
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_head_dim=128,
        moe_experts=64,
        moe_topk=6,
        moe_dff=1408,
        moe_norm_topk=False,
        moe_shared_dff=2 * 1408,
        moe_n_held=64,
        norm_eps=1e-6,
        tie_embeddings=False,
        param_dtype=jnp.bfloat16,
    )
