"""granite-4.0-h-small [hybrid]: 40L d4096, 4 periods of 10 layers: Mamba-2
at 9 positions and GQA attention (32 heads, 8 KV, head 128, NoPE) at
position 5; every layer's MLP a MoE of 72 routed experts of 768 (SwiGLU,
top-10, softmax over the top-10 logits) plus a shared SwiGLU of 1,536;
vocab 100,352, tied, RMSNorm 1e-5.
[hf:ibm-granite/granite-4.0-h-small config.json (granitemoehybrid); hf]

Layer equations, with h the RMSNorm of the residual stream x:

* Mamba-2 (128 heads of 64, d_inner 8,192, d_state 128, 1 group, conv 4
  with bias, no projection bias): [z | xBC | dt] = h W_in (8,192 + 8,448
  + 128); xBC = SiLU(causal depthwise conv(xBC) + b), split into x, B, C;
  dt = softplus(dt + dt_bias), A = −exp(A_log) per head;
  h_t = exp(dt·A)·h_{t−1} + dt·(x_t ⊗ B_t), y_t = h_t·C_t + D·x_t;
  out = RMSNorm(y·SiLU(z)) over the 8,192 (eps 1e-5) W_out.
* Attention: causal GQA without positions, softmax scale
  ``attention_multiplier`` 0.0078125 (not 1/sqrt(128)).
* MoE: Σ_{e in top10} softmax(top-10 of h W_router)_e · expert_e(h)
  + shared(h).
* Granite's multipliers: embeddings x 12, each residual branch x 0.22,
  logits / 16.
"""
import jax.numpy as jnp

from repro.models.common import FULL, MAMBA2, MOE, LayerSpec, ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-small",
        family="hybrid",
        n_layers=40,
        d_model=4096,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        d_ff=768,
        vocab=100352,
        layout=tuple(LayerSpec(FULL if i == 5 else MAMBA2, MOE) for i in range(10)),
        pos="none",
        attn_scale=0.0078125,
        moe_experts=72,
        moe_topk=10,
        moe_dff=768,
        moe_shared_dff=1536,
        moe_n_held=72,  # every expert from its own key: a share is a slice
        ssm_d_state=128,
        ssm_d_conv=4,
        ssm_expand=2,
        ssm_n_heads=128,
        ssm_head_dim=64,
        ssm_n_groups=1,
        ssm_chunk=256,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        norm_eps=1e-5,
        tie_embeddings=True,
        param_dtype=jnp.bfloat16,
    )
