"""Model configuration and parameter initialization.

One unified config drives all 10 assigned architectures.  A model is a
period-repeated stack of blocks; each period position has a ``LayerSpec``
(mixer kind × mlp kind), so dense llama-likes, alternating local/global
gemma-2, 1:7 mamba:attention jamba, and MoE stacks all share one code path
(and one scan-over-periods compile structure, which keeps 512-device AOT
compiles tractable).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# Mixer kinds: how the sequence dimension is mixed.
FULL, SWA, MLA, MAMBA, MAMBA2 = "full", "swa", "mla", "mamba", "mamba2"
# MLP kinds.
DENSE, MOE, NONE = "dense", "moe", "none"


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str  # full | swa | mla | mamba | mamba2
    mlp: str  # dense | moe | none


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encoder | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    layout: Tuple[LayerSpec, ...]  # one period
    # attention details
    window: int = 4096  # SWA window
    attn_softcap: float = 0.0
    attn_scale: float = 0.0  # softmax scale; 0: 1/sqrt(head_dim)
    logit_softcap: float = 0.0
    causal: bool = True
    rope_theta: float = 10000.0
    pos: str = "rope"  # rope | mrope | none
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)  # pairs per (t, h, w)
    # activation
    activation: str = "silu"  # silu (swiglu) | geglu | gelu (dense, no gate)
    # YaRN context extension of the rope frequencies (factor 1: plain rope)
    yarn_factor: float = 1.0
    yarn_original_max_pos: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # MLA (DeepSeek/MiniCPM3-style latent attention); q_lora_rank 0: plain wq
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # MoE
    moe_experts: int = 0
    moe_topk: int = 0
    moe_dff: int = 0
    moe_capacity_factor: float = 1.25
    moe_norm_topk: bool = True  # renormalise the top-k weights to sum 1
    moe_shared_dff: int = 0  # width of the shared experts, as one SwiGLU
    # Expert parallelism: the experts held here, [offset, offset + n_held)
    # of moe_experts; 0 holds all of them.  Held experts are drawn one key
    # per global expert id, so a share is a slice of the whole layer.
    moe_n_held: int = 0
    moe_held_offset: int = 0
    # leading dense layers ahead of the period (layout[0]'s mixer, a d_ff MLP)
    first_k_dense: int = 0
    # Mamba (SSM)
    ssm_d_state: int = 16
    ssm_d_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0
    # Mamba-2 (SSD): heads of ssm_head_dim (ssm_n_heads x ssm_head_dim =
    # d_inner), B and C shared by the heads of each of ssm_n_groups groups,
    # the sequence pass scanned in chunks of ssm_chunk positions
    ssm_n_heads: int = 0
    ssm_head_dim: int = 0
    ssm_n_groups: int = 1
    ssm_chunk: int = 256
    # granite's multipliers: embeddings times embedding_multiplier, each
    # residual branch times residual_multiplier, logits over logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # misc
    norm_eps: float = 1e-6
    emb_scale: bool = False  # gemma: hidden *= sqrt(d_model)
    sandwich_norm: bool = False  # gemma2: post-norms after mixer/mlp
    tie_embeddings: bool = True
    modality: str = "text"  # text | audio_stub | vision_stub
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    # ------------------------------------------------------------- derived
    @property
    def period(self) -> int:
        return len(self.layout)

    @property
    def vocab_padded(self) -> int:
        """Embedding rows padded to a lane/shard-friendly multiple of 256.

        Odd published vocabularies (49155, 73448) neither tile the MXU nor
        shard 16-way; padding is standard practice.  Padded logit columns
        are masked to −inf in unembed() so the softmax is exact."""
        return ((self.vocab + 255) // 256) * 256

    @property
    def n_periods(self) -> int:
        n = self.n_layers - self.first_k_dense
        assert n % self.period == 0, (self.name, self.n_layers, self.period)
        return n // self.period

    @property
    def lead_spec(self) -> LayerSpec:
        """The leading dense layers' kind."""
        return LayerSpec(self.layout[0].mixer, DENSE)

    @property
    def n_experts_held(self) -> int:
        return self.moe_n_held or self.moe_experts

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_conv_dim(self) -> int:
        """Mamba-2's convolved channels: x, then B and C of every group."""
        return self.d_inner + 2 * self.ssm_n_groups * self.ssm_d_state

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or max(1, self.d_model // 16)

    @property
    def qk_dim(self) -> int:
        return (self.qk_nope_dim + self.qk_rope_dim) if self.mixer_has(MLA) else self.head_dim

    def mixer_has(self, kind: str) -> bool:
        return any(s.mixer == kind for s in self.layout)

    def mlp_has(self, kind: str) -> bool:
        return any(s.mlp == kind for s in self.layout)

    @property
    def is_encoder(self) -> bool:
        return not self.causal

    @property
    def attention_free(self) -> bool:
        return all(s.mixer in (MAMBA, MAMBA2) for s in self.layout)

    @property
    def subquadratic(self) -> bool:
        """Eligible for long_500k (SSM / hybrid-with-few-attn / pure-SWA)."""
        return (all(s.mixer in (MAMBA, MAMBA2, SWA) for s in self.layout)
                or self.family == "hybrid")

    def _layer_params(self, spec: LayerSpec) -> int:
        d, f = self.d_model, self.d_ff
        n = 0
        if spec.mixer in (FULL, SWA):
            n += d * self.n_heads * self.head_dim  # q
            n += 2 * d * self.n_kv_heads * self.head_dim  # k, v
            n += self.n_heads * self.head_dim * d  # o
        elif spec.mixer == MLA:
            qh = self.qk_nope_dim + self.qk_rope_dim
            if self.q_lora_rank:
                n += d * self.q_lora_rank + self.q_lora_rank * self.n_heads * qh
                n += self.q_lora_rank  # q_ln
            else:
                n += d * self.n_heads * qh
            n += d * (self.kv_lora_rank + self.qk_rope_dim)
            n += self.kv_lora_rank * self.n_heads * (self.qk_nope_dim + self.v_head_dim)
            n += self.n_heads * self.v_head_dim * d
            n += self.kv_lora_rank  # kv_ln
        elif spec.mixer == MAMBA:
            di = self.d_inner
            n += d * 2 * di + di * self.ssm_d_conv + di  # in_proj, conv_w, conv_b
            n += di * (self.dt_rank + 2 * self.ssm_d_state)  # x_proj
            n += self.dt_rank * di + di  # dt_proj, dt_bias
            n += di * self.ssm_d_state + di  # A_log, D
            n += di * d  # out_proj
        elif spec.mixer == MAMBA2:
            di, H, cd = self.d_inner, self.ssm_n_heads, self.ssm_conv_dim
            n += d * (di + cd + H)  # in_proj: z, x B C, dt
            n += self.ssm_d_conv * cd + cd  # conv_w, conv_b
            n += 3 * H  # dt_bias, A_log, D
            n += di + di * d  # ssm_norm, out_proj
        if spec.mlp == DENSE:
            n += (3 if self.activation in ("silu", "geglu") else 2) * d * f
        elif spec.mlp == MOE:
            n += d * self.moe_experts
            n += self.n_experts_held * 3 * d * self.moe_dff
            n += 3 * d * self.moe_shared_dff
        n += d  # ln1
        if spec.mlp != NONE:
            n += d  # ln2
        if self.sandwich_norm:
            n += d + (d if spec.mlp != NONE else 0)
        return n

    def n_params(self) -> int:
        """Analytic parameter count (for roofline MODEL_FLOPS): the experts
        held here, the leading dense layers, the periods."""
        d = self.d_model
        total = self.vocab_padded * d  # embedding
        if not self.tie_embeddings:
            total += self.vocab_padded * d
        total += self.first_k_dense * self._layer_params(self.lead_spec)
        total += self.n_periods * sum(self._layer_params(s) for s in self.layout)
        total += d  # final_ln
        return total

    def n_active_params(self) -> int:
        """Active params per token (MoE: only top-k experts count)."""
        if not self.mlp_has(MOE):
            return self.n_params()
        full = self.n_params()
        per_layer_moe = self.n_experts_held * 3 * self.d_model * self.moe_dff
        n_moe_layers = sum(1 for s in self.layout if s.mlp == MOE) * self.n_periods
        inactive = per_layer_moe * (1 - self.moe_topk / self.moe_experts)
        return int(full - n_moe_layers * inactive)


# ---------------------------------------------------------------- initializers
def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * std).astype(dtype)


def init_layer_params(cfg: ModelConfig, spec: LayerSpec, key) -> Dict[str, Any]:
    """Parameters for ONE period-position, stacked later over n_periods."""
    d, dt = cfg.d_model, cfg.param_dtype
    ks = iter(jax.random.split(key, 24))
    p: Dict[str, Any] = {"ln1": jnp.ones((d,), dt)}
    if spec.mixer in (FULL, SWA):
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        p["wq"] = _dense_init(next(ks), (d, H * hd), dt)
        p["wk"] = _dense_init(next(ks), (d, KV * hd), dt)
        p["wv"] = _dense_init(next(ks), (d, KV * hd), dt)
        p["wo"] = _dense_init(next(ks), (H * hd, d), dt)
    elif spec.mixer == MLA:
        H = cfg.n_heads
        qh = cfg.qk_nope_dim + cfg.qk_rope_dim
        if cfg.q_lora_rank:
            p["wdq"] = _dense_init(next(ks), (d, cfg.q_lora_rank), dt)
            p["q_ln"] = jnp.ones((cfg.q_lora_rank,), dt)
            p["wuq"] = _dense_init(next(ks), (cfg.q_lora_rank, H * qh), dt)
        else:
            p["wq"] = _dense_init(next(ks), (d, H * qh), dt)
        p["wdkv"] = _dense_init(next(ks), (d, cfg.kv_lora_rank + cfg.qk_rope_dim), dt)
        p["kv_ln"] = jnp.ones((cfg.kv_lora_rank,), dt)
        p["wuk"] = _dense_init(next(ks), (cfg.kv_lora_rank, H * cfg.qk_nope_dim), dt)
        p["wuv"] = _dense_init(next(ks), (cfg.kv_lora_rank, H * cfg.v_head_dim), dt)
        p["wo"] = _dense_init(next(ks), (H * cfg.v_head_dim, d), dt)
    elif spec.mixer == MAMBA:
        di, st, dc, dr = cfg.d_inner, cfg.ssm_d_state, cfg.ssm_d_conv, cfg.dt_rank
        p["in_proj"] = _dense_init(next(ks), (d, 2 * di), dt)
        p["conv_w"] = _dense_init(next(ks), (dc, di), dt, scale=1.0 / math.sqrt(dc))
        p["conv_b"] = jnp.zeros((di,), dt)
        p["x_proj"] = _dense_init(next(ks), (di, dr + 2 * st), dt)
        p["dt_proj"] = _dense_init(next(ks), (dr, di), dt)
        p["dt_bias"] = jnp.log(
            jnp.expm1(
                jnp.exp(
                    jax.random.uniform(
                        next(ks), (di,), minval=math.log(1e-3), maxval=math.log(1e-1)
                    )
                )
            )
        ).astype(dt)
        p["A_log"] = jnp.log(
            jnp.tile(jnp.arange(1, st + 1, dtype=jnp.float32), (di, 1))
        ).astype(dt)
        p["D"] = jnp.ones((di,), dt)
        p["out_proj"] = _dense_init(next(ks), (di, d), dt)
    elif spec.mixer == MAMBA2:
        # The published Mamba-2 recipe: A = 1..H, dt log-uniform in
        # [1e-3, 1e-1] through the inverse softplus, D = 1; the conv bias
        # as a depthwise Conv1d draws it, uniform in +-1/sqrt(d_conv).
        di, H, cd, dc = cfg.d_inner, cfg.ssm_n_heads, cfg.ssm_conv_dim, cfg.ssm_d_conv
        p["in_proj"] = _dense_init(next(ks), (d, di + cd + H), dt)
        p["conv_w"] = _dense_init(next(ks), (dc, cd), dt, scale=1.0 / math.sqrt(dc))
        bound = 1.0 / math.sqrt(dc)
        p["conv_b"] = jax.random.uniform(next(ks), (cd,), minval=-bound, maxval=bound).astype(dt)
        dt0 = jnp.exp(jax.random.uniform(next(ks), (H,), minval=math.log(1e-3),
                                         maxval=math.log(1e-1)))
        p["dt_bias"] = (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dt)
        p["A_log"] = jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)).astype(dt)
        p["D"] = jnp.ones((H,), dt)
        p["ssm_norm"] = jnp.ones((di,), dt)
        p["out_proj"] = _dense_init(next(ks), (di, d), dt)

    if spec.mlp == DENSE:
        f = cfg.d_ff
        p["ln2"] = jnp.ones((d,), dt)
        if cfg.activation in ("silu", "geglu"):
            p["w_gate"] = _dense_init(next(ks), (d, f), dt)
        p["w_up"] = _dense_init(next(ks), (d, f), dt)
        p["w_down"] = _dense_init(next(ks), (f, d), dt)
    elif spec.mlp == MOE:
        E, f = cfg.moe_experts, cfg.moe_dff
        p["ln2"] = jnp.ones((d,), dt)
        p["router"] = _dense_init(next(ks), (d, E), dt)
        if cfg.moe_n_held:
            ids = jnp.arange(cfg.moe_held_offset, cfg.moe_held_offset + cfg.moe_n_held)

            def experts(k, shape):  # expert i from fold_in(k, i): a share is a slice
                return jax.vmap(lambda i: _dense_init(jax.random.fold_in(k, i), shape, dt))(ids)

            p["moe_gate"] = experts(next(ks), (d, f))
            p["moe_up"] = experts(next(ks), (d, f))
            p["moe_down"] = experts(next(ks), (f, d))
        else:
            p["moe_gate"] = _dense_init(next(ks), (E, d, f), dt)
            p["moe_up"] = _dense_init(next(ks), (E, d, f), dt)
            p["moe_down"] = _dense_init(next(ks), (E, f, d), dt)
        if cfg.moe_shared_dff:
            fs = cfg.moe_shared_dff
            p["shared_gate"] = _dense_init(next(ks), (d, fs), dt)
            p["shared_up"] = _dense_init(next(ks), (d, fs), dt)
            p["shared_down"] = _dense_init(next(ks), (fs, d), dt)
    if cfg.sandwich_norm:
        p["post_ln1"] = jnp.ones((d,), dt)
        if spec.mlp != NONE:
            p["post_ln2"] = jnp.ones((d,), dt)
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    """Full parameter pytree. Layer params stacked over periods per position;
    the leading dense layers, where the config has them, under ``lead`` as
    a one-position layout stacked over ``first_k_dense``."""
    keys = jax.random.split(key, cfg.period + 3)
    params: Dict[str, Any] = {
        # 1/sqrt(d) keeps tied-unembed logits O(1) at init (emb_scale archs
        # multiply hidden states back up by sqrt(d)).  An embedding
        # multiplier is divided out, so the embedded input keeps that scale:
        # drawn at 1/sqrt(d), granite-4.0-h's x12 makes the input token's
        # own tied logit outweigh whatever the random layers compute.
        "embed": _dense_init(
            keys[-1], (cfg.vocab_padded, cfg.d_model), cfg.param_dtype,
            scale=1.0 / (math.sqrt(cfg.d_model) * cfg.embedding_multiplier),
        ),
        "final_ln": jnp.ones((cfg.d_model,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = _dense_init(
            keys[-2], (cfg.d_model, cfg.vocab_padded), cfg.param_dtype
        )
    if cfg.first_k_dense:
        lkeys = jax.random.split(keys[-3], cfg.first_k_dense)
        params["lead"] = [jax.vmap(lambda k: init_layer_params(cfg, cfg.lead_spec, k))(lkeys)]
    layers = []
    for pos, spec in enumerate(cfg.layout):
        pkeys = jax.random.split(keys[pos], cfg.n_periods)
        stacked = jax.vmap(lambda k: init_layer_params(cfg, spec, k))(pkeys)
        layers.append(stacked)
    params["layers"] = layers
    return params
