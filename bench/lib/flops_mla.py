"""Model operations and least bytes of one chip's share of a served MLA +
shared-expert MoE decoder (DeepSeek-V2 keys), from its shapes.

Counts follow the configuration file's keys and count the work a request
needs on this chip, not what a program happens to do:

* a token through the layers costs 2 FLOPs per active weight held here:
  the attention projections (a plain W_q, W_kv_a, W_uk, W_uv, W_o), the
  leading dense layers' SwiGLU, and in each MoE layer the router, the
  shared experts, and the routed experts at ``num_experts_per_tok`` x
  ``n_routed_experts`` / ``program.router_experts`` experts a token (6 x
  8/64 = 0.75 for the held share: uniform routing sends that many of a
  token's assignments here);
* the untied head (``vocab_size`` columns, padding excluded) is paid only
  at positions whose logits are used: the last prompt position and every
  decode step;
* attention costs 2·heads·(nope + rope + v) FLOPs per causal (query, key)
  pair (10,240 at DeepSeek-V2-Lite's 16 x (128 + 64 + 128)), in prefill
  and decode alike: the published form, so that the expanded prefill and
  the absorbed decode read the same work; a query at position p sees
  p + 1 keys.
"""
from __future__ import annotations


def _dims(c):
    return (c["hidden_size"], c["num_attention_heads"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"],
            c["num_hidden_layers"], c["first_k_dense_replace"], c["vocab_size"])


def attn_weights(c) -> int:
    """W_q (no q compression), W_kv_a, W_uk, W_uv, W_o."""
    d, H, nope, rope, vh, kvr, *_ = _dims(c)
    return d * H * (nope + rope) + d * (kvr + rope) + kvr * H * (nope + vh) + H * vh * d


def dense_layer_weights(c) -> int:
    """A leading dense layer: attention and a SwiGLU of ``intermediate_size``."""
    return attn_weights(c) + 3 * c["hidden_size"] * c["intermediate_size"]


def expert_weights(c) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def moe_layer_fixed_weights(c) -> int:
    """An MoE layer's weights every token reads: attention, router, shared experts."""
    d = c["hidden_size"]
    return (attn_weights(c) + d * c["program"]["router_experts"]
            + c["n_shared_experts"] * expert_weights(c))


def routed_per_token(c) -> float:
    """Held experts a token's assignments reach under uniform routing."""
    return c["num_experts_per_tok"] * c["n_routed_experts"] / c["program"]["router_experts"]


def n_active(c) -> float:
    """Active weights per token: every layer held here, plus the head."""
    d, *_, L, lead, V = _dims(c)
    moe = moe_layer_fixed_weights(c) + routed_per_token(c) * expert_weights(c)
    return lead * dense_layer_weights(c) + (L - lead) * moe + V * d


def _attn_pairs_flops(c, pairs: float) -> float:
    d, H, nope, rope, vh, kvr, L, *_ = _dims(c)
    return 2.0 * H * (nope + rope + vh) * L * pairs


def prefill_flops(c, batch: int, prompt: int) -> float:
    d, *_, V = _dims(c)
    body = 2.0 * (n_active(c) - V * d) * batch * prompt
    head = 2.0 * V * d * batch
    return body + head + _attn_pairs_flops(c, batch * prompt * (prompt + 1) / 2.0)


def decode_step_flops(c, batch: int, pos: int) -> float:
    """One decode step: ``batch`` tokens at position ``pos``."""
    return 2.0 * n_active(c) * batch + _attn_pairs_flops(c, batch * (pos + 1))


def wave_flops(c, batch: int, prompt: int, new: int) -> dict:
    """A wave of ``serve()``: one prefill, then ``new`` decode steps at
    positions prompt .. prompt + new - 1."""
    dec = sum(decode_step_flops(c, batch, prompt + t) for t in range(new))
    pre = prefill_flops(c, batch, prompt)
    return {"prefill": pre, "decode": dec, "total": pre + dec}


def experts_read(c, batch: int) -> float:
    """Held experts a decode step of ``batch`` tokens reaches in expectation
    under uniform top-k routing: held·(1 − ((E − k)/E)^batch), 4.36 for 8
    of 64 experts, top-6, batch 8."""
    E, k = c["program"]["router_experts"], c["num_experts_per_tok"]
    return c["n_routed_experts"] * (1.0 - ((E - k) / E) ** batch)


def latent_cache_bytes(c, batch: int, pos: int, cache_bytes: int) -> float:
    """The latent cache of positions 0..pos, every layer: c_kv and k_pe."""
    d, H, nope, rope, vh, kvr, L, *_ = _dims(c)
    return float(L * batch * (pos + 1) * (kvr + rope)) * cache_bytes


def decode_step_bytes(c, batch: int, pos: int, param_bytes: int, cache_bytes: int) -> float:
    """Least HBM bytes one decode step must move: every held weight a token
    reads (the leading dense layers, each MoE layer's attention, router and
    shared experts), the held experts the step reaches in expectation, the
    norms, the head, ``batch`` embedding rows, and the latent cache of
    positions 0..pos."""
    d, H, nope, rope, vh, kvr, L, lead, V = _dims(c)
    norms = 2 * d + kvr
    weights = (lead * (dense_layer_weights(c) + norms)
               + (L - lead) * (moe_layer_fixed_weights(c) + norms
                               + experts_read(c, batch) * expert_weights(c))
               + V * d + d)
    return float(weights + batch * d) * param_bytes + latent_cache_bytes(c, batch, pos, cache_bytes)
