"""Model operations and least bytes of one chip's share of a served Mamba-2
+ attention hybrid with a MoE in every layer (granite-4.0-h keys), from its
shapes.

Counts follow the configuration file's keys and count the work a request
needs on this chip, not what a program happens to do:

* a token through the layers costs 2 FLOPs per active weight held here:
  each Mamba-2 layer's in_proj, depthwise conv taps and out_proj, each
  attention layer's W_q, W_k, W_v, W_o, and in every layer the router,
  the shared expert and the routed experts at ``num_experts_per_tok`` x
  ``num_local_experts`` / ``program.router_experts`` experts a token
  (10 x 9/72 = 1.25 for the held share: uniform routing sends that many
  of a token's assignments here);
* the tied head (``vocab_size`` rows, padding excluded) is paid only at
  positions whose logits are used: the last prompt position and every
  decode step;
* the SSD costs 4·heads·head_dim·state FLOPs per token per Mamba-2 layer
  (the state update dt·x ⊗ B and the readout h·C), in prefill and decode
  alike: the recurrence's own work, so that the chunked prefill and the
  stepwise decode read the same work;
* attention costs 4·heads·head_dim FLOPs per causal (query, key) pair; a
  query at position p sees p + 1 keys.
"""
from __future__ import annotations

STATE_BYTES = 4  # the SSM state is float32 (program.ssm_state_dtype)


def _dims(c):
    d = c["hidden_size"]
    di = c["mamba_expand"] * d
    conv = di + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    return d, di, conv, c["num_hidden_layers"], c["vocab_size"]


def _kinds(c):
    """(Mamba-2 layers, attention layers) held here."""
    types = c["layer_types"][: c["num_hidden_layers"]]
    return types.count("mamba"), types.count("attention")


def mamba_weights(c) -> int:
    """in_proj to [z | x B C | dt], the conv taps, out_proj."""
    d, di, conv, *_ = _dims(c)
    return d * (di + conv + c["mamba_n_heads"]) + c["mamba_d_conv"] * conv + di * d


def attn_weights(c) -> int:
    d = c["hidden_size"]
    hd = d // c["num_attention_heads"]
    return 2 * d * c["num_attention_heads"] * hd + 2 * d * c["num_key_value_heads"] * hd


def expert_weights(c) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def moe_fixed_weights(c) -> int:
    """The weights of a layer's MoE every token reads: router, shared expert."""
    d = c["hidden_size"]
    return d * c["program"]["router_experts"] + 3 * d * c["shared_intermediate_size"]


def routed_per_token(c) -> float:
    """Held experts a token's assignments reach under uniform routing."""
    return c["num_experts_per_tok"] * c["num_local_experts"] / c["program"]["router_experts"]


def n_active(c) -> float:
    """Active weights per token: every layer held here, plus the head."""
    d, *_, V = _dims(c)
    n_mamba, n_attn = _kinds(c)
    moe = moe_fixed_weights(c) + routed_per_token(c) * expert_weights(c)
    return n_mamba * mamba_weights(c) + n_attn * attn_weights(c) + (n_mamba + n_attn) * moe + V * d


def ssd_token_flops(c) -> float:
    """The SSD of one token through every Mamba-2 layer: update and readout."""
    return 4.0 * c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"] * _kinds(c)[0]


def _attn_pairs_flops(c, pairs: float) -> float:
    d = c["hidden_size"]
    return 4.0 * d * _kinds(c)[1] * pairs  # heads x head_dim = hidden_size


def prefill_flops(c, batch: int, prompt: int) -> float:
    d, *_, V = _dims(c)
    body = (2.0 * (n_active(c) - V * d) + ssd_token_flops(c)) * batch * prompt
    head = 2.0 * V * d * batch
    return body + head + _attn_pairs_flops(c, batch * prompt * (prompt + 1) / 2.0)


def decode_step_flops(c, batch: int, pos: int) -> float:
    """One decode step: ``batch`` tokens at position ``pos``."""
    return ((2.0 * n_active(c) + ssd_token_flops(c)) * batch
            + _attn_pairs_flops(c, batch * (pos + 1)))


def wave_flops(c, batch: int, prompt: int, new: int) -> dict:
    """A wave of ``serve()``: one prefill, then ``new`` decode steps at
    positions prompt .. prompt + new - 1."""
    dec = sum(decode_step_flops(c, batch, prompt + t) for t in range(new))
    pre = prefill_flops(c, batch, prompt)
    return {"prefill": pre, "decode": dec, "total": pre + dec}


def experts_read(c, batch: int) -> float:
    """Held experts a decode step of ``batch`` tokens reaches in expectation
    under uniform top-k routing: held·(1 − ((E − k)/E)^batch), 8.18 of 9
    for 72 experts, top-10, batch 16."""
    E, k = c["program"]["router_experts"], c["num_experts_per_tok"]
    return c["num_local_experts"] * (1.0 - ((E - k) / E) ** batch)


def ssm_state_step_bytes(c, batch: int, cache_bytes: int) -> float:
    """State bytes a decode step must read and write, every Mamba-2 layer:
    the float32 SSM state (batch, heads, head_dim, state) and the conv tail
    (batch, d_conv - 1, conv channels) in the cache dtype."""
    _, _, conv, *_ = _dims(c)
    h = c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"] * STATE_BYTES
    tail = (c["mamba_d_conv"] - 1) * conv * cache_bytes
    return 2.0 * _kinds(c)[0] * batch * (h + tail)


def kv_cache_bytes(c, batch: int, pos: int, cache_bytes: int) -> float:
    """The attention layers' K and V of positions 0..pos."""
    d = c["hidden_size"]
    kv = 2 * c["num_key_value_heads"] * (d // c["num_attention_heads"])
    return float(_kinds(c)[1] * batch * (pos + 1) * kv) * cache_bytes


def decode_step_bytes(c, batch: int, pos: int, param_bytes: int, cache_bytes: int) -> float:
    """Least HBM bytes one decode step must move: every held weight a token
    reads (each layer's mixer, norms, router and shared expert), the held
    experts the step reaches in expectation, the head, ``batch`` embedding
    rows, the SSM state and conv tails read and written, and the K/V of
    positions 0..pos."""
    d, di, *_, V = _dims(c)
    n_mamba, n_attn = _kinds(c)
    moe = moe_fixed_weights(c) + experts_read(c, batch) * expert_weights(c) + 2 * d
    mixers = n_mamba * (mamba_weights(c) + conv_bias_and_heads(c) + di) + n_attn * attn_weights(c)
    weights = mixers + (n_mamba + n_attn) * moe + V * d + d
    return (float(weights + batch * d) * param_bytes + ssm_state_step_bytes(c, batch, cache_bytes)
            + kv_cache_bytes(c, batch, pos, cache_bytes))


def conv_bias_and_heads(c) -> int:
    """A Mamba-2 layer's vectors besides its gated norm: the conv bias and
    the per-head dt_bias, A_log and D."""
    _, _, conv, *_ = _dims(c)
    return conv + 3 * c["mamba_n_heads"]
