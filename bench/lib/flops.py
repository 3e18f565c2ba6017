"""Model operations and least bytes of a served MoE decoder, from its shapes.

Counts follow the configuration file's keys (Hugging Face names) and count
the work a request needs, not what a program happens to do:

* a token through the layers costs 2 FLOPs per active weight: attention
  projections, the router, ``num_experts_per_tok`` experts;
* the tied LM head (``vocab_size`` rows, padding excluded) is paid only at
  positions whose logits are used: the last prompt position and every
  decode step;
* attention scores and values cost 2·2·heads·head_dim FLOPs per
  (query, key) pair, causal: a query at position p sees p + 1 keys.
"""
from __future__ import annotations


def _dims(c):
    return (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["intermediate_size"], c["num_local_experts"],
            c["num_experts_per_tok"], c["num_hidden_layers"], c["vocab_size"])


def attn_weights(c) -> int:
    d, H, KV, hd, *_ = _dims(c)
    return d * H * hd + 2 * d * KV * hd + H * hd * d


def layer_active_weights(c) -> int:
    d, H, KV, hd, f, E, k, L, V = _dims(c)
    return attn_weights(c) + d * E + k * 3 * d * f


def n_active(c) -> int:
    """Active weights per token: all layers plus the tied LM head."""
    d, *_, L, V = _dims(c)
    return L * layer_active_weights(c) + V * d


def _attn_pairs_flops(c, pairs: float) -> float:
    d, H, KV, hd, f, E, k, L, V = _dims(c)
    return 4.0 * H * hd * L * pairs


def prefill_flops(c, batch: int, prompt: int) -> float:
    d, *_, L, V = _dims(c)
    body = 2.0 * L * layer_active_weights(c) * batch * prompt
    head = 2.0 * V * d * batch
    pairs = batch * prompt * (prompt + 1) / 2.0
    return body + head + _attn_pairs_flops(c, pairs)


def decode_step_flops(c, batch: int, pos: int) -> float:
    """One decode step: ``batch`` tokens at position ``pos``."""
    return 2.0 * n_active(c) * batch + _attn_pairs_flops(c, batch * (pos + 1))


def wave_flops(c, batch: int, prompt: int, new: int) -> dict:
    """A wave of ``serve()``: one prefill, then ``new`` decode steps at
    positions prompt .. prompt + new - 1."""
    dec = sum(decode_step_flops(c, batch, prompt + t) for t in range(new))
    pre = prefill_flops(c, batch, prompt)
    return {"prefill": pre, "decode": dec, "total": pre + dec}


def decode_step_bytes(c, batch: int, pos: int, param_bytes: int, kv_bytes: int) -> float:
    """Least HBM bytes one decode step must move: every non-expert weight,
    ``num_experts_per_tok`` experts per MoE layer, the tied LM head, norms,
    ``batch`` embedding rows, and the K/V cache of positions 0..pos."""
    d, H, KV, hd, f, E, k, L, V = _dims(c)
    weights = L * (layer_active_weights(c) + 2 * d) + V * d + d
    embed_rows = batch * d
    kv = L * batch * (pos + 1) * KV * hd * 2
    return float(weights + embed_rows) * param_bytes + float(kv) * kv_bytes
