"""Serve cells of a Mamba-2 + attention hybrid with a MoE in every layer
(granite-4.0-h keys, ``model_type`` granitemoehybrid).

The window, the set-up, the observation, the gap statistics and the check
(the widest and the mean gap of each served token below the reference's
best logit, limits ``widest_gap_limit`` and ``mean_gap_limit`` in the
traffic file) are ``drivers/serve_mla.py``'s ``run``, which is loaded and
run here, not copied.  What this driver brings is its configuration check
and its counts (``lib/flops_hybrid.py``), which that ``run`` reads in the
place of its own: it looks up ``_check_program_config`` in its module and
imports its counts as ``lib.flops_mla`` when it runs.  The readers of the
state-space mixer find the state bytes a decode step must move on ``R``.

The check adds ``state_bytes_off``, limit 0: the decode cache's state as
``serve()`` reports holding it (``repro_serve_cache_bytes{kind=state}``)
against the float32 state the configuration states.  A state held in
bfloat16 reads about half, where the gaps of a bfloat16 program hardly
move.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
from types import SimpleNamespace

import numpy as np


def _driver(name: str):
    """A private instance of ``drivers/<name>.py``."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_driver_{name}_for_hybrid", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MLA = _driver("serve_mla")

# The keys the program implements as the file gives them, and no other.
_FIXED = {"hidden_act": "silu", "attention_bias": False, "mamba_conv_bias": True,
          "mamba_proj_bias": False, "normalization_function": "rmsnorm",
          "position_embedding_type": "nope", "rope_scaling": None,
          "model_type": "granitemoehybrid"}


def _check_program_config(c: dict, mcfg) -> None:
    """The program's config must be the one the file states, and the file
    must ask only for what the program implements."""
    import jax.numpy as jnp

    p = c["program"]
    types = ["attention" if s.mixer == "full" else "mamba" if s.mixer == "mamba2" else s.mixer
             for s in mcfg.layout] * mcfg.n_periods
    want = {
        "d_model": c["hidden_size"], "n_layers": c["num_hidden_layers"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c["hidden_size"] // c["num_attention_heads"],
        "attn_scale": c["attention_multiplier"], "pos": "none",
        "ssm_n_heads": c["mamba_n_heads"], "ssm_head_dim": c["mamba_d_head"],
        "ssm_n_groups": c["mamba_n_groups"], "ssm_d_state": c["mamba_d_state"],
        "ssm_d_conv": c["mamba_d_conv"], "ssm_expand": c["mamba_expand"],
        "ssm_chunk": c["mamba_chunk_size"], "period": p["period"],
        "moe_experts": p["router_experts"], "n_experts_held": c["num_local_experts"],
        "moe_held_offset": p["held_offset"], "moe_topk": c["num_experts_per_tok"],
        "moe_dff": c["intermediate_size"], "moe_shared_dff": c["shared_intermediate_size"],
        "moe_norm_topk": True, "moe_capacity_factor": p["moe_capacity_factor"],
        "embedding_multiplier": c["embedding_multiplier"],
        "residual_multiplier": c["residual_multiplier"],
        "logits_scaling": c["logits_scaling"],
        "vocab": c["vocab_size"], "vocab_padded": p["embedding_rows"],
        "norm_eps": c["rms_norm_eps"], "tie_embeddings": c["tie_word_embeddings"],
        "param_dtype": jnp.dtype(p["param_dtype"]),
        "compute_dtype": jnp.dtype(p["compute_dtype"]),
    }
    got = {k: getattr(mcfg, k) for k in want}
    got["param_dtype"] = jnp.dtype(got["param_dtype"])
    got["compute_dtype"] = jnp.dtype(got["compute_dtype"])
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if types != c["layer_types"][: c["num_hidden_layers"]]:
        bad["layer_types"] = (types, c["layer_types"][: c["num_hidden_layers"]])
    if mcfg.ssm_n_heads * mcfg.ssm_head_dim != mcfg.d_inner:
        bad["mamba_n_heads x mamba_d_head"] = (mcfg.ssm_n_heads * mcfg.ssm_head_dim, mcfg.d_inner)
    if p["ssm_state_dtype"] != "float32":  # the decode cache's state leaves are float32
        bad["program.ssm_state_dtype"] = ("float32", p["ssm_state_dtype"])
    bad.update({k: (v, c[k]) for k, v in _FIXED.items() if c[k] != v})
    if bad:
        raise SystemExit(f"program config differs from the configuration file: {bad}")


def smoke_config(mcfg) -> dict:
    """A configuration dict for the program's smoke widths (CPU tests)."""
    import jax.numpy as jnp

    types = ["attention" if s.mixer == "full" else "mamba" for s in mcfg.layout]
    return {
        "name": "smoke", "hidden_size": mcfg.d_model, "intermediate_size": mcfg.moe_dff,
        "shared_intermediate_size": mcfg.moe_shared_dff, "num_hidden_layers": mcfg.n_layers,
        "layer_types": types * mcfg.n_periods, "num_attention_heads": mcfg.n_heads,
        "num_key_value_heads": mcfg.n_kv_heads, "attention_multiplier": mcfg.attn_scale,
        "mamba_n_heads": mcfg.ssm_n_heads, "mamba_d_head": mcfg.ssm_head_dim,
        "mamba_n_groups": mcfg.ssm_n_groups, "mamba_d_state": mcfg.ssm_d_state,
        "mamba_d_conv": mcfg.ssm_d_conv, "mamba_expand": mcfg.ssm_expand,
        "mamba_chunk_size": mcfg.ssm_chunk, "num_local_experts": mcfg.n_experts_held,
        "num_experts_per_tok": mcfg.moe_topk, "vocab_size": mcfg.vocab,
        "embedding_multiplier": mcfg.embedding_multiplier,
        "residual_multiplier": mcfg.residual_multiplier,
        "logits_scaling": mcfg.logits_scaling, "rms_norm_eps": mcfg.norm_eps,
        "tie_word_embeddings": mcfg.tie_embeddings,
        "program": {
            "arch": mcfg.name, "param_dtype": jnp.dtype(mcfg.param_dtype).name,
            "compute_dtype": jnp.dtype(mcfg.compute_dtype).name, "ssm_state_dtype": "float32",
            "moe_capacity_factor": mcfg.moe_capacity_factor, "router_experts": mcfg.moe_experts,
            "n_held": mcfg.n_experts_held, "held_offset": mcfg.moe_held_offset,
            "period": mcfg.period, "embedding_rows": mcfg.vocab_padded,
        },
    }


@contextlib.contextmanager
def _counts_as_mla(counts):
    """``counts`` as ``lib.flops_mla`` (what ``from lib import flops_mla``
    finds) while the MLA driver runs."""
    import lib

    saved = vars(lib).get("flops_mla")
    lib.flops_mla = counts
    try:
        yield
    finally:
        if saved is None:
            del lib.flops_mla
        else:
            lib.flops_mla = saved


def run(ctx) -> dict:
    from lib import flops_hybrid as F

    # The MLA driver's R keeps a latent cache size per position; a hybrid
    # has none, so its entry is dropped and the state's put in its place.
    counts = SimpleNamespace(wave_flops=F.wave_flops, decode_step_flops=F.decode_step_flops,
                             decode_step_bytes=F.decode_step_bytes,
                             latent_cache_bytes=lambda *a: 0.0)
    MLA._check_program_config = _check_program_config
    with _counts_as_mla(counts):
        res = MLA.run(ctx)
    c, B = ctx.config, ctx.traffic["batch"]
    R = res["R"]
    del R.latent_cache_bytes
    R.ssm_state_step_bytes = F.ssm_state_step_bytes(
        c, B, np.dtype(c["program"]["compute_dtype"]).itemsize)
    # a step reads and writes the state: half of that is what is held
    res["check"]["state_bytes_off"] = [abs(_state_cache_bytes() - R.ssm_state_step_bytes / 2), 0]
    return res


def _state_cache_bytes() -> float:
    """``repro_serve_cache_bytes{kind=state}``: the state ``serve()``'s last
    call held."""
    from repro.telemetry.registry import get_registry

    series = get_registry().snapshot()["repro_serve_cache_bytes"]["series"]
    return sum(v for k, v in series.items() if dict(json.loads(k)).get("kind") == "state")
