"""AOT compiles for a described TPU v5e chip: the moments kernel, and the
served decode step's op metadata.

Interpret mode (tests/test_kernels.py) cannot see Mosaic's layout and VMEM
rules; the TPU compiler can, without a chip attached.  The topology is
described inside a fixture, never at import, so every pytest worker
collects the same tests and only the worker running this file loads the
TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.moments import moments_and_labels

FRAME = 65_536  # events in one frame


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "num_funcs,num_events",
    [(128, FRAME), (2048, FRAME), (2048, FRAME + 777)],  # last: padded tail block
)
def test_moments_kernel_compiles_for_v5e(one_chip, num_funcs, num_events):
    assert one_chip.device_set.pop().device_kind == "TPU v5 lite"
    fids = jax.ShapeDtypeStruct((num_events,), jnp.int32, sharding=one_chip)
    durs = jax.ShapeDtypeStruct((num_events,), jnp.float32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((num_funcs, 5), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda f, d, t: moments_and_labels(f, d, t, interpret=False)
    ).lower(fids, durs, table).compile()
    assert "tpu_custom_call" in compiled.as_text()
    delta, labels = compiled.out_info
    assert delta.shape == (num_funcs, 5) and delta.dtype == jnp.float32
    assert labels.shape == (num_events,) and labels.dtype == jnp.int8


def _compile_decode_step_for_v5e(one_chip, arch, prompt, params_of=lambda cfg, p: p):
    """The served decode step of ``arch`` at published widths (batch 8,
    ``prompt`` + 128 cache slots), compiled for a described v5e on
    ``params_of(cfg, params)``: (cfg, the cache's shapes, HLO text)."""
    from repro import configs
    from repro.launch.steps import (StepOptions, build_decode_step, build_prefill_step,
                                    make_shard_ctx)
    from repro.models.common import init_params

    cfg = configs.get_config(arch)
    batch = 8
    opts = StepOptions()
    ctx = make_shard_ctx(cfg, None, batch, opts)

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: params_of(cfg, init_params(cfg, jax.random.key(0)))))
    prompts = on_chip({"tokens": jax.ShapeDtypeStruct((batch, prompt), jnp.int32)})
    prefill = build_prefill_step(cfg, ctx, opts, max_seq=prompt + 128)
    cache = on_chip(jax.eval_shape(prefill, params, prompts)[1])
    tokens = on_chip(jax.ShapeDtypeStruct((batch, 1), jnp.int32))
    text = jax.jit(build_decode_step(cfg, ctx, opts), donate_argnums=(1,)).lower(
        params, cache, tokens).compile().as_text()
    return cfg, cache, text


def _decode_step_for_v5e(one_chip, params_of):
    """The lines of granite's compiled decode step (1,024-token prompts) that
    convert the stacked expert weights to bfloat16."""
    cfg, _, text = _compile_decode_step_for_v5e(one_chip, "granite_moe_1b_a400m", 1024,
                                                params_of)
    expert = re.compile(rf"= bf16\[{cfg.n_layers},{cfg.moe_experts},\d+,\d+\]\S* convert\(")
    return [line for line in text.splitlines() if expert.search(line)]


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")


def _top_level_ops(text):
    """(name, shape, op) of every array-valued instruction of the entry
    computation and of the loop bodies it runs; fused computations are left
    out, since their ops write no buffer of their own."""
    comps, name = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = "ENTRY" if line.startswith("ENTRY") else m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    todo, seen, ops = ["ENTRY"], set(), []
    while todo:
        comp = todo.pop()
        seen.add(comp)
        for line in comps[comp]:
            todo += [b for b in re.findall(r"body=%([\w.-]+)", line) if b not in seen]
            m = _INSTRUCTION.match(line)
            if m:
                ops.append((m.group(1), f"{m.group(2)}[{m.group(3)}]", m.group(4)))
    return ops


@pytest.mark.parametrize("arch,prompt", [("granite_moe_1b_a400m", 1024),
                                         ("deepseek_v2_lite_ep8", 16384)])
def test_decode_step_writes_cache_rows_in_place_for_v5e(one_chip, arch, prompt):
    """At published widths, the decode step's program keeps every cache
    leaf aliased input to output, and neither its entry computation nor its
    layer loop copies or fuses out a positional cache leaf whole or one
    layer's (batch, slots, ...) slice of it: the cache is read in place and
    its only writes are the one-row ``dynamic-update-slice``s."""
    from repro.models.model import _CACHE_KINDS

    cfg, cache, text = _compile_decode_step_for_v5e(one_chip, arch, prompt)
    leaves = jax.tree_util.tree_leaves_with_path(cache)
    # outputs: the logits, then the cache's leaves in order
    aliased = {int(i) for i in re.findall(r"\{(\d+)\}: \(\d+,", text.splitlines()[0])}
    assert aliased == set(range(1, len(leaves) + 1)), aliased
    positional = [leaf for path, leaf in leaves
                  if _CACHE_KINDS.get(path[-1].key) in ("latent", "kv")]
    assert positional
    dt = {"bfloat16": "bf16", "int32": "s32"}

    def hlo(leaf, shape):
        return f"{dt[leaf.dtype.name]}[{','.join(map(str, shape))}]"

    whole = {hlo(leaf, leaf.shape) for leaf in positional}
    whole |= {hlo(leaf, leaf.shape[1:]) for leaf in positional if leaf.ndim > 2}
    ops = [op for op in _top_level_ops(text) if op[1] in whole]
    assert [op for op in ops if op[2] in ("copy", "fusion")] == []
    assert sum(op[2] == "dynamic-update-slice" for op in ops) == len(positional)


def test_decode_step_expert_casts_keep_their_scope_for_v5e(one_chip):
    """At published widths, each float32->bfloat16 convert of the stacked
    expert weights (the decode step's largest device cost) keeps the
    ``cast_params`` name scope through XLA's passes: ``decode_cast_share``
    reads it from the ops' metadata in a profiler trace."""
    casts = _decode_step_for_v5e(one_chip, lambda cfg, p: p)
    assert len(casts) == 3  # gate, up, down
    assert all('op_name="jit(decode_step)/decode/cast_params/' in line for line in casts)


def test_decode_step_on_compute_params_casts_no_expert_weight_for_v5e(one_chip):
    """On serve()'s compute-dtype copy the decode step converts no expert
    weight: the three casts above leave the compiled program."""
    from repro.models.model import compute_params

    assert _decode_step_for_v5e(one_chip, compute_params) == []
