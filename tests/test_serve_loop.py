"""serve()'s decode loop queues step n before it reads step n-1's tokens.
It must hand every request exactly the tokens of a plain greedy loop that
reads each step's tokens before it dispatches the next, built from the same
step functions, and run the decode step ``max_new`` times a wave.  Smoke
widths on the CPU, two waves, the last one padded."""
from __future__ import annotations

import numpy as np
import pytest

BATCH, PROMPT, NEW, SEED = 2, 8, 4, 5
N_REQUESTS = 3  # two waves, the second padded to the compiled batch
WAVES = -(-N_REQUESTS // BATCH)


def _read_then_dispatch(arch, prompts):
    """Each request's tokens from a greedy loop that reads a step's tokens
    before it dispatches the next step, on serve()'s weights and steps."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.launch.steps import (StepOptions, build_decode_step, build_prefill_step,
                                    make_shard_ctx)
    from repro.models.common import init_params
    from repro.models.model import compute_params

    cfg = configs.smoke(arch)
    opts = StepOptions()
    ctx = make_shard_ctx(cfg, None, BATCH, opts)
    params = compute_params(cfg, init_params(cfg, jax.random.key(SEED)))
    prefill = jax.jit(build_prefill_step(cfg, ctx, opts, max_seq=PROMPT + NEW))
    decode = jax.jit(build_decode_step(cfg, ctx, opts))
    outs = []
    for w in range(0, len(prompts), BATCH):
        wave = prompts[w : w + BATCH]
        padded = np.concatenate([wave, np.tile(wave[-1:], (BATCH - len(wave), 1))])
        logits, cache = prefill(params, {"tokens": jnp.asarray(padded)})
        tok = jnp.argmax(logits[:, -1], axis=-1)
        steps = []
        for _ in range(NEW):
            steps.append(np.asarray(tok))
            logits, cache = decode(params, cache, tok[:, None])
            tok = jnp.argmax(logits[:, 0], axis=-1)
        outs.extend(np.stack(steps, axis=1)[: len(wave)].tolist())
    return outs


@pytest.mark.parametrize("arch", ["granite_moe_1b_a400m", "deepseek_v2_lite_ep8",
                                  "granite_4_0_h_small_ep8"])
def test_serve_tokens_match_a_read_then_dispatch_loop(monkeypatch, arch):
    import jax

    from repro.launch import serve as S

    made, decode_runs = [], []

    class Req(S.Request):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    real = S.build_decode_step

    def build(cfg, ctx, opts):
        step = real(cfg, ctx, opts)

        def counted(params, cache, tokens):
            jax.debug.callback(lambda: decode_runs.append(1))
            return step(params, cache, tokens)

        return counted

    monkeypatch.setattr(S, "Request", Req)
    monkeypatch.setattr(S, "build_decode_step", build)
    out = S.serve(arch=arch, n_requests=N_REQUESTS, batch=BATCH, prompt_len=PROMPT,
                  max_new=NEW, seed=SEED)
    jax.effects_barrier()
    assert out["requests"] == len(made) == N_REQUESTS
    assert len(decode_runs) == WAVES * NEW
    want = _read_then_dispatch(arch, np.stack([r.prompt for r in made]))
    assert [r.out for r in made] == want
