"""AOT compiles of the moments kernel for a described TPU v5e chip.

Interpret mode (tests/test_kernels.py) cannot see Mosaic's layout and VMEM
rules; the TPU compiler can, without a chip attached.  The topology is
described inside a fixture, never at import, so every pytest worker
collects the same tests and only the worker running this file loads the
TPU library.
"""
import os

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
