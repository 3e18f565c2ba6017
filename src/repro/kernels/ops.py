"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend (``JAX_PLATFORMS=cpu``, the test suite) kernels run in
the Pallas interpreter; on any other backend they compile to Mosaic, so a
run that finds no TPU fails instead of interpreting quietly.  ``ops`` also
does the shape hygiene (head-dim lane padding, event padding, format
conversion to the jax_ad (n, mean, M2, min, max) table layout).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import mamba_scan as _ms
from . import moments as _mo


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# ------------------------------------------------------------------ moments
def sums_to_stats(sums: jnp.ndarray) -> jnp.ndarray:
    """(n, Σx, Σx², min, max) -> (n, mean, M2, min, max) (jax_ad layout)."""
    n = sums[:, 0]
    mean = jnp.where(n > 0, sums[:, 1] / jnp.maximum(n, 1.0), 0.0)
    m2 = jnp.maximum(sums[:, 2] - n * mean * mean, 0.0)
    return jnp.stack([n, mean, m2, sums[:, 3], sums[:, 4]], axis=-1)


def stats_to_sums(table: jnp.ndarray) -> jnp.ndarray:
    n, mean, m2 = table[:, 0], table[:, 1], table[:, 2]
    return jnp.stack(
        [n, n * mean, m2 + n * mean * mean, table[:, 3], table[:, 4]], axis=-1
    )


@functools.partial(jax.jit, static_argnames=("alpha", "min_count"))
def moments_update(
    table: jnp.ndarray,  # (F, 5) jax_ad stats layout
    fids: jnp.ndarray,
    durs: jnp.ndarray,
    alpha: float = 6.0,
    min_count: float = 10.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Kernel-backed ad_step: label against ``table``, then fold events in."""
    sums = stats_to_sums(table)
    delta, labels = _mo.moments_and_labels(
        fids, durs, sums, alpha=alpha, min_count=min_count,
        interpret=_interpret(),
    )
    from repro.core.jax_ad import merge_tables

    new_table = merge_tables(table, sums_to_stats(delta))
    return new_table, labels


def moments_table(
    fids: jnp.ndarray, durs: jnp.ndarray, F: int, fid_offset: int = 0
) -> jnp.ndarray:
    """Kernel-backed batch_table (distributed AD's local reduction).

    With ``fid_offset``, computes the delta for the contiguous PS-shard
    block [fid_offset, fid_offset + F) only — the federated per-shard
    segment reduction (events outside the block are masked in-kernel).
    """
    zero = jnp.zeros((F, 5), jnp.float32)
    delta, _ = _mo.moments_and_labels(
        fids, durs, zero, fid_offset=fid_offset, interpret=_interpret()
    )
    return sums_to_stats(delta)


# ----------------------------------------------------------- flash attention
def _pad_lanes(x: jnp.ndarray, mult: int = 128) -> Tuple[jnp.ndarray, int]:
    hd = x.shape[-1]
    pad = (-hd) % mult
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, hd


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "cap", "scale", "block_q", "block_k", "kv_len"),
)
def flash_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    causal: bool = True, window: int = 0, cap: float = 0.0,
    scale: Optional[float] = None,
    block_q: int = 128, block_k: int = 128, kv_len: Optional[int] = None,
) -> jnp.ndarray:
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    qp, hd = _pad_lanes(q)
    kp, _ = _pad_lanes(k)
    vp, _ = _pad_lanes(v)
    out = _fa.flash_attention(
        qp, kp, vp, causal=causal, window=window, cap=cap, scale=scale,
        block_q=block_q, block_k=block_k, kv_len=kv_len, interpret=_interpret(),
    )
    return out[..., :hd]


# ----------------------------------------------------------------- mamba scan
@functools.partial(jax.jit, static_argnames=("block_d", "chunk"))
def mamba_scan(
    a: jnp.ndarray, b: jnp.ndarray, C: jnp.ndarray,
    block_d: int = 512, chunk: int = 128,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    di = a.shape[2]
    bd = min(block_d, di)
    while di % bd:
        bd //= 2
    return _ms.mamba_scan(
        a, b, C, block_d=bd, chunk=chunk, interpret=_interpret()
    )
