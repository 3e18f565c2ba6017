"""On-device distributed anomaly detection — Chimbuko's PS as collectives.

TPU-native rethink of the paper's two-level AD architecture (§III-B): on a
pod, "on-node AD module" = the per-device shard of a shard_map'd program, and
the parameter-server merge of per-function moments is two ``psum``s (+
``pmin``/``pmax``) over the mesh — Pébay's parallel-moment formulas are
exactly an all-reduce of sufficient statistics:

    n      = Σ_k n_k                              (psum 1)
    μ      = Σ_k n_k μ_k / n                      (psum 1)
    M2     = Σ_k [ M2_k + n_k (μ_k − μ)² ]        (psum 2, needs μ)

Per-device event batches never leave the chip; only (F, 5) statistic tables
cross the ICI — the paper's "process data where it is produced" principle.

Device tables are (F, 5) float32: [n, mean, M2, min, max].  Events are
(fids int32, durations f32); fid < 0 marks padding.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

N, MEAN, M2, MIN, MAX = range(5)
NCOLS = 5
DEFAULT_ALPHA = 6.0


def init_table(num_funcs: int, dtype=jnp.float32) -> jnp.ndarray:
    t = jnp.zeros((num_funcs, NCOLS), dtype)
    t = t.at[:, MIN].set(jnp.inf)
    t = t.at[:, MAX].set(-jnp.inf)
    return t


def batch_table(fids: jnp.ndarray, durs: jnp.ndarray, num_funcs: int) -> jnp.ndarray:
    """Exact per-fid batch moments via segment reductions (ref for the kernel)."""
    valid = fids >= 0
    w = valid.astype(jnp.float32)
    seg = jnp.clip(fids, 0, num_funcs - 1)
    x = durs.astype(jnp.float32)
    n = jnp.zeros(num_funcs, jnp.float32).at[seg].add(w)
    s = jnp.zeros(num_funcs, jnp.float32).at[seg].add(w * x)
    mean = jnp.where(n > 0, s / jnp.maximum(n, 1.0), 0.0)
    d = x - mean[seg]
    m2 = jnp.zeros(num_funcs, jnp.float32).at[seg].add(w * d * d)
    big = jnp.float32(jnp.inf)
    mn = jnp.full(num_funcs, big).at[seg].min(jnp.where(valid, x, big))
    mx = jnp.full(num_funcs, -big).at[seg].max(jnp.where(valid, x, -big))
    return jnp.stack([n, mean, m2, mn, mx], axis=-1)


def merge_tables(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Pairwise Pébay merge of two (F, 5) tables (exact, assoc/comm)."""
    na, nb = a[:, N], b[:, N]
    n = na + nb
    safe = jnp.maximum(n, 1.0)
    delta = b[:, MEAN] - a[:, MEAN]
    mean = a[:, MEAN] + delta * nb / safe
    m2 = a[:, M2] + b[:, M2] + delta * delta * na * nb / safe
    mn = jnp.minimum(a[:, MIN], b[:, MIN])
    mx = jnp.maximum(a[:, MAX], b[:, MAX])
    out = jnp.stack([n, jnp.where(n > 0, mean, 0.0), jnp.where(n > 0, m2, 0.0), mn, mx], -1)
    return out


def label_events(
    table: jnp.ndarray,
    fids: jnp.ndarray,
    durs: jnp.ndarray,
    alpha: float = DEFAULT_ALPHA,
    min_count: float = 10.0,
) -> jnp.ndarray:
    """SSTD labels (int8) for events against a stats table."""
    seg = jnp.clip(fids, 0, table.shape[0] - 1)
    n = table[seg, N]
    mu = table[seg, MEAN]
    sd = jnp.sqrt(jnp.maximum(jnp.where(n > 1, table[seg, M2] / jnp.maximum(n, 1.0), 0.0), 0.0))
    x = durs.astype(jnp.float32)
    out = ((x > mu + alpha * sd) | (x < mu - alpha * sd)) & (n >= min_count) & (fids >= 0)
    return out.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("alpha", "min_count"))
def ad_step(
    table: jnp.ndarray,
    fids: jnp.ndarray,
    durs: jnp.ndarray,
    alpha: float = DEFAULT_ALPHA,
    min_count: float = 10.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-instance AD step: label against current table, then update."""
    labels = label_events(table, fids, durs, alpha, min_count)
    new_table = merge_tables(table, batch_table(fids, durs, table.shape[0]))
    return new_table, labels


def _merge_across(local: jnp.ndarray, axes) -> jnp.ndarray:
    """Multi-way Pébay merge across mesh axes = 2 psums + pmin/pmax."""
    n_l, mu_l, m2_l = local[:, N], local[:, MEAN], local[:, M2]
    n_g = jax.lax.psum(n_l, axes)
    s_g = jax.lax.psum(n_l * mu_l, axes)
    mu_g = jnp.where(n_g > 0, s_g / jnp.maximum(n_g, 1.0), 0.0)
    m2_g = jax.lax.psum(m2_l + n_l * (mu_l - mu_g) ** 2, axes)
    mn_g = jax.lax.pmin(local[:, MIN], axes)
    mx_g = jax.lax.pmax(local[:, MAX], axes)
    return jnp.stack([n_g, mu_g, m2_g, mn_g, mx_g], -1)


def make_distributed_ad_step(
    mesh: Mesh,
    axis_names=("ranks",),
    alpha: float = DEFAULT_ALPHA,
    min_count: float = 10.0,
    use_pallas: bool = False,
    func_axis: Optional[str] = None,
):
    """Build the pod-wide AD step: events sharded over ``axis_names``.

    Args to the returned fn:
      table: (F, 5) global table — replicated when ``func_axis`` is None,
             sharded ``P(func_axis)`` on dim 0 otherwise (F divisible by the
             ``func_axis`` mesh size; see :func:`padded_num_funcs`)
      fids:  (R, E) int32, sharded over axis_names on dim 0
      durs:  (R, E) f32,   sharded likewise
    Returns (new_table, labels sharded like events).

    ``func_axis`` mirrors the host-side PS federation (core/ps.py) on the
    mesh: each ``func_axis`` slice owns the contiguous fid block
    [shard·Fs, (shard+1)·Fs) of the stats table, merges only its own rows
    across ranks (psum over ``axis_names`` — per-shard PS work independent
    of both rank count *and* total function count), and labels only the
    events it owns; a psum over ``func_axis`` reassembles complete labels.
    With a size-1 ``func_axis`` (or ``func_axis=None``) this degenerates to
    the original single-instance all-reduce.  Contiguous blocks (not the
    host PS's cyclic slices) keep each device's table rows dense for
    VMEM/BlockSpec friendliness.
    """
    if use_pallas:
        from repro.kernels import ops as _kops

        _batch = lambda f, d, F: _kops.moments_table(f, d, F)
    else:
        _batch = batch_table

    ax = axis_names if isinstance(axis_names, tuple) else (axis_names,)

    if func_axis is None:

        def _shard_fn(table, fids, durs):
            F = table.shape[0]
            f = fids.reshape(-1)
            d = durs.reshape(-1)
            labels = label_events(table, f, d, alpha, min_count).reshape(fids.shape)
            local = _batch(f, d, F)
            global_delta = _merge_across(local, ax)
            new_table = merge_tables(table, global_delta)
            return new_table, labels

        table_spec = P()
    else:

        def _shard_fn(table, fids, durs):
            Fs = table.shape[0]  # this shard's contiguous block of fids
            base = jax.lax.axis_index(func_axis) * Fs
            f = fids.reshape(-1)
            d = durs.reshape(-1)
            # Rebase into shard-local rows; non-owned events become padding.
            f_local = jnp.where((f >= base) & (f < base + Fs), f - base, -1)
            owned_labels = label_events(table, f_local, d, alpha, min_count)
            # Each event is owned by exactly one funcs shard — summing the
            # per-shard label vectors reassembles the full labeling.
            labels = (
                jax.lax.psum(owned_labels.astype(jnp.int32), func_axis)
                .astype(jnp.int8)
                .reshape(fids.shape)
            )
            local = _batch(f_local, d, Fs)
            shard_delta = _merge_across(local, ax)  # ranks only, per shard
            new_table = merge_tables(table, shard_delta)
            return new_table, labels

        table_spec = P(func_axis)

    fn = shard_map(
        _shard_fn,
        mesh=mesh,
        in_specs=(table_spec, P(ax), P(ax)),
        out_specs=(table_spec, P(ax)),
        # pallas_call has no replication rule; the specs above are still
        # sound (outputs are psum-reduced over the axes they omit).
        check_vma=not use_pallas,
    )
    return jax.jit(fn)


def padded_num_funcs(num_funcs: int, num_shards: int) -> int:
    """Smallest F' >= num_funcs divisible by the funcs-axis mesh size."""
    return -(-num_funcs // num_shards) * num_shards


def straggler_scores(step_times: jnp.ndarray, alpha: float = 3.0) -> jnp.ndarray:
    """Per-rank straggler z-scores from one step's (R,) phase times.

    Used by the training monitor: ranks whose step time exceeds μ + ασ are
    flagged for mitigation (the workflow-level use of the paper's detector).
    """
    mu = step_times.mean()
    sd = jnp.maximum(step_times.std(), 1e-9)
    return (step_times - mu) / sd
