"""Pallas TPU kernel: Chimbuko's AD hot loop (per-function moments + labels).

The paper's on-node AD module folds each trace frame into per-function
runtime statistics and labels events against μ±ασ (§III-B1).  On TPU the
segment-reduction is *rethought for the MXU*: instead of scatter/gather
(slow, serializing on TPU), a block of events becomes a one-hot matrix
(events × functions) and the statistics are matmuls on the systolic array
(each f32 operand split into three bf16 terms, see ``_bf16_terms``):

    n_f   = 1ᵀ  · onehot        Σx_f = xᵀ · onehot        Σx²_f = (x²)ᵀ · onehot

Gathers of μ/σ per event for labeling reuse the same one-hot (table read
back through the MXU).  min/max fall to the VPU via masked reductions.

Grid: 1-D over event blocks; the (F, 5) accumulator table lives in VMEM
scratch across grid steps and is flushed to the output on the last step.
Blocks: EB=1024 events; F ≤ 2048 functions per table tile (each f32
(EB, F) temporary of the min/max pass takes 1024×2048×4 B = 8 MiB of VMEM,
the bf16 one-hot half that).

Mosaic layout rules the kernel is shaped by: a 1-D int32/f32 operand gets
XLA's ``T(1024)`` tiling on TPU, so a 1-D event block must hold 1024 events
(or the whole, shorter, event vector); and a boolean vector cannot be
widened to int8 in-kernel, so labels leave the kernel as int32 and are
narrowed to int8 outside it.

Padding: fid < 0 marks padding events (weight 0, label 0).

Federation: PS shards own contiguous fid blocks [offset, offset + F).  For
callers whose shard offset is a static Python int (host-driven per-shard
reductions over one event stream), ``fid_offset`` rebases global fids into
shard-local rows inside the kernel; events outside the block are masked out
exactly like padding, so a shard's delta covers only the rows it owns.  The
traced ``func_axis`` path in core/jax_ad.py gets its offset from
``axis_index`` (dynamic), so it rebases with a ``jnp.where`` before the call
and keeps ``fid_offset=0`` — the in-kernel bounds masking still drops the
out-of-shard events it maps to -1.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
POS = 1e30


def _bf16_terms(v: jnp.ndarray):
    """Split f32 ``v`` into three bf16 terms whose f32 sum is ``v``.

    A 0/1 one-hot times a bf16 term is exact on the MXU, so one one-pass
    bf16 matmul per term (f32 accumulation) gathers or segment-sums f32
    values exactly; a one-pass f32 matmul would round them to 8 bits.
    """
    hi = v.astype(jnp.bfloat16)
    r = v - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _moments_kernel(
    fids_ref, durs_ref, table_ref, out_ref, labels_ref, acc_ref,
    *, alpha: float, min_count: float, F: int, fid_offset: int,
):
    ib = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(ib == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        acc_ref[:, 3] = jnp.full((F,), POS, jnp.float32)
        acc_ref[:, 4] = jnp.full((F,), NEG, jnp.float32)

    fids = fids_ref[...] - fid_offset  # (EB,) int32, rebased to shard rows
    x = durs_ref[...]  # (EB,) f32
    valid = (fids >= 0) & (fids < F)  # padding + out-of-shard events drop out
    EB = fids.shape[0]

    # one-hot on the MXU: (EB, F); 0/1 is exact in bf16.  Padding and
    # out-of-shard fids fall outside [0, F) and so match no column.
    iota_f = jax.lax.broadcasted_iota(jnp.int32, (EB, F), 1)
    hit = iota_f == fids[:, None]
    onehot = hit.astype(jnp.bfloat16)

    # ---- labeling against the PREVIOUS global table (paper semantics) ----
    # (EB, F) x (F, 3) matmuls read every event's n, Σx, Σx² back.
    rows = sum(
        jnp.dot(onehot, t, preferred_element_type=jnp.float32)
        for t in _bf16_terms(table_ref[:, :3])
    )  # (EB, 3)
    n_prev, s_prev, q_prev = rows[:, 0], rows[:, 1], rows[:, 2]
    mu = jnp.where(n_prev > 0, s_prev / jnp.maximum(n_prev, 1.0), 0.0)
    var = jnp.maximum(
        jnp.where(n_prev > 1, q_prev / jnp.maximum(n_prev, 1.0) - mu * mu, 0.0), 0.0
    )
    sd = jnp.sqrt(var)
    out = ((x > mu + alpha * sd) | (x < mu - alpha * sd)) & (n_prev >= min_count) & valid
    labels_ref[...] = out.astype(jnp.int32)

    # ---- moment accumulation on the MXU ----------------------------------
    stacked = jnp.stack([jnp.ones_like(x), x, x * x], axis=0)  # (3, EB)
    sums = sum(
        jnp.dot(t, onehot, preferred_element_type=jnp.float32)
        for t in _bf16_terms(stacked)
    )  # (3, F)
    masked = jnp.where(hit, x[:, None], POS)
    mins = jnp.min(masked, axis=0)
    masked = jnp.where(hit, x[:, None], NEG)
    maxs = jnp.max(masked, axis=0)
    acc_ref[:, 0] += sums[0]
    acc_ref[:, 1] += sums[1]
    acc_ref[:, 2] += sums[2]
    acc_ref[:, 3] = jnp.minimum(acc_ref[:, 3], mins)
    acc_ref[:, 4] = jnp.maximum(acc_ref[:, 4], maxs)

    @pl.when(ib == nb - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def moments_and_labels(
    fids: jnp.ndarray,
    durs: jnp.ndarray,
    table_sums: jnp.ndarray,
    *,
    alpha: float = 6.0,
    min_count: float = 10.0,
    block_events: int = 1024,
    fid_offset: int = 0,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (delta table (F,5) [n,Σx,Σx²,min,max], labels (N,) int8).

    ``table_sums`` is the previous global table in raw-sums format.
    ``fid_offset`` rebases global fids: the delta covers the contiguous
    shard block [fid_offset, fid_offset + F); other events are masked.
    """
    N = fids.shape[0]
    F = table_sums.shape[0]
    EB = min(block_events, max(N, 1))
    pad = (-N) % EB if N else EB
    if pad:
        fids = jnp.concatenate([fids, jnp.full((pad,), -1, fids.dtype)])
        durs = jnp.concatenate([durs, jnp.zeros((pad,), durs.dtype)])
    nb = fids.shape[0] // EB
    kernel = functools.partial(
        _moments_kernel, alpha=alpha, min_count=min_count, F=F,
        fid_offset=fid_offset,
    )
    delta, labels = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((EB,), lambda i: (i,)),
            pl.BlockSpec((EB,), lambda i: (i,)),
            pl.BlockSpec((F, 5), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((F, 5), lambda i: (0, 0)),
            pl.BlockSpec((EB,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((F, 5), jnp.float32),
            jax.ShapeDtypeStruct((N + pad,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((F, 5), jnp.float32)],
        interpret=interpret,
    )(fids, durs.astype(jnp.float32), table_sums.astype(jnp.float32))
    return delta, labels[:N].astype(jnp.int8)
