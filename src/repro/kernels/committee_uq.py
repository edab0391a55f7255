"""Pallas TPU kernel for fused committee uncertainty quantification.

One streaming pass over the committee axis computes everything the
acquisition engine (core/acquisition.py) needs — for BOTH the exchange
loop's central check and the Manager's ``dynamic_oracle_list``
re-prioritization:

  * committee mean                       (n, d)  fp32
  * scalar disagreement per sample       (n,)    fp32  — max over output
    components of the ddof=1 std (the quantity the paper thresholds)
  * component disagreement per sample    (n,)    fp32  — mean over output
    components of the same std (the ``adjust_input_for_oracle`` ranking
    score), finalized from the same Welford state at zero extra passes
  * uncertainty mask ``scalar_std > threshold``  (n,)  bool
  * finite-member count per sample       (n,)    int32 — members with any
    non-finite output component are quarantined out of the statistics
    (degraded-K mean/std) inside the same pass; the count is the
    degradation signal surfaced as ``UQResult.finite_members``

The K axis is the sequential innermost grid dimension; per-row Welford
state (running mean in its output ref, running M2 and finite count in VMEM
scratch) is carried across committee members, so the (K, n, d) prediction
tensor is never materialized anywhere outside the committee forward
itself — the controller transfers only the small per-row outputs to host.

Layout (what Mosaic accepts on a TPU): every ref is 2-D.  Rows are the
sublane axis, blocked in multiples of 8; the trailing output dim d is the
lane axis (a full-array block dim, so any d is legal).  The four per-row
statistics leave the kernel as ONE lane-dense ``(n, 128)`` fp32 slab —
columns ``(scalar_std, component_std, mask, finite)`` — written with a
lane-iota select, so no 1-D block, no 1-D<->2-D shape cast and no sub-32-bit
output is involved; the wrapper slices the columns back out.  Validated
against ``ref.committee_uq_ref`` with ``interpret=True`` in
tests/test_committee_uq.py and compiled for a v5e in
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# lane width of a TPU vector register: the per-row statistics slab is one
# lane-dense (bn, 128) fp32 block, its first four columns used
_LANES = 128
_SSTD, _CSTD, _MASK, _FINITE = range(4)  # slab columns


def _kernel(preds_ref, mean_ref, stats_ref, m2_ref, cnt_ref, *,
            n_members: int, threshold: float):
    """One grid step: fold committee member ``k`` into the Welford state
    of one row block.

    Refs (shapes per block, bn = row-block size, d = output components):

      ``preds_ref``  (1, bn, d)  in   — member k's predictions for the block
      ``mean_ref``   (bn, d)     out  — running masked mean; after k = K-1
                                        the committee mean over FINITE
                                        members (Welford: ``mean +=
                                        (x - mean) / cnt`` where cnt counts
                                        only finite rows)
      ``stats_ref``  (bn, 128)   out  — written once at k = K-1, columns
                                        ``_SSTD``: MAX over d of
                                        ``sqrt(M2 / (cnt-1))`` (ddof=1 over
                                        the finite members); ``_CSTD``: MEAN
                                        over d of the same std; ``_MASK``:
                                        1.0 where ``scalar_std > threshold``
                                        AND at least one member is finite;
                                        ``_FINITE``: the finite-member count
      ``m2_ref``     (bn, d)     VMEM — running sum of squared deviations
                                        (``M2 += delta * (x - new_mean)``)
      ``cnt_ref``    (bn, 1)     VMEM — running count of finite members
                                        per row (fp32)

    K is the sequential innermost grid dimension, so the mean output block
    and the scratch persist across the k steps as carried state — the
    classic streaming-statistics trick that keeps the (K, n, d) tensor out
    of memory.  ``@pl.when`` guards split init (k=0) / accumulate (k>0) /
    finalize (k=K-1); with K=1 the k=0 branch also finalizes to std 0.

    Every row quantity keeps a trailing lane dim of 1 (``keepdims``), and
    the row-finite flag is broadcast across lanes as fp32 before it
    becomes a mask, so Mosaic never sees a 1-D vector or a boolean
    broadcast.

    Member quarantine: a member whose row has ANY non-finite component is
    excluded from the fold for that row (its delta is zeroed BEFORE it can
    contaminate mean/M2 — 0 * NaN would be NaN, hence the double where).
    ``|x| < inf`` is False for NaN and for +-inf alike.  With all members
    finite ``cnt`` equals ``k + 1`` at every step and the recurrence is
    bit-identical to the unmasked Welford fold.
    """
    k = pl.program_id(1)
    x = preds_ref[0].astype(jnp.float32)                     # (bn, d)
    bad = jnp.max(jnp.where(jnp.abs(x) < jnp.inf, 0.0, 1.0),
                  axis=-1, keepdims=True)                    # (bn, 1)
    finf = 1.0 - bad                                         # (bn, 1)
    keep = jnp.broadcast_to(finf, x.shape) > 0.0             # (bn, d)

    @pl.when(k == 0)
    def _init():
        mean_ref[...] = jnp.where(keep, x, 0.0)
        m2_ref[...] = jnp.zeros_like(x)
        cnt_ref[...] = finf

    @pl.when(k > 0)
    def _welford():
        mean = mean_ref[...]
        cnt = cnt_ref[...] + finf                            # (bn, 1)
        delta = jnp.where(keep, x - mean, 0.0)
        mean = mean + delta / jnp.maximum(cnt, 1.0)
        m2_ref[...] += delta * jnp.where(keep, x - mean, 0.0)
        mean_ref[...] = mean
        cnt_ref[...] = cnt

    @pl.when(k == n_members - 1)
    def _finalize():
        cnt = cnt_ref[...]                                   # (bn, 1)
        m2 = m2_ref[...]
        var = m2 / jnp.maximum(cnt - 1.0, 1.0)               # ddof=1
        var = jnp.where(jnp.broadcast_to(cnt, m2.shape) >= 2.0, var, 0.0)
        std = jnp.sqrt(var)                                  # (bn, d)
        sstd = jnp.max(std, axis=-1, keepdims=True)          # (bn, 1)
        cstd = jnp.mean(std, axis=-1, keepdims=True)         # (bn, 1)
        hit = jnp.where((sstd > threshold) & (cnt > 0.0), 1.0, 0.0)
        lane = jax.lax.broadcasted_iota(jnp.int32, stats_ref.shape, 1)
        stats_ref[...] = jnp.where(
            lane == _SSTD, sstd, jnp.where(
                lane == _CSTD, cstd, jnp.where(lane == _MASK, hit, cnt)))


def committee_uq(
    preds: jnp.ndarray,      # (K, n, d) committee predictions
    threshold: float,
    *,
    block_n: int = 128,
    interpret: bool = False,
):
    """Fused mean / ddof=1 std statistics / threshold mask over the K axis.

    Returns the 5-tuple ``(mean (n, d) fp32, scalar_std (n,) fp32,
    component_std (n,) fp32, mask (n,) bool, finite (n,) int32)`` —
    scalar_std is the max-over-components std (the exchange check
    quantity), component_std the mean-over-components std (the oracle
    re-prioritization score); both finalize from the SAME single Welford
    pass, so the Manager's ``dynamic_oracle_list`` score costs no extra
    reduction.  ``finite`` counts, per row, the committee members whose
    outputs were finite — members with any non-finite component are
    quarantined out of the statistics inside the same pass (degraded-K
    mean/std; see ``ref.committee_uq_ref`` for the exact semantics), so a
    diverged member degrades UQ quality instead of poisoning it, at zero
    extra dispatches.

    Row blocking: n rows fit one block when ``n <= block_n`` (the block is
    then the whole row axis, legal at any n).  Larger n is processed in
    blocks of ``block_n`` rounded up to a multiple of 8 (the fp32 sublane
    tile) and padded up to a whole number of blocks; padding rows carry
    zeros through the Welford state (std 0, mask 0) and are sliced off
    before returning, so callers always see exactly n rows.  The
    acquisition engine's power-of-two shape buckets
    (``committee.shape_bucket``, 8 and up) are multiples of 8, so for
    them the pad is a no-op.  ``interpret=True`` runs the same kernel
    under the Pallas interpreter (CPU validation; tests/test_committee_uq.py
    checks parity against ``ref.committee_uq_ref``).
    """
    K, n, d = preds.shape
    bn = n if n <= block_n else -(-block_n // 8) * 8
    pad = (-n) % bn
    if pad:
        preds = jnp.pad(preds, ((0, 0), (0, pad), (0, 0)))
    npad = n + pad

    kernel = functools.partial(_kernel, n_members=K,
                               threshold=float(threshold))
    mean, stats = pl.pallas_call(
        kernel,
        grid=(npad // bn, K),
        in_specs=[pl.BlockSpec((1, bn, d), lambda i, k: (k, i, 0))],
        out_specs=[pl.BlockSpec((bn, d), lambda i, k: (i, 0)),
                   pl.BlockSpec((bn, _LANES), lambda i, k: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((npad, d), jnp.float32),
                   jax.ShapeDtypeStruct((npad, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32),
                        pltpu.VMEM((bn, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="committee_uq",
    )(preds)
    stats = stats[:n]
    return (mean[:n], stats[:, _SSTD], stats[:, _CSTD],
            stats[:, _MASK] > 0.0, stats[:, _FINITE].astype(jnp.int32))
