"""Unified device-resident acquisition engine — ONE UQ path from the
exchange hot loop to the Manager's oracle re-prioritization.

The paper's promise is a modular controller where uncertainty estimation,
selection, and oracle re-prioritization are user-swappable without giving up
parallel throughput.  This module is that contract:

  * ``UQResult``  — everything the controller ever needs from a committee
    evaluation: mean, scalar (max-over-components) std, mean-over-components
    std, and the final selection mask.  Nothing larger ever crosses to host.
  * ``UQEngine``  — the one interface: ``score(inputs) -> UQResult``.
  * Backends     — ``FusedEngine`` (vmapped committee forward fused with the
    ``committee_uq`` kernel, impl='pallas'|'pallas_interpret'|'xla', one
    device dispatch per exchange iteration, shape-bucketed jit cache) and
    ``LegacyEngine`` (per-member ``UserModel.predict`` for arbitrary user
    kernels, float64 host statistics — the paper's original structure).
  * Rules        — composable selection logic (``ThresholdRule``,
    ``TopFractionRule``, ``DiversityRule``) written in jnp.  The fused
    backend traces them INSIDE its compiled dispatch, so custom selection
    runs device-side and never forfeits fusion; the legacy backend executes
    the very same functions eagerly on host statistics, so both backends
    select identically by construction.  Rules may be STATEFUL
    (``stateful = True`` + ``init_state`` / ``apply_stateful``): their
    small carried state is threaded through the compiled dispatch and
    stays device-resident across rounds — ``core/budget.py`` builds the
    cross-round oracle-rate controller (``BudgetRule``) and the rolling
    re-weighting rule (``RollingReweightRule``) on this protocol.
  * ``make_engine`` — config-driven factory (``PALRunConfig.uq_impl`` /
    ``uq_block_n`` / ``uq_bucket``, plus the ``oracle_budget`` /
    ``budget_horizon`` / ``reweight_*`` budget knobs): the runtime never
    hand-threads engines.

The pre-engine escape hatches (``prediction_check=`` host callables,
manual ``fused_engine=`` threading, ``predict_stacked`` host round trips)
are gone: every scenario — examples, benchmarks, the Manager's
``dynamic_oracle_list`` — consumes ``UQResult`` from the same hot path.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import re
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

log = logging.getLogger(__name__)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.committee import (
    committee_size, make_committee_apply, member, shape_bucket, stack_members,
    update,
)
from repro.core.monitor import Monitor


# ---------------------------------------------------------------------------
# Results and statistics
# ---------------------------------------------------------------------------

# Scoring-stream tags: every ``UQEngine.score`` round is attributed to the
# traffic stream that produced it — the exchange hot loop or the serving
# path.  The tag enters the fused dispatch as a TRACED scalar (part of
# ``UQStats``), so stream-aware rules (``core/budget.BudgetRule`` with a
# distinct ``target_serve``) meter both streams through ONE compiled
# program per shape bucket instead of doubling the trace cache.
STREAM_EXCHANGE = 0
STREAM_SERVE = 1


@dataclasses.dataclass
class UQResult:
    """Host-side outcome of one committee scoring round (all (n,)-shaped or
    (n, d)-shaped numpy arrays, n = true number of inputs scored).

    ``scalar_std``    max over output components of the ddof=1 committee std
                      — the quantity the paper's ``prediction_check``
                      thresholds.
    ``component_std`` mean over output components of the same std — the
                      ranking score of ``adjust_input_for_oracle``
                      (``dynamic_oracle_list``), emitted in the same Welford
                      pass so the Manager never recomputes statistics from a
                      ``(K, n, d)`` host tensor.
    ``mask``          final selection decision after the rule pipeline.
    ``finite_members`` per-row count of committee members whose outputs
                      were finite (int32).  Members with any non-finite
                      component are quarantined out of the statistics
                      inside the same fused pass (degraded-K mean/std),
                      so ``finite_members < K`` is the degradation signal
                      for monitoring/serving health.  None on paths that
                      predate quarantine (direct constructors).
    """

    mean: np.ndarray            # (n, d)
    scalar_std: np.ndarray      # (n,)
    component_std: np.ndarray   # (n,)
    mask: np.ndarray            # (n,) bool
    finite_members: Optional[np.ndarray] = None   # (n,) int32


@dataclasses.dataclass
class FusedStepOut:
    """Host-side outcome of one ``FusedEngine.score_after`` round — the
    fused walker-advance + scoring dispatch used by the exploration fleet
    (``exploration/fleet.py``).

    Unlike ``UQResult``, the per-row statistics stay DEVICE-resident
    (``mask``/``scalar_std``/... are jax arrays over the padded bucket):
    the exchange loop never needs them on host, and transferring them for
    N walkers every iteration would reintroduce exactly the per-row host
    traffic the fleet exists to remove.  The only host fields are
    ``n_selected`` (one int32 scalar) and ``selected`` — the selected
    rows, packed to the front of the bucket on device and sliced, so
    unselected walkers cost zero host bytes.
    """

    n_selected: int             # rows selected this round (host int)
    selected: np.ndarray        # (n_selected, d) host — the oracle candidates
    mask: Any                   # (nb,) bool, device
    mean: Any                   # (nb, d), device
    scalar_std: Any             # (nb,), device
    component_std: Any          # (nb,), device
    finite_members: Any         # (nb,) int32, device


@dataclasses.dataclass
class UQStats:
    """Per-round statistics handed to selection rules.

    Inside the fused dispatch every field is a traced jnp array over the
    PADDED bucket; on the legacy path they are host numpy arrays over the
    true n.  ``valid`` masks real rows (padding rows are never selectable);
    ``n_valid`` is the true input count (traced scalar on device, so
    fraction-of-n rules never force a retrace when n varies in a bucket).
    """

    x: Any                      # (nb, in_dim) the stacked proposal batch
    mean: Any                   # (nb, d)
    scalar_std: Any             # (nb,)
    component_std: Any          # (nb,)
    valid: Any                  # (nb,) bool
    n_valid: Any                # scalar int
    stream: Any = STREAM_EXCHANGE  # scalar int: STREAM_EXCHANGE | STREAM_SERVE
    finite_members: Any = None  # (nb,) int32 finite-member count (quarantine)


# ---------------------------------------------------------------------------
# Selection rules — jnp-traceable, so one definition serves both backends
# ---------------------------------------------------------------------------


class SelectionRule:
    """Composable selection logic: ``apply(stats, mask) -> mask``.

    Rules are folded in order over the incoming mask (initially every valid
    row).  Implementations must be pure jnp so the fused backend can trace
    them into its single compiled dispatch; the same code runs eagerly on
    host arrays for the legacy backend.  Set ``needs_inputs`` when the rule
    reads ``stats.x`` — the legacy backend only stacks the input batch
    (which the fused path gets for free) for rules that declare it.

    STATEFUL rules (``stateful = True``) carry a small jax-pytree state
    across scoring rounds — the cross-round budget controller and the
    rolling re-weighting rule in ``core/budget.py``.  They implement
    ``init_state()`` and ``apply_stateful(stats, mask, state) ->
    (stats, mask, new_state)`` instead of ``apply``; returning ``stats``
    lets a rule transform the statistics downstream rules consume (e.g.
    re-weighted scores) without touching the raw ``UQResult`` the engine
    reports.  On the fused backend the state is an input/output of the
    compiled dispatch and stays device-resident between rounds; the engine
    snapshots it to host only for checkpoints (``UQEngine.state_dict``).
    """

    needs_inputs: bool = False
    stateful: bool = False

    def apply(self, stats: UQStats, mask: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def init_state(self) -> Any:
        """Initial carried state (stateful rules only): a jax pytree of
        small arrays/scalars."""
        raise NotImplementedError

    def apply_stateful(self, stats: UQStats, mask: jnp.ndarray,
                       state: Any) -> Tuple[UQStats, jnp.ndarray, Any]:
        """Stateful fold step: ``(stats, mask, state) -> (stats', mask',
        state')`` in pure jnp (traced into the fused dispatch)."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class ThresholdRule(SelectionRule):
    """The paper's central check: select where scalar_std > threshold.

    Compares in the statistics' native dtype — float32 on the fused device
    path, float64 on the legacy host path (the seed ``prediction_check``
    semantics; forcing a jnp cast here would silently downgrade the legacy
    backend's near-threshold decisions to fp32)."""

    threshold: float

    def apply(self, stats: UQStats, mask):
        return mask & (stats.scalar_std > self.threshold)


@dataclasses.dataclass(frozen=True)
class TopFractionRule(SelectionRule):
    """Keep exactly the top ``round(fraction * n_valid)`` most-uncertain
    candidates (by scalar_std) among those still masked — the device-side
    equivalent of ``selection.top_fraction``.  Caps oracle traffic at a
    fixed fraction of the generator pool regardless of how noisy the
    committee currently is.  Rank-based, so exact ties (e.g. duplicate
    proposals from patience-restarted generators) never push the selection
    over the cap; tied ranks break toward the lower index.
    """

    fraction: float

    def apply(self, stats: UQStats, mask):
        # k must equal the host's int(round(n * fraction)) EXACTLY — fp32
        # arithmetic on the device cannot reproduce float64 rounding for
        # arbitrary (n, fraction) (e.g. 45*0.7: fp32 lands on 31.5 -> 32,
        # float64 on 31.499999999999996 -> 31).  fraction is static and
        # n_valid is bounded by the (static) bucket size, so the exact k
        # for every possible n is precomputed host-side at trace time and
        # the traced n_valid just indexes the table.
        n = int(mask.shape[0])
        k_table = jnp.asarray(
            [int(round(m * self.fraction)) for m in range(n + 1)],
            jnp.int32)
        k = k_table[jnp.clip(stats.n_valid, 0, n)]
        score = jnp.where(mask, stats.scalar_std, -jnp.inf)
        order = jnp.argsort(-score)            # stable: ties by lower index
        rank = jnp.zeros(n, jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
        return mask & (rank < k)


@dataclasses.dataclass(frozen=True)
class DiversityRule(SelectionRule):
    """Greedy de-duplication in input space (paper §3.1: avoid redundant
    oracle calculations): visit masked candidates in descending-uncertainty
    order and keep one only if no already-kept candidate lies closer than
    ``min_dist`` — ``selection.diversity_filter`` compiled into the
    dispatch (the O(n^2) distance matrix lives on device; n is the bucket).
    """

    min_dist: float
    needs_inputs = True

    def apply(self, stats: UQStats, mask):
        x = jnp.asarray(stats.x, jnp.float32)
        mask = jnp.asarray(mask)
        n = x.shape[0]
        md2 = jnp.float32(self.min_dist) ** 2
        order = jnp.argsort(
            jnp.where(mask, -jnp.asarray(stats.scalar_std), jnp.inf))

        # distances per candidate row inside the loop, via direct
        # differences — NOT the Gram identity (||a||^2+||b||^2-2ab cancels
        # catastrophically in fp32 for large-norm inputs; the host
        # diversity_filter needs a float64 boundary recompute for exactly
        # this reason) and NOT a precomputed (n, n, in_dim) difference
        # tensor (the Manager scores whole oracle buffers through the same
        # engine, where that intermediate would be GBs); O(n*d) memory,
        # same O(n^2*d) work.
        def body(t, kept):
            i = order[t]
            di = jnp.sum((x - x[i]) ** 2, axis=-1)
            ok = mask[i] & ~jnp.any(kept & (di < md2))
            return kept.at[i].set(ok)

        return jax.lax.fori_loop(0, n, body, jnp.zeros(n, bool))


def default_rules(threshold: float) -> Tuple[SelectionRule, ...]:
    return (ThresholdRule(threshold),)


# ---------------------------------------------------------------------------
# Engine protocol
# ---------------------------------------------------------------------------


class UQEngine:
    """One interface for committee scoring.  ``score`` is the ONLY call the
    controller makes on the hot path; ``refresh_from`` pulls fresh weights
    from a WeightStore (no-op for backends whose members refresh
    themselves); ``uses_models`` tells the PredictionPool whether the
    per-member ``UserModel`` instances are part of this engine's path.

    ``rule_state`` carries the state of stateful rules (``BudgetRule``,
    ``RollingReweightRule``) across rounds — one pytree per stateful rule,
    in pipeline order.  ``score(..., advance=False)`` evaluates the
    pipeline against the current state WITHOUT advancing it: the Manager's
    ``dynamic_oracle_list`` re-scoring and read-only serving traffic use
    this so they never consume exchange-round budget.  ``state_dict`` /
    ``load_state_dict`` snapshot the carried state to host numpy for
    ``PAL.checkpoint`` and restore it on resume."""

    uses_models: bool = False
    rule_state: Tuple[Any, ...] = ()

    def score(self, list_data: Sequence[np.ndarray], *,
              advance: bool = True,
              stream: int = STREAM_EXCHANGE) -> UQResult:
        raise NotImplementedError

    def refresh_from(self, store) -> int:
        return 0

    def _init_rule_state(self):
        """Shared stateful-rule plumbing: one state pytree per stateful
        rule (pipeline order) plus the lock that makes an ADVANCING
        round's read-state -> score -> store-state cycle atomic."""
        self.rule_state = tuple(r.init_state() for r in self.rules
                                if r.stateful)
        self._state_lock = threading.Lock()

    def _state_guard(self, advance: bool):
        """Lock held by advancing scorers (exchange loop, serving with
        advance=True): without it, concurrent rounds would both update
        from the same base state and the second store would silently drop
        the first round's controller/re-weighting update.  advance=False
        scorers (Manager re-scoring) stay lock-free — they only snapshot
        the state tuple."""
        if advance and self.rule_state:
            return self._state_lock
        return contextlib.nullcontext()

    def state_dict(self) -> Tuple[Any, ...]:
        """Host-numpy snapshot of the carried cross-round rule state."""
        return jax.tree.map(np.asarray, tuple(self.rule_state))

    def load_state_dict(self, state: Sequence[Any]):
        """Restore a ``state_dict`` snapshot — if it structurally matches
        the CURRENT rule pipeline.  A snapshot taken under a different
        budget/re-weighting configuration (different rule count, state
        keys, or array shapes) is skipped with a warning and the freshly
        initialized state is kept: the controller re-converges instead of
        crashing at trace time inside the fused dispatch."""
        restored = jax.tree.map(jnp.asarray, tuple(state))
        cur_leaves, cur_def = jax.tree.flatten(tuple(self.rule_state))
        new_leaves, new_def = jax.tree.flatten(restored)
        if cur_def != new_def or any(
                np.shape(a) != np.shape(b)
                for a, b in zip(cur_leaves, new_leaves)):
            log.warning(
                "engine rule-state snapshot does not match the current "
                "rule pipeline (%s vs %s) — skipping restore, carried "
                "acquisition state re-converges from scratch",
                new_def, cur_def)
            return
        self.rule_state = restored


class FusedEngine(UQEngine):
    """Single-dispatch committee inference + UQ + device-side selection.

    One exchange iteration is ONE compiled device program: the vmapped
    committee forward, the ``ops.committee_uq`` statistics (streaming
    Welford over the K axis: mean / max-component std / mean-component std /
    threshold mask), and the rule pipeline all trace into the same jit.
    Only ``(mean, scalar_std, component_std, mask)`` cross back to host —
    the ``(K, n, d)`` prediction tensor never leaves the device, regardless
    of which rules are installed.

    Varying generator counts are padded to power-of-two shape buckets so a
    run with fluctuating ``n_gen`` compiles at most once per bucket
    (``trace_counts`` records tracings per bucket; tests assert <= 1); the
    true count enters the program as a traced scalar, so fraction-of-n rules
    don't retrace either.  The padded input batch is donated to the compiled
    program where the backend supports aliasing.

    ``apply_fn(params, x)`` must map a single member's params over a batch
    ``x: (n, in_dim) -> (n, out_dim)``.

    MESH-PARALLEL PATH (``mesh=``): the same single compiled dispatch, laid
    out over a device mesh.  The stacked committee parameters are placed
    over the mesh via the ``COMMITTEE`` logical-axis rules
    (``sharding/rules.py``: ``COMMITTEE -> ('model',)``, with the standard
    divisibility fallback — a K=4 committee on a 16-way model axis simply
    replicates), the padded request batch is sharded over the ``data`` axis
    (``BATCH`` rules), and the compiled program is constructed with
    ``jax.jit``'s ``in_shardings``/``out_shardings`` so the vmapped
    forward, the Welford UQ kernel, and the rule pipeline stay inside ONE
    dispatch — XLA inserts the collectives.  Carried rule state and the
    ``n_valid``/``stream`` scalars are replicated.  On the degenerate
    ``launch.mesh.make_host_mesh()`` (1x1) every sharding resolves to the
    single device and the program is the SAME computation as the
    unsharded path — bit-identical results (tested).
    """

    def __init__(self, apply_fn: Callable, cparams: Any, threshold: float,
                 *, rules: Optional[Sequence[SelectionRule]] = None,
                 impl: str = "xla", min_bucket: int = 8,
                 donate: bool = True, block_n: int = 128,
                 mesh=None, sharding_rules=None,
                 monitor: Optional[Monitor] = None):
        from repro.kernels import ops as _ops

        self._ops = _ops
        self.apply = make_committee_apply(apply_fn)
        self.mesh = mesh
        self._mesh_rules = None
        self._x_shardings: Dict[int, Any] = {}
        if mesh is not None:
            from repro.sharding.rules import MeshRules, warn_fallbacks

            self._mesh_rules = MeshRules(mesh, sharding_rules)
            cparams = jax.device_put(
                cparams, self._cparams_shardings(cparams))
            # surface divisibility fallbacks (e.g. K=3 on an 8-way model
            # axis degrading to replicated) once, with the chosen layout
            self._fallback_mark = warn_fallbacks(
                self._mesh_rules, "FusedEngine")
        self.cparams = cparams
        self.threshold = float(threshold)
        self.rules = tuple(rules) if rules is not None \
            else default_rules(threshold)
        # carried state of stateful rules (budget controller, rolling
        # re-weighting), device-resident between rounds — an input/output
        # of the compiled dispatch, never a host round trip
        self._init_rule_state()
        self.rule_state = self._place_replicated(self.rule_state)
        self.impl = impl
        self.min_bucket = min_bucket
        self.donate = donate
        self.block_n = block_n
        self.version = -1                      # last WeightStore version seen
        self._cache: Dict[int, Callable] = {}
        self.trace_counts: Dict[int, int] = {}
        # score_after (fused step+score, exploration fleet) keeps its OWN
        # jit cache and trace counter: its programs are keyed by (caller
        # key, bucket) and must not perturb the plain score() cache whose
        # per-bucket trace counts tests assert exactly
        self._step_cache: Dict[Tuple[str, int], Callable] = {}
        self.step_trace_counts: Dict[Tuple[str, int], int] = {}
        self._step_warmed: set = set()
        # the Exchange and Manager threads score through the SAME engine:
        # the compile cache and traffic counters need a lock or two threads
        # hitting a fresh bucket would both trace it (duplicate multi-second
        # XLA compiles, trace_counts == 2) and lose counter increments
        self._compile_lock = threading.Lock()
        self._counter_lock = threading.Lock()
        self._warmed: set = set()
        # host<->device traffic accounting (benchmarks/committee_uq.py)
        self.bytes_to_device = 0
        self.bytes_to_host = 0
        # weight-refresh accounting (benchmarks/committee_train.py): the
        # WeightStore path round-trips packed 1-D arrays through host
        # memory; the device path (refresh_from_device) must stay at 0
        self.refresh_host_bytes = 0
        self.device_refreshes = 0
        # quarantine observability (PAL.report): finite-member count of the
        # most recent round's worst row, and how many rounds saw any member
        # quarantined at all
        self.last_finite_min: Optional[int] = None
        self.quarantine_rounds = 0
        # host spans of score_after (engine.dispatch / wait /
        # fetch_selected): the runtime passes its own Monitor
        self.monitor = monitor if monitor is not None else Monitor()

    @property
    def size(self) -> int:
        return committee_size(self.cparams)

    # ------------------------------------------------------------ sharding
    def _cparams_shardings(self, cparams):
        """NamedShardings laying the stacked committee over the mesh: the
        leading K axis follows the COMMITTEE logical-axis rules, every
        other dimension is replicated (per-member params are small; it is
        the K-way ensemble that scales out)."""
        from repro.sharding.rules import committee_shardings

        return committee_shardings(self._mesh_rules, cparams)

    def _batch_sharding(self, nb: int):
        """Request-batch sharding for one shape bucket: rows over the BATCH
        rules' mesh axes (divisibility fallback applies — an 8-row bucket
        on a 16-way data axis replicates), features replicated.  The spec
        depends only on the bucket size: the feature dim's logical axis is
        None (never mapped), so its concrete size is irrelevant — cached
        per nb alongside the jit cache."""
        from repro.configs import base as axes

        sh = self._x_shardings.get(nb)
        if sh is None:
            sh = self._mesh_rules.sharding(
                (axes.BATCH, None), (nb, 1), name="uq_batch")
            self._x_shardings[nb] = sh
        return sh

    def _place_replicated(self, tree):
        """Explicitly replicate a pytree over the mesh (no-op unsharded).

        Rule state and other small carried pytrees are created on the
        default device; at >= 2 devices, mixing a single-device-committed
        leaf into a mesh-sharded dispatch either fails to place or pays a
        reshard in the program prologue every round — placing once at
        init/restore keeps the hot loop transfer-free."""
        if self._mesh_rules is None:
            return tree
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        rep = NamedSharding(self._mesh_rules.mesh, P())
        return jax.tree.map(lambda a: jax.device_put(jnp.asarray(a), rep),
                            tree)

    def place_carry(self, carry, nb: int):
        """Lay a ``score_after`` carry out over the mesh: leaves whose
        leading dimension equals the padded bucket ``nb`` (per-walker
        state — positions, velocities, RNG keys, patience counters) shard
        rows over the BATCH mesh axes alongside the proposal batch;
        everything else replicates.  The exploration fleet calls this at
        construction and checkpoint restore so the fused step+score
        dispatch never resharding-copies the fleet each iteration.
        No-op without a mesh."""
        if self._mesh_rules is None:
            return carry
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh_rules.mesh
        rep = NamedSharding(mesh, P())
        row_axes = self._batch_sharding(nb).spec[0] \
            if len(self._batch_sharding(nb).spec) else None

        def leaf(a):
            a = jnp.asarray(a)
            if a.ndim and int(a.shape[0]) == nb:
                spec = P(row_axes, *([None] * (a.ndim - 1)))
                return jax.device_put(a, NamedSharding(mesh, spec))
            return jax.device_put(a, rep)

        return jax.tree.map(leaf, carry)

    def _committee_uq(self, preds, nb: int):
        """The ``committee_uq`` statistics of the (K, nb, d) prediction
        tensor, with K gathered (unsharded) and rows kept on the mesh.

        The Welford committee-UQ reduction runs over K; leaving K sharded
        over 'model' makes XLA reduce local partials then all-reduce,
        changing the fp32 summation ORDER and costing 1-2 ULP vs the
        unsharded program.  Gathering K before the reduction restores the
        sequential order bit-for-bit.  Row reductions downstream (rule
        sums/maxes over selected rows) are integer/max arithmetic — exact
        under any row partitioning — so rows spread over EVERY free mesh
        axis ('data' AND 'model', greedy divisibility like rules.pspec):
        on a committee-axis mesh the gathered tensor's UQ work is then
        row-split across the devices instead of redundantly replicated.

        On a mesh the statistics run per row shard inside a
        ``shard_map``: rows are independent, so this is the same
        arithmetic as one unsharded call, and the compiler cannot
        partition a Pallas (Mosaic) kernel by itself."""
        def uq(p):
            return self._ops.committee_uq(p, self.threshold, impl=self.impl,
                                          block_n=self.block_n)

        if self._mesh_rules is None:
            return uq(preds)
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh_rules.mesh
        chosen, prod = [], 1
        for a in ("data", "model"):
            sz = mesh.shape.get(a, 1)
            if a in mesh.shape and nb % (prod * sz) == 0:
                chosen.append(a)
                prod *= sz
        rows = tuple(chosen) if chosen else None
        return jax.shard_map(
            uq, mesh=mesh, in_specs=P(None, rows, None),
            out_specs=(P(rows, None),) + (P(rows),) * 4,
            check_vma=False)(preds)

    def _jit_shardings(self, nb: int):
        """(in_shardings, out_shardings) for one bucket's compiled dispatch.
        Row-wise outputs inherit the batch's row partitioning; scalars and
        carried rule state are replicated."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh_rules.mesh
        rep = NamedSharding(mesh, P())
        x_sh = self._batch_sharding(nb)
        row_axes = x_sh.spec[0] if len(x_sh.spec) else None
        vec_sh = NamedSharding(mesh, P(row_axes))
        mat_sh = NamedSharding(mesh, P(row_axes, None))
        state_sh = jax.tree.map(lambda _: rep, tuple(self.rule_state))
        cp_sh = self._cparams_shardings(self.cparams)
        in_sh = (cp_sh, x_sh, rep, rep, state_sh)
        out_sh = (mat_sh, vec_sh, vec_sh, vec_sh, vec_sh, state_sh)
        return in_sh, out_sh

    # ------------------------------------------------------------- compile
    def _score_rows(self, cparams, x, nb: int, n_valid, stream, rstate):
        """The traced body both programs share: committee forward, the
        ``committee_uq`` statistics and the rule pipeline over one padded
        (nb, in_dim) batch.  Returns the raw statistics ``(mean,
        scalar_std, component_std, finite_members)``, the ``UQStats`` as
        the rules left them (a re-weighting rule adjusts them for the
        rules after it), the selection mask and the rules' new state."""
        with jax.named_scope("committee_forward"):
            preds = self.apply(cparams, x)
        with jax.named_scope("committee_uq"):
            mean, sstd, cstd, _, finite = self._committee_uq(preds, nb)
        with jax.named_scope("selection"):
            valid = jnp.arange(nb) < n_valid
            stats = UQStats(x=x, mean=mean, scalar_std=sstd,
                            component_std=cstd, valid=valid,
                            n_valid=n_valid, stream=stream,
                            finite_members=finite)
            mask = valid
            new_state, si = [], 0
            for rule in self.rules:
                if rule.stateful:
                    stats, mask, ns = rule.apply_stateful(
                        stats, mask, rstate[si])
                    mask = jnp.asarray(mask) & valid
                    new_state.append(ns)
                    si += 1
                else:
                    mask = jnp.asarray(rule.apply(stats, mask)) & valid
            # quarantine floor: a row no finite member scored carries no
            # information — never selectable, whatever the rules say
            mask = mask & (finite > 0)
        return (mean, sstd, cstd, finite), stats, mask, tuple(new_state)

    def _compiled_locked(self, nb: int) -> Callable:
        # caller holds self._compile_lock
        fn = self._cache.get(nb)
        if fn is None:
            # the function's name names the program in a device trace:
            # jit_engine_score
            def engine_score(cparams, x, n_valid, stream, rstate):
                # trace-time counter: fires once per (bucket) compilation
                self.trace_counts[nb] = self.trace_counts.get(nb, 0) + 1
                (mean, sstd, cstd, finite), _, mask, new_state = \
                    self._score_rows(cparams, x, nb, n_valid, stream, rstate)
                return mean, sstd, cstd, mask, finite, new_state
            # donation is a no-op (plus a warning) on CPU — only request it
            # where XLA can actually alias the buffer
            donate = self.donate and jax.default_backend() != "cpu"
            kw: Dict[str, Any] = {"donate_argnums": (1,)} if donate else {}
            if self._mesh_rules is not None:
                kw["in_shardings"], kw["out_shardings"] = \
                    self._jit_shardings(nb)
            fn = jax.jit(engine_score, **kw)
            self._cache[nb] = fn
        return fn

    def _pad_batch(self, list_data: Sequence[np.ndarray]):
        """Stack generator proposals into one padded (bucket, in_dim) batch.

        Pre-stacked 2-D input (serving microbatches, benchmark drivers)
        takes a vectorized path — one ``np.asarray`` + block copy instead
        of a per-row Python loop, which at mesh scale-out batch sizes
        (hundreds of rows per dispatch) otherwise dominates the host-side
        cost of ``score``."""
        if isinstance(list_data, np.ndarray):
            arr = list_data.astype(np.float32, copy=False)
        else:
            try:
                arr = np.asarray(list_data, dtype=np.float32)
            except ValueError:          # ragged rows: slow path below
                arr = np.empty(0, np.float32)
        if arr.ndim == 2:
            n = arr.shape[0]
            nb = shape_bucket(n, self.min_bucket)
            if nb == n:
                return np.ascontiguousarray(arr), n, nb
            x = np.zeros((nb, arr.shape[1]), np.float32)
            x[:n] = arr
            return x, n, nb
        # ragged / object input: normalize row by row
        rows = [np.asarray(x, dtype=np.float32).reshape(-1)
                for x in list_data]
        n = len(rows)
        nb = shape_bucket(n, self.min_bucket)
        x = np.zeros((nb, rows[0].size), np.float32)
        for i, r in enumerate(rows):
            x[i] = r
        return x, n, nb

    # -------------------------------------------------------------- score
    def _dispatch(self, nb: int, args):
        if nb in self._warmed:                 # steady state: lock-free call
            return self._cache[nb](*args)
        # first call per bucket traces lazily inside jit — hold the
        # lock across it so concurrent Exchange/Manager scoring can't
        # double-trace the same bucket
        with self._compile_lock:
            out = self._compiled_locked(nb)(*args)
            self._warmed.add(nb)
            return out

    def score(self, list_data: Sequence[np.ndarray], *,
              advance: bool = True,
              stream: int = STREAM_EXCHANGE) -> UQResult:
        x, n, nb = self._pad_batch(list_data)
        if self._mesh_rules is not None:
            xd = jax.device_put(x, self._batch_sharding(nb))
        else:
            xd = jnp.asarray(x)
        head = (self.cparams, xd, np.int32(n), np.int32(stream))
        # advancing rounds are semantically sequential (_state_guard); the
        # state itself advances on device — only the compiled program's
        # output handle moves, no host transfer
        with self._state_guard(advance):
            out = self._dispatch(nb, head + (self.rule_state,))
            if advance:
                self.rule_state = out[5]
        mean, sstd, cstd, mask, finite = (np.asarray(o) for o in out[:5])
        finite_n = finite[:n]
        with self._counter_lock:
            self.bytes_to_device += x.nbytes
            self.bytes_to_host += (mean.nbytes + sstd.nbytes + cstd.nbytes
                                   + mask.nbytes + finite.nbytes)
            if finite_n.size:
                self.last_finite_min = int(finite_n.min())
                if self.last_finite_min < self.size:
                    self.quarantine_rounds += 1
        return UQResult(mean[:n], sstd[:n], cstd[:n], mask[:n], finite_n)

    # ------------------------------------------------- fused step + score
    def _step_compiled_locked(self, ckey: str, nb: int, step_fn: Callable,
                              react_fn: Optional[Callable]) -> Callable:
        # caller holds self._compile_lock
        key = (ckey, nb)
        fn = self._step_cache.get(key)
        if fn is None:
            # traces as jit_engine_step_score
            def engine_step_score(cparams, carry, n_valid, stream, rstate):
                self.step_trace_counts[key] = \
                    self.step_trace_counts.get(key, 0) + 1
                with jax.named_scope("advance"):
                    x, mid = step_fn(carry)
                (mean, sstd, cstd, finite), stats, mask, new_state = \
                    self._score_rows(cparams, x, nb, n_valid, stream, rstate)
                with jax.named_scope("react"):
                    new_carry = react_fn(mid, stats, mask) \
                        if react_fn is not None else mid
                # pack selected rows to the front (stable order) so the
                # host can slice exactly n_selected rows off the device —
                # unselected walkers never cross the boundary
                with jax.named_scope("pack_selected"):
                    order = jnp.argsort(~mask)
                    sel_x = jnp.take(x, order, axis=0)
                    n_sel = jnp.sum(mask).astype(jnp.int32)
                return (new_carry, mean, sstd, cstd, mask, finite,
                        n_sel, sel_x, new_state)
            donate = self.donate and jax.default_backend() != "cpu"
            kw: Dict[str, Any] = {"donate_argnums": (1,)} if donate else {}
            fn = jax.jit(engine_step_score, **kw)
            self._step_cache[key] = fn
        return fn

    def score_after(self, step_fn: Callable, carry: Any, n: int, nb: int,
                    *, react_fn: Optional[Callable] = None,
                    cache_key: str = "step", advance: bool = True,
                    stream: int = STREAM_EXCHANGE
                    ) -> Tuple[Any, FusedStepOut]:
        """Fuse a caller-supplied advance step with committee scoring:
        ``step_fn(carry) -> (x, mid)`` produces the (nb, in_dim) proposal
        batch INSIDE the compiled dispatch, then the committee forward,
        the ``committee_uq`` Welford statistics, and the selection-rule
        pipeline run exactly as in :meth:`score`, and finally
        ``react_fn(mid, stats, mask) -> new_carry`` (e.g. the fleet's
        patience/restart update) folds the round's outcome back into the
        carried state — one device program per (cache_key, bucket).

        ``carry`` is a device-resident pytree the caller owns (the fleet's
        stacked walker state); it never crosses to host.  ``n`` is the
        true row count, ``nb`` the padded bucket (the caller pads once at
        construction, so the hot loop has zero uploads).  Host traffic per
        call is the int32 selected count plus the selected rows only.

        Stateful-rule state is shared with :meth:`score` — both entry
        points thread ``self.rule_state`` under the same ``_state_guard``,
        so a budget controller meters fleet and host traffic jointly.
        """
        key = (cache_key, nb)
        mon = self.monitor
        with mon.span("engine.dispatch"), self._state_guard(advance):
            args = (self.cparams, carry, np.int32(n), np.int32(stream),
                    self.rule_state)
            if key in self._step_warmed:
                out = self._step_cache[key](*args)
            else:
                with self._compile_lock:
                    out = self._step_compiled_locked(
                        cache_key, nb, step_fn, react_fn)(*args)
                    self._step_warmed.add(key)
            if advance:
                self.rule_state = out[8]
        new_carry, mean, sstd, cstd, mask, finite, n_sel_d, sel_x = out[:8]
        with mon.span("engine.wait"):
            n_sel = int(n_sel_d)                   # one int32 to host
        with mon.span("engine.fetch_selected"):
            if n_sel:                              # selected rows only
                selected = np.asarray(sel_x[:n_sel])
            else:
                selected = np.zeros((0,) + tuple(sel_x.shape[1:]),
                                    np.float32)
        with self._counter_lock:
            self.bytes_to_host += 4 + selected.nbytes
        return new_carry, FusedStepOut(
            n_selected=n_sel, selected=selected, mask=mask, mean=mean,
            scalar_std=sstd, component_std=cstd, finite_members=finite)

    # -------------------------------------------------------------- weights
    def refresh_from(self, store) -> int:
        """Refresh the stacked committee from a WeightStore if anything
        newer exists.  Prediction member i replicates training member
        ``i % store.n_members`` (paper: prediction models are replicas of
        training models), so the committee size K is preserved even when
        fewer trainers publish — shapes never change, so no retrace.
        Returns the number of refreshed committees (0 or 1)."""
        v = store.version()
        if v <= self.version:
            return 0
        K = self.size
        packs = [store.pull_packed(i % store.n_members) for i in range(K)]
        if any(p is None for p in packs):
            return 0              # not all trainers have published yet
        self.refresh_host_bytes += sum(p[0].nbytes for p in packs)
        members = [update(member(self.cparams, i), packs[i][0])
                   for i in range(K)]
        cparams = stack_members(members)
        if self._mesh_rules is not None:
            # fresh weights land replicated on the default device; put them
            # back on the committee layout so the next dispatch doesn't
            # reshard inside the compiled program's prologue every round
            cparams = jax.device_put(
                cparams, self._cparams_shardings(cparams))
        self.cparams = cparams
        self.version = v
        return 1

    def refresh_from_device(self, cparams) -> int:
        """Zero-copy weight handoff from the fused committee trainer: the
        refreshed STACKED pytree is re-placed on the committee layout
        directly (a device_put onto the mesh sharding when one is
        installed; a reference swap otherwise).  No packed 1-D host round
        trip — ``refresh_host_bytes`` stays untouched, which the
        benchmark/acceptance tests assert.  The caller must hand over a
        pytree it will not donate away (``CommitteeTrainer.
        snapshot_cparams``)."""
        k = committee_size(cparams)
        if k != self.size:
            raise ValueError(
                f"refresh_from_device: committee size changed ({k} vs "
                f"{self.size}) — shapes are baked into the jit cache")
        if self._mesh_rules is not None:
            cparams = jax.device_put(
                cparams, self._cparams_shardings(cparams))
        self.cparams = cparams
        self.device_refreshes += 1
        return 1

    # ------------------------------------------------------------ snapshot
    def load_state_dict(self, state: Sequence[Any]):
        """Restore carried rule state, then re-place it on the mesh: a
        checkpoint restores to host numpy -> default device, which at
        >= 2 devices would make every subsequent dispatch reshard the
        state in its prologue."""
        super().load_state_dict(state)
        self.rule_state = self._place_replicated(self.rule_state)


class LegacyEngine(UQEngine):
    """Per-member backend for arbitrary ``UserModel`` kernels (the paper's
    original per-process structure): K sequential ``model.predict`` calls
    (or a user ``predict_all_override``), float64 host statistics, then the
    SAME rule objects executed eagerly — so swapping a user model in never
    changes selection semantics, only throughput.

    Weight refresh stays with the PredictionPool (the models own their
    parameters), hence ``uses_models`` and a no-op ``refresh_from``.
    """

    uses_models = True

    def __init__(self, predict_all: Callable[[Sequence[np.ndarray]],
                                             np.ndarray],
                 threshold: float,
                 *, rules: Optional[Sequence[SelectionRule]] = None):
        self.predict_all = predict_all
        self.threshold = float(threshold)
        self.rules = tuple(rules) if rules is not None \
            else default_rules(threshold)
        self._init_rule_state()
        self.last_finite_min: Optional[int] = None
        self.quarantine_rounds = 0

    def score(self, list_data: Sequence[np.ndarray], *,
              advance: bool = True,
              stream: int = STREAM_EXCHANGE) -> UQResult:
        with self._state_guard(advance):
            return self._score(list_data, advance=advance, stream=stream)

    def _score(self, list_data: Sequence[np.ndarray], *,
               advance: bool, stream: int = STREAM_EXCHANGE) -> UQResult:
        preds = np.asarray(self.predict_all(list_data), dtype=np.float64)
        k = preds.shape[0]
        fin = np.isfinite(preds).all(axis=tuple(range(2, preds.ndim)))  # (K, n)
        cnt = fin.sum(axis=0).astype(np.int32)                          # (n,)
        if fin.all():
            # steady state: keep the exact historical float64 reductions
            mean = preds.mean(axis=0)
            std = preds.std(axis=0, ddof=1) if k > 1 \
                else np.zeros_like(preds[0])
        else:
            # degraded-K statistics over the finite members only — same
            # quarantine semantics as the fused kernels (ref.committee_uq_ref)
            w = fin.reshape(fin.shape + (1,) * (preds.ndim - 2))
            safe = np.maximum(cnt, 1).astype(np.float64)
            safe = safe.reshape((-1,) + (1,) * (preds.ndim - 2))
            mean = np.where(w, preds, 0.0).sum(axis=0) / safe
            dev = np.where(w, preds - mean, 0.0)
            var = (dev * dev).sum(axis=0) / np.maximum(
                cnt - 1, 1).reshape(safe.shape)
            var[cnt < 2] = 0.0
            std = np.sqrt(var)
        flat = std.reshape(std.shape[0], -1)
        sstd = flat.max(axis=-1)
        cstd = flat.mean(axis=-1)
        n = len(list_data)
        x = np.stack([np.asarray(r, np.float32).reshape(-1)
                      for r in list_data]) \
            if any(r.needs_inputs for r in self.rules) else None
        stats = UQStats(
            x=x, mean=mean, scalar_std=sstd, component_std=cstd,
            valid=np.ones(n, bool), n_valid=n, stream=stream,
            finite_members=cnt)
        mask = np.ones(n, bool)
        states, si = list(self.rule_state), 0
        for rule in self.rules:
            if rule.stateful:
                # the SAME jnp code the fused backend traces, run eagerly
                stats, mask, states[si] = rule.apply_stateful(
                    stats, mask, states[si])
                mask = np.asarray(mask, dtype=bool)
                si += 1
            else:
                mask = np.asarray(rule.apply(stats, mask), dtype=bool)
        mask = mask & (cnt > 0)
        if advance:
            self.rule_state = tuple(states)
        if cnt.size:
            self.last_finite_min = int(cnt.min())
            if self.last_finite_min < k:
                self.quarantine_rounds += 1
        return UQResult(mean, sstd, cstd, mask, cnt)


# ---------------------------------------------------------------------------
# Config-driven construction
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CommitteeSpec:
    """What the fused backends need from the user: a single-member batch
    apply ``apply_fn(params, x: (n, in_dim)) -> (n, out_dim)`` plus the
    stacked committee parameters (leading K axis, ``committee.stack_members``).
    """

    apply_fn: Callable
    cparams: Any


def wants_legacy(run_cfg, committee: Optional[CommitteeSpec],
                 force_legacy: bool = False) -> bool:
    """Whether ``make_engine`` will build the per-member legacy backend for
    this configuration — i.e. whether per-member prediction ``UserModel``
    instances are actually needed (the runtime skips constructing them
    otherwise)."""
    impl = getattr(run_cfg, "uq_impl", "auto")
    return force_legacy or impl == "legacy" or (impl == "auto"
                                                and committee is None)


def resolve_mesh(run_cfg):
    """``PALRunConfig.uq_mesh`` -> a concrete mesh (or None).

    ''  (default) — no mesh: single-device dispatch, today's path.
    'host'        — ``launch.mesh.make_host_mesh()``: the degenerate 1x1
                    ('data', 'model') mesh; same computation, sharded
                    construction exercised (CI parity).
    'scaleout'    — ``launch.mesh.make_scaleout_mesh()``: all visible
                    devices on the 'data' axis (committee replicated, rows
                    scale out) — the CI/emulated-device bring-up layout.
    'DxM'         — e.g. ``'4x2'``: an explicit ('data', 'model') grid
                    over the first D*M visible devices.
    'production'  — ``launch.mesh.make_production_mesh()``: the 16x16
                    ('data', 'model') pod mesh (committee over 'model',
                    request batch over 'data').

    Divisibility fallbacks (a committee/batch that does not divide the
    mapped axes) are NOT silent: ``FusedEngine``/``CommitteeTrainer`` log
    a WARNING with the chosen fallback layout at construction
    (``sharding.rules.warn_fallbacks``).
    """
    name = getattr(run_cfg, "uq_mesh", "") or ""
    if not name:
        return None
    from repro.launch import mesh as mesh_mod

    if name == "host":
        return mesh_mod.make_host_mesh()
    if name == "scaleout":
        return mesh_mod.make_scaleout_mesh()
    if name == "production":
        return mesh_mod.make_production_mesh()
    m = re.fullmatch(r"(\d+)x(\d+)", name)
    if m:
        return mesh_mod.make_scaleout_mesh(int(m.group(1)), int(m.group(2)))
    raise ValueError(f"uq_mesh={name!r}: expected '', 'host', 'scaleout', "
                     "'DxM' (e.g. '4x2') or 'production'")


def make_engine(
    run_cfg,
    *,
    committee: Optional[CommitteeSpec] = None,
    predict_all: Optional[Callable] = None,
    rules: Optional[Sequence[SelectionRule]] = None,
    force_legacy: bool = False,
    mesh=None,
    sharding_rules=None,
    monitor: Optional[Monitor] = None,
) -> UQEngine:
    """Build the acquisition engine from ``PALRunConfig`` knobs.

    ``uq_impl``:
      'auto'             — fused XLA backend when a ``CommitteeSpec`` is
                           given, per-member legacy otherwise
      'xla'              — fused single-dispatch, jnp reference statistics
      'pallas'           — fused single-dispatch, Pallas TPU kernel
      'pallas_interpret' — same kernel, interpret mode (CPU validation)
      'legacy'           — per-member ``UserModel.predict`` + host float64

    ``force_legacy`` overrides everything (used when a
    ``predict_all_override`` puts the user in control of raw predictions).

    ``monitor`` receives the fused engine's spans (the runtime's own).

    ``mesh`` / ``sharding_rules`` select the mesh-parallel fused dispatch
    (committee over the ``model`` axis, request batch over ``data``); when
    ``mesh`` is None it is resolved from ``run_cfg.uq_mesh``
    (:func:`resolve_mesh`).  Meshes are a fused-backend feature — the
    legacy per-member path ignores them.

    When no explicit ``rules=`` are given, the pipeline comes from the
    config's budget knobs (``core/budget.rules_from_config``):
    ``oracle_budget > 0`` installs the cross-round oracle-rate controller
    (``BudgetRule``) in place of the static threshold rule, and
    ``reweight_buckets > 0`` prepends the rolling re-weighting rule.
    """
    impl = getattr(run_cfg, "uq_impl", "auto")
    threshold = run_cfg.std_threshold
    if rules is None:
        from repro.core import budget as _budget

        rules = _budget.rules_from_config(run_cfg)
    if wants_legacy(run_cfg, committee, force_legacy):
        if predict_all is None:
            raise ValueError(
                "legacy UQ backend needs a predict_all callable "
                "(no committee spec was provided)")
        return LegacyEngine(predict_all, threshold, rules=rules)
    if committee is None:
        raise ValueError(
            f"uq_impl={impl!r} is a fused backend and needs a CommitteeSpec "
            "(apply_fn + stacked cparams); pass committee=... to PAL or use "
            "uq_impl='legacy'")
    if mesh is None:
        mesh = resolve_mesh(run_cfg)
    return FusedEngine(
        committee.apply_fn, committee.cparams, threshold,
        rules=rules,
        impl=("xla" if impl == "auto" else impl),
        block_n=getattr(run_cfg, "uq_block_n", 128),
        min_bucket=getattr(run_cfg, "uq_bucket", 8),
        mesh=mesh,
        sharding_rules=sharding_rules,
        monitor=monitor,
    )
