"""Fused committee training: ALL K members advance in ONE jitted step.

The paper's training kernel retrains every committee member in parallel
(one MPI rank per member) and ships weights to the prediction kernel as
packed 1-D arrays.  Here the whole committee is ONE SPMD program, mirroring
what PRs 1–4 did for scoring and serving:

  * per-member ``TrainState`` (params + AdamW moments + step) stacked on a
    leading committee axis — built once from the SAME stacked ``cparams``
    the acquisition engine scores, so training and prediction share layout;
  * ``training/train_step.make_train_step`` ``vmap``-ed over that axis:
    one compiled dispatch advances all K members, each on its OWN bootstrap
    minibatch (per-member fold of the step key keeps members decorrelated;
    ``bootstrap=False`` gives every member the identical minibatch — the
    legacy same-data-order semantics, used by the parity tests);
  * minibatches are gathered ON DEVICE from a
    ``data/replay.ReplayTrainingBuffer`` (fixed-capacity device ring,
    host blocks appended once) — a train step moves zero training bytes
    across the host boundary;
  * shardable over the ``model`` mesh axis by reusing
    ``sharding/rules.committee_shardings`` on the stacked TrainState, so a
    production mesh trains and scores the committee on the same layout
    (the degenerate 1x1 host mesh is bit-identical to unsharded — tested);
  * refreshed weights hand off DEVICE-TO-DEVICE:
    ``FusedEngine.refresh_from_device(trainer.snapshot_cparams())``
    re-places the stacked pytree on the committee layout directly.
    ``WeightStore``'s packed 1-D round trip remains only for the
    legacy per-member backend and checkpoint wire format.

Per-member storage is a POLICY, not hard-coded fp32: ``memory_policy``
(``optim/memory_policy.MemoryPolicy`` or a preset name) picks the AdamW
moment format (fp32 | bf16 | int8 ``QTensor``), the stacked-param storage
dtype, and the replay-ring row dtype.  Quantize/dequantize lives INSIDE
the one fused dispatch (``optim/adamw.py``), so K=64 with int8 moments
trains through the same single jitted vmapped step as K=8 fp32.  Update
math is fp32 under every policy.

``state_dict``/``load_state_dict`` snapshot the FULL TrainState (params,
Adam moments, per-member step) plus the RNG cursor and the replay ring, so
a restored run continues mid-schedule instead of resetting its optimizer.
Quantized moments checkpoint NATIVELY (int8 ``q`` + fp32 ``scale``, never
dequantized on save); restoring a snapshot whose storage format mismatches
the configured policy raises instead of silently re-formatting.
"""
from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

log = logging.getLogger(__name__)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig
from repro.core.committee import committee_size, member
from repro.core.monitor import Monitor
from repro.data.replay import ReplayTrainingBuffer
from repro.optim.adamw import QTensor, resolve_moments
from repro.optim.memory_policy import MemoryPolicy, resolve_policy
from repro.training.train_step import make_train_state, make_train_step


def default_train_config(lr: float) -> TrainConfig:
    """The committee-retrain optimizer defaults: constant-LR AdamW without
    warmup (retraining resumes continuously; a re-warmup every round would
    stall the member right when fresh labels arrive)."""
    return TrainConfig(learning_rate=lr, schedule="constant",
                       warmup_steps=0, weight_decay=0.0)


class CommitteeTrainer:
    """One-dispatch K-member retraining on a device-resident replay ring.

    ``loss_fn(params, batch) -> (loss, aux_dict)`` is a SINGLE member's
    loss over a minibatch ``{"x": (B, dx), "y": (B, dy)}`` — the same
    signature ``make_train_step`` consumes; the trainer vmaps it over the
    committee axis.  ``cparams`` is the stacked committee
    (``committee.stack_members``), typically the very pytree handed to the
    acquisition engine via ``CommitteeSpec``.
    """

    def __init__(
        self,
        loss_fn: Callable[[Any, Dict[str, jnp.ndarray]],
                          Tuple[jnp.ndarray, Dict]],
        cparams: Any,
        *,
        steps: int = 200,
        batch: int = 32,
        lr: float = 1e-3,
        bootstrap: bool = True,
        replay_capacity: int = 2048,
        train_cfg: Optional[TrainConfig] = None,
        mesh=None,
        sharding_rules=None,
        seed: int = 0,
        monitor=None,
        memory_policy: Union[str, MemoryPolicy, None] = None,
    ):
        self.size = committee_size(cparams)
        self.steps = int(steps)
        self.batch = int(batch)
        self.bootstrap = bool(bootstrap)
        self.monitor = monitor if monitor is not None else Monitor()
        tcfg = train_cfg if train_cfg is not None else default_train_config(lr)
        policy = resolve_policy(memory_policy)
        if policy is None:
            # legacy path: derive the effective policy from TrainConfig so
            # snapshots always carry storage metadata, but leave tcfg alone
            fmt = resolve_moments(getattr(tcfg, "opt_moments", ""),
                                  tcfg.quantized_opt_state)
            policy = MemoryPolicy(name=fmt, moments=fmt)
        else:
            tcfg = dataclasses.replace(
                tcfg, opt_moments=policy.moments,
                quantized_opt_state=(policy.moments == "int8"))
        self.policy = policy
        # the replay ring must live where the train step runs: on a mesh,
        # `_write`'s jit output would otherwise commit the ring to device 0
        # and every mesh-sharded step would reshard it in its prologue
        # (or fail placement outright at >= 2 devices)
        ring_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            ring_sharding = NamedSharding(mesh, P())
        self.replay = ReplayTrainingBuffer(replay_capacity,
                                           dtype=policy.replay_dtype,
                                           sharding=ring_sharding)
        self._member_step = make_train_step(loss_fn, tcfg)
        if policy.params_dtype != "float32":
            pd = jnp.dtype(policy.params_dtype)
            cparams = jax.tree.map(
                lambda x: x.astype(pd)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
                cparams)

        # stacked TrainState: every leaf (step, params, mu, nu) grows a
        # leading K axis; adamw moments start as zeros_like(params) so the
        # stack preserves the committee layout of cparams itself
        states = [make_train_state(member(cparams, i), tcfg)
                  for i in range(self.size)]
        cstate = jax.tree.map(lambda *xs: jnp.stack(xs), *states)

        self.mesh = mesh
        self._mesh_rules = None
        if mesh is not None:
            from repro.sharding.rules import (MeshRules, committee_shardings,
                                              warn_fallbacks)

            self._mesh_rules = MeshRules(mesh, sharding_rules)
            cstate = jax.device_put(
                cstate, committee_shardings(self._mesh_rules, cstate))
            warn_fallbacks(self._mesh_rules, "CommitteeTrainer")
        self.cstate = cstate

        # donation keeps steady-state training alloc-free off-CPU; it also
        # means published params MUST be copied before the next step frees
        # them (snapshot_cparams handles that)
        self._donate = jax.default_backend() != "cpu"
        self._key = jax.random.PRNGKey(seed)
        self._step_seq = 0              # RNG cursor: one fold per step
        self.steps_done = 0
        self.rounds = 0
        self._last_metrics: Optional[Dict[str, Any]] = None
        # (K,) bool verdict of the last trained round's final step: False
        # entries are members whose step was rolled back (non-finite loss
        # or params) — the trainer-side quarantine signal
        self.last_member_ok: Optional[np.ndarray] = None
        # round lock: serializes whole train() rounds (trainer loop vs
        # warm-start/consolidation callers)
        self._lock = threading.Lock()
        # state lock: guards every cstate/replay-handle transition at STEP
        # granularity — held across each fused dispatch (which donates and
        # replaces the state buffers), across state_dict's host snapshot
        # (so a concurrent checkpoint can neither read a torn
        # params/_step_seq pair nor np.asarray a buffer the next step just
        # donated away), and across replay appends (which donate and
        # replace the ring buffers a queued step would otherwise re-use)
        self._state_lock = threading.Lock()
        self._fused = self._build_step()
        self._idx_fn = jax.jit(self._draw_indices)

    # ------------------------------------------------------------- compile
    def _draw_indices(self, key, size):
        """(K, B) bootstrap minibatch indices for one step.  Per-member key
        folds keep members decorrelated; ``bootstrap=False`` replays ONE
        draw to every member (same data order — the parity baseline)."""
        size_c = jnp.maximum(size, 1)
        if self.bootstrap:
            keys = jax.random.split(key, self.size)
            return jax.vmap(
                lambda k: jax.random.randint(k, (self.batch,), 0, size_c)
            )(keys)
        one = jax.random.randint(key, (self.batch,), 0, size_c)
        return jnp.tile(one[None], (self.size, 1))

    def _build_step(self):
        def member_ok(new_state, loss):
            """(K,) finite check for loss AND every post-update param leaf
            — a NaN/Inf anywhere means that member's step diverged."""
            ok = jnp.isfinite(loss)
            for leaf in jax.tree.leaves(new_state.params):
                ok = ok & jnp.all(
                    jnp.isfinite(leaf), axis=tuple(range(1, leaf.ndim)))
            return ok

        # traces as jit_committee_train_step
        def committee_train_step(cstate, xb, yb, size, key):
            idx = self._draw_indices(key, size)             # (K, B)
            # (K, B, d) gather; cast back to fp32 ON DEVICE so a bf16
            # replay ring never leaks its storage dtype into the loss math
            mb = {"x": xb[idx].astype(jnp.float32),
                  "y": yb[idx].astype(jnp.float32)}
            new_state, metrics = jax.vmap(self._member_step)(cstate, mb)
            # per-member quarantine: a member whose step produced a
            # non-finite loss or any non-finite parameter is rolled back to
            # its pre-step state (params, Adam moments AND step counter) via
            # jnp.where inside the SAME dispatch — healthy members advance,
            # nothing extra crosses to host, no retrace
            ok = member_ok(new_state, metrics["loss"])      # (K,)

            def keep(new, old):
                sel = ok.reshape((ok.shape[0],) + (1,) * (new.ndim - 1))
                return jnp.where(sel, new, old)

            rolled = jax.tree.map(keep, new_state, cstate)
            metrics = dict(metrics)
            metrics["member_ok"] = ok
            return rolled, metrics

        kw: Dict[str, Any] = {}
        if self._donate:
            kw["donate_argnums"] = (0,)
        if self._mesh_rules is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from repro.sharding.rules import committee_shardings

            rep = NamedSharding(self._mesh_rules.mesh, P())
            cs = committee_shardings(self._mesh_rules, self.cstate)
            # metrics subtree: a single replicated sharding works as a
            # pytree prefix for whatever aux dict the loss emits
            kw["in_shardings"] = (cs, rep, rep, rep, rep)
            kw["out_shardings"] = (cs, rep)
        return jax.jit(committee_train_step, **kw)

    # ---------------------------------------------------------------- data
    def add_blocks(self, datapoints: Sequence[Tuple[np.ndarray, np.ndarray]]):
        """Absorb a Manager-released ``retrain_size`` block of
        (input, label) pairs into the device replay ring (one transfer).
        Safe concurrently with a running train round: the state lock keeps
        the append's buffer donation from invalidating the ring handles a
        step in flight is about to dispatch with (appends that bypass the
        trainer and hit ``replay.append`` directly do not get this
        protection)."""
        if not datapoints:
            return
        xs = [np.asarray(x, np.float32).reshape(-1) for x, _ in datapoints]
        ys = [np.asarray(y, np.float32).reshape(-1) for _, y in datapoints]
        with self._state_lock:
            self.replay.append(np.stack(xs), np.stack(ys))

    def minibatch_indices(self, step_seq: int, size: int) -> np.ndarray:
        """Host view of the (K, B) indices step ``step_seq`` draws — the
        EXACT computation the fused step runs (same key fold), so
        sequential parity baselines can replay the identical data order."""
        key = jax.random.fold_in(self._key, step_seq)
        return np.asarray(self._idx_fn(key, np.int32(size)))

    # --------------------------------------------------------------- train
    def train(self, interrupt=None, steps: Optional[int] = None
              ) -> Dict[str, np.ndarray]:
        """Advance all K members ``steps`` fused steps (default: the
        configured per-round budget).  ``interrupt`` is the transport
        Request of the NEXT pending data block — training yields early the
        moment new labels arrive, like the paper's ``retrain`` loop.
        Returns the last step's per-member metrics (host numpy)."""
        n_steps = self.steps if steps is None else int(steps)
        mon = self.monitor
        with mon.span("trainer.round", step=self._step_seq):
            with self._lock:
                if len(self.replay) == 0 or n_steps <= 0:
                    return {}
                metrics = None
                done = 0
                for _ in range(n_steps):
                    # per-step state lock: the ring handles are re-fetched
                    # inside it so a concurrent add_blocks (which donates
                    # and replaces the buffers) can never leave this step
                    # holding a deleted array, and a concurrent state_dict
                    # sees a consistent (cstate, _step_seq) pair
                    with mon.span("trainer.dispatch", step=self._step_seq), \
                            self._state_lock:
                        xb, yb, size = self.replay.arrays()
                        key = jax.random.fold_in(self._key, self._step_seq)
                        self._step_seq += 1
                        self.cstate, metrics = self._fused(
                            self.cstate, xb, yb, np.int32(size), key)
                        self.steps_done += 1
                    done += 1
                    if interrupt is not None and interrupt.test():
                        break
                self.rounds += 1
                self._last_metrics = metrics
                mon.incr("train.fused_steps", done)
            with mon.span("trainer.sync"):
                out = jax.tree.map(np.asarray, metrics)
            # rollback accounting rides the round's existing host
            # conversion — zero extra device syncs (the per-step mask never
            # leaves the chip mid-round; only the final step's verdict is
            # inspected here)
            ok = out.get("member_ok") if isinstance(out, dict) else None
            if ok is not None:
                self.last_member_ok = np.asarray(ok, bool)
                bad = int((~self.last_member_ok).sum())
                if bad:
                    mon.incr("train.member_rollbacks", bad)
        return out

    # ------------------------------------------------------------- weights
    @property
    def cparams(self) -> Any:
        """The live stacked committee params (leading K axis)."""
        return self.cstate.params

    def snapshot_cparams(self) -> Any:
        """Donation-safe stacked params for device-to-device handoff to the
        acquisition engine: when the train step donates its state buffers,
        the published pytree must be copied on device before the next step
        invalidates it; without donation the live buffers are immutable and
        handed out as-is.  Either way nothing touches the host."""
        with self._state_lock:
            if not self._donate:
                return self.cstate.params
            return jax.tree.map(lambda a: jnp.array(a, copy=True),
                                self.cstate.params)

    def poison_member(self, i: int):
        """Chaos/test hook: overwrite member ``i``'s parameters with NaN —
        the observable signature of a diverged member.  Downstream, the
        fused step's per-member quarantine rolls back every subsequent
        update for that member (it stays NaN, never contaminating the
        others) and the acquisition kernel's degraded-K statistics exclude
        it from scoring once the poisoned weights publish."""
        if not 0 <= int(i) < self.size:
            raise ValueError(f"member index {i} out of range 0..{self.size - 1}")
        with self._state_lock:
            onehot = jnp.arange(self.size) == int(i)
            params = jax.tree.map(
                lambda leaf: jnp.where(
                    onehot.reshape((self.size,) + (1,) * (leaf.ndim - 1)),
                    jnp.nan, leaf),
                self.cstate.params)
            self.cstate = self.cstate._replace(params=params)
        self.monitor.incr("train.members_poisoned")

    # ---------------------------------------------------------- checkpoint
    def state_dict(self) -> Dict[str, Any]:
        """FULL training snapshot: TrainState (params + AdamW mu/nu + step),
        RNG cursor, and the replay ring — a restore continues mid-schedule
        instead of resetting Adam moments.  Takes the state lock, so a
        checkpoint fired mid-round (``PAL.checkpoint`` from the manager
        thread) snapshots a consistent (cstate, RNG-cursor) pair and the
        host conversion finishes before the next step can donate the
        buffers away."""
        with self._state_lock:
            # QTensor moments snapshot NATIVELY: tree.map hits their int8
            # ``q`` / fp32 ``scale`` leaves, never a dequantized fp32 blob
            return {
                "cstate": jax.tree.map(np.asarray, self.cstate),
                "memory_policy": dataclasses.asdict(self.policy),
                "step_seq": self._step_seq,
                "steps_done": self.steps_done,
                "rounds": self.rounds,
                "replay": self.replay.state_dict(),
            }

    @staticmethod
    def _snapshot_formats(cstate) -> Optional[Dict[str, str]]:
        """Infer {moments, params_dtype} from a snapshot's leaves (legacy
        snapshots carry no policy metadata).  None if the structure is too
        foreign to inspect — the structural check below handles that."""
        try:
            mu_leaves = jax.tree.leaves(
                cstate.opt.mu, is_leaf=lambda x: isinstance(x, QTensor))
            p_leaves = jax.tree.leaves(cstate.params)
        except AttributeError:
            return None
        if any(isinstance(l, QTensor) for l in mu_leaves):
            moments = "int8"
        elif any(np.asarray(l).dtype == jnp.bfloat16
                 for l in jax.tree.leaves(cstate.opt.mu)):
            moments = "bf16"
        else:
            moments = "fp32"
        params_dtype = ("bfloat16" if any(
            np.asarray(l).dtype == jnp.bfloat16 for l in p_leaves)
            else "float32")
        return {"moments": moments, "params_dtype": params_dtype}

    def load_state_dict(self, state: Dict[str, Any]):
        """Restore a ``state_dict`` snapshot if it structurally matches the
        current committee; mismatches (different K, param shapes, or
        optimizer layout) are skipped with a warning — training re-starts
        from the constructor state instead of crashing at trace time.

        A MEMORY-POLICY mismatch is different: the snapshot is valid data
        in another storage format, and silently re-quantizing (or worse,
        reinterpreting sqrt-space int8 nu as fp32) would corrupt the run —
        so it raises ``ValueError`` instead."""
        restored = jax.tree.map(jnp.asarray, state["cstate"])
        snap_policy = state.get("memory_policy")
        if snap_policy is None:
            snap_policy = self._snapshot_formats(restored)
        if snap_policy is not None:
            mine = {"moments": self.policy.moments,
                    "params_dtype": self.policy.params_dtype}
            bad = {k: (snap_policy[k], mine[k]) for k in mine
                   if k in snap_policy and snap_policy[k] != mine[k]}
            if bad:
                raise ValueError(
                    "committee-trainer snapshot memory policy does not "
                    "match the configured policy — refusing to silently "
                    "re-format optimizer state: "
                    + ", ".join(f"{k}: snapshot={s!r} vs config={c!r}"
                                for k, (s, c) in sorted(bad.items()))
                    + ". Restore with a matching memory_policy (or retrain "
                    "from scratch).")
        cur_leaves, cur_def = jax.tree.flatten(self.cstate)
        new_leaves, new_def = jax.tree.flatten(restored)
        if cur_def != new_def or any(
                np.shape(a) != np.shape(b)
                for a, b in zip(cur_leaves, new_leaves)):
            log.warning(
                "committee-trainer snapshot does not match the current "
                "committee (%s vs %s) — skipping restore, training state "
                "starts fresh", new_def, cur_def)
            return
        if self._mesh_rules is not None:
            from repro.sharding.rules import committee_shardings

            restored = jax.device_put(
                restored, committee_shardings(self._mesh_rules, restored))
        with self._state_lock:
            self.cstate = restored
            self._step_seq = int(state.get("step_seq", 0))
            self.steps_done = int(state.get("steps_done", 0))
            self.rounds = int(state.get("rounds", 0))
            self.replay.load_state_dict(state.get("replay", {}))
