"""Device-resident exploration fleet — the paper's generator processes,
vectorized.

The paper (§2.2) runs each MD walker as a host process: propose on host,
ship to the prediction kernel, wait for the committee mean, react to the
uncertainty flag.  ``WalkerFleet`` replaces N of those processes with ONE
stacked, device-resident walker state (positions, velocities, per-walker
RNG keys, patience counters) advanced by a jitted vmapped sampler step
that is FUSED with acquisition: walker advance → committee forward →
Welford UQ → selection-rule pipeline compile into a single device program
per shape bucket (``FusedEngine.score_after``).  Per-walker restart /
patience becomes a device rule (``PatienceRestart`` — the ``jnp.where``
realization of ``core/selection.PatienceTracker``), so the exchange loop
collapses to explore→score→select with the selected oracle candidates as
the only per-iteration host traffic.

Sampler protocol
----------------
A sampler is ``sample(x, v, f, keys) -> (x', v')`` in pure jnp over the
stacked ``(nb, d)`` state, with one PRNG key per walker.  Two built-ins:

  'euler'     — ``x + dt * clip(f, ±clip) + noise * N(0, 1)``; with
                ``noise=0`` this reproduces the host ``MDGenerator``
                update exactly (the parity tests drive it).
  'langevin'  — damped velocity dynamics: ``v' = (1-friction) v +
                dt * clip(f) + noise * N(0,1)``, ``x' = x + dt * v'``.

The force driving the advance is the committee MEAN from the PREVIOUS
fused round (``stats.mean`` folded back into the carry by the react step)
— the same information a host generator receives from the exchange
scatter, with zero host round trip.

Restart semantics
-----------------
``PatienceRestart`` applies the host tracker's exact update on device:
counts increment while a walker stays selected (uncertain), a count
exceeding ``patience`` flags the walker, flagged walkers reset to their
trusted state ``x0`` at the START of the next step (mirroring the host
path, where the generator receives ``None`` and restarts on its next
call).  Non-finite walkers (diverged dynamics, chaos ``nan_walker``)
reset through the same gate instead of crashing the loop.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.acquisition import FusedStepOut
from repro.core.committee import shape_bucket

_FLEET_IDS = itertools.count()


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Knobs for one walker fleet (``PALRunConfig.fleet_*`` plumbs these).

    ``patience`` follows the host semantics: a walker may stay uncertain
    for up to ``patience`` consecutive steps; the step AFTER that resets
    it to its trusted state.  ``max_steps`` (0 = unbounded) stops the
    exchange loop after that many fleet steps.
    """

    dt: float = 0.002
    clip: float = 20.0
    noise: float = 0.01
    friction: float = 0.1
    sampler: str = "euler"
    patience: int = 5
    max_steps: int = 0
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class PatienceRestart:
    """Device realization of ``selection.PatienceTracker`` — identical
    update, expressed as ``jnp.where`` over the stacked counters:

        counts'   = where(uncertain, counts + 1, 0)
        flag      = counts' > patience
        restarts' = restarts + flag
        counts''  = where(flag, 0, counts')

    ``flag`` marks walkers that must reset to their trusted state on the
    next advance (the host path realizes the same flag as a ``None``
    scatter the generator reacts to one call later)."""

    patience: int

    def apply(self, counts, restarts, uncertain):
        counts = jnp.where(uncertain, counts + 1, 0)
        flag = counts > self.patience
        restarts = restarts + flag.astype(restarts.dtype)
        counts = jnp.where(flag, 0, counts)
        return counts, restarts, flag


def make_sampler(cfg: FleetConfig) -> Callable:
    """Build the stacked sampler step ``(x, v, f, keys) -> (x', v')``."""
    dt = jnp.float32(cfg.dt)
    clip = jnp.float32(cfg.clip)
    noise = jnp.float32(cfg.noise)
    friction = jnp.float32(cfg.friction)

    def _noise(keys, d):
        return jax.vmap(lambda k: jax.random.normal(k, (d,)))(keys)

    if cfg.sampler == "euler":
        def sample(x, v, f, keys):
            fx = jnp.clip(f, -clip, clip)
            return x + dt * fx + noise * _noise(keys, x.shape[-1]), v
    elif cfg.sampler == "langevin":
        def sample(x, v, f, keys):
            fx = jnp.clip(f, -clip, clip)
            v2 = (1.0 - friction) * v + dt * fx \
                + noise * _noise(keys, x.shape[-1])
            return x + dt * v2, v2
    else:
        raise ValueError(
            f"fleet sampler {cfg.sampler!r}: expected 'euler' or 'langevin'")
    return sample


class WalkerFleet:
    """N stacked device-resident walkers, one fused dispatch per step.

    The carry pytree never leaves the device on the hot path:

        x          (nb, d)  walker positions (the proposal batch)
        v          (nb, d)  walker velocities ('langevin' sampler)
        f          (nb, d)  committee-mean force from the previous round
        key        (nb, 2)  per-walker PRNG keys (uint32)
        counts     (nb,)    consecutive-uncertain counters (PatienceRestart)
        restarts   (nb,)    realized patience restarts per walker
        flag       (nb,)    walkers that must reset on the next advance
        x0         (nb, d)  trusted restart states
        step       scalar   fleet step counter (first-call semantics)
        nan_resets scalar   walkers reset because they went non-finite

    ``step()`` calls ``engine.score_after``: the sampler advance, the
    committee forward, the Welford UQ, the rule pipeline, and the
    patience/restart react all run inside ONE compiled program; the host
    receives the selected oracle candidates and one int32 count.  The
    committee output dimension must equal the walker dimension (forces).

    ``engine`` must be a ``FusedEngine`` — the legacy per-member backend
    has no fused step entry point (the runtime enforces this).
    """

    def __init__(self, engine, x0: np.ndarray, cfg: FleetConfig,
                 monitor=None, chaos=None):
        if not hasattr(engine, "score_after"):
            raise ValueError(
                "WalkerFleet needs a fused acquisition engine "
                "(FusedEngine.score_after); the legacy per-member backend "
                "cannot fuse the walker advance with scoring")
        x0 = np.asarray(x0, np.float32)
        if x0.ndim != 2:
            raise ValueError(
                f"fleet x0 must be (n_walkers, dim), got {x0.shape}")
        self.engine = engine
        self.cfg = cfg
        self.monitor = monitor
        self.chaos = chaos
        self.n_walkers, self.dim = int(x0.shape[0]), int(x0.shape[1])
        self.nb = shape_bucket(self.n_walkers, engine.min_bucket)
        self.restart_rule = PatienceRestart(cfg.patience)
        self._sampler = make_sampler(cfg)
        # one jit-cache key per fleet instance: different fleets (different
        # sampler/patience closures) on the same engine must not collide
        self._cache_key = f"fleet{next(_FLEET_IDS)}"
        self.steps_done = 0
        self.last: Optional[FusedStepOut] = None
        # carry lock: ``score_after`` DONATES the carry off-CPU, so between
        # the dispatch and the rebinding of ``_carry`` the old buffers are
        # deleted.  Every reader (a checkpoint from the manager thread,
        # ``report``) and writer of ``_carry`` holds this lock.
        self._lock = threading.Lock()

        pad = np.zeros((self.nb, self.dim), np.float32)
        pad[:self.n_walkers] = x0
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            jax.random.PRNGKey(cfg.seed), jnp.arange(self.nb))
        self._carry: Dict[str, Any] = {
            "x": jnp.asarray(pad),
            "v": jnp.zeros((self.nb, self.dim), jnp.float32),
            "f": jnp.zeros((self.nb, self.dim), jnp.float32),
            "key": keys,
            "counts": jnp.zeros((self.nb,), jnp.int32),
            "restarts": jnp.zeros((self.nb,), jnp.int32),
            "flag": jnp.zeros((self.nb,), bool),
            "x0": jnp.asarray(pad),
            "step": jnp.zeros((), jnp.int32),
            "nan_resets": jnp.zeros((), jnp.int32),
        }
        # on a mesh, per-walker state shards rows over the 'data' axis
        # alongside the proposal batch (scalars replicate) — without this
        # the first score_after output commits the carry to device 0 and
        # subsequent sharded dispatches reshard it every iteration
        self._carry = engine.place_carry(self._carry, self.nb)

    # ------------------------------------------------------------- device fns
    def _step_fn(self, carry):
        """Advance all walkers (traced into the fused dispatch).

        Order matches the host generator's reaction protocol: first react
        to LAST round's outcome (restart flagged walkers to x0), then
        advance with the sampler.  The very first step proposes the
        initial states unchanged — the host generators' first-call
        semantics, so scoring starts from the trusted configurations."""
        first = carry["step"] == 0
        keys = jax.vmap(jax.random.split)(carry["key"])
        sub, nxt = keys[:, 0], keys[:, 1]

        bad = ~jnp.all(jnp.isfinite(carry["x"]), axis=-1)
        reset = carry["flag"] | bad
        x = jnp.where(reset[:, None], carry["x0"], carry["x"])
        v = jnp.where(reset[:, None], 0.0, carry["v"])
        f = jnp.where(reset[:, None], 0.0, carry["f"])

        x_adv, v_adv = self._sampler(x, v, f, sub)
        # a freshly restarted (or first-step) walker proposes its trusted
        # state itself, exactly like a host generator receiving None
        skip = first | reset
        x = jnp.where(skip[:, None], x, x_adv)
        v = jnp.where(skip[:, None], v, v_adv)
        # dynamics can still diverge within the advance itself
        blown = ~jnp.all(jnp.isfinite(x), axis=-1)
        x = jnp.where(blown[:, None], carry["x0"], x)
        v = jnp.where(blown[:, None], 0.0, v)
        nan_hits = jnp.sum(bad | blown).astype(jnp.int32)

        mid = dict(
            carry, x=x, v=v, key=nxt,
            counts=jnp.where(reset, 0, carry["counts"]),
            flag=jnp.zeros_like(carry["flag"]),
            nan_resets=carry["nan_resets"] + nan_hits)
        return x, mid

    def _react_fn(self, mid, stats, mask):
        """Fold the round's outcome back into the carry (traced): patience
        counters advance on the selection mask, the committee mean becomes
        next step's driving force."""
        counts, restarts, flag = self.restart_rule.apply(
            mid["counts"], mid["restarts"], mask)
        return dict(mid, counts=counts, restarts=restarts, flag=flag,
                    f=stats.mean, step=mid["step"] + 1)

    # ------------------------------------------------------------------ step
    def step(self) -> FusedStepOut:
        """One fused explore→score→select round.  Host traffic: the
        selected oracle candidates plus one int32 count — nothing for
        unselected walkers."""
        if self.chaos is not None:
            ev = self.chaos.take("fleet.step")
            if ev is not None:
                if ev.kind == "nan_walker":
                    self.poison_walker(int(ev.arg))
                else:
                    self.chaos.execute(ev)
        with self._lock:
            self._carry, out = self.engine.score_after(
                self._step_fn, self._carry, self.n_walkers, self.nb,
                react_fn=self._react_fn, cache_key=self._cache_key)
        self.steps_done += 1
        self.last = out
        return out

    # ------------------------------------------------------------ inspection
    def positions(self) -> np.ndarray:
        """(n_walkers, d) host snapshot of walker positions — diagnostics
        and tests only; the hot loop never calls this."""
        with self._lock:
            return np.asarray(self._carry["x"][:self.n_walkers])

    def stats(self) -> Dict[str, Any]:
        """Host snapshot of fleet health (PAL.report) — one transfer per
        call, off the hot path."""
        with self._lock:
            c = {k: np.asarray(self._carry[k])
                 for k in ("step", "restarts", "nan_resets", "counts")}
        return {
            "walkers": self.n_walkers,
            "steps": int(c["step"]),
            "restarts": int(np.sum(c["restarts"][:self.n_walkers])),
            "nan_resets": int(c["nan_resets"]),
            "uncertain_streak_max": int(np.max(c["counts"][:self.n_walkers])),
        }

    # ------------------------------------------------------------ checkpoint
    def state_dict(self) -> Dict[str, np.ndarray]:
        """Full host-numpy snapshot of the carry — including the per-walker
        RNG keys and step counter, so a restored fleet replays the exact
        trajectory (bit-identical resume)."""
        with self._lock:
            return {k: np.asarray(v) for k, v in self._carry.items()}

    def load_state_dict(self, state: Dict[str, np.ndarray]):
        if set(state) != set(self._carry):
            raise ValueError(
                f"fleet snapshot keys {sorted(state)} do not match the "
                f"carry {sorted(self._carry)}")
        carry = self.engine.place_carry(
            {k: jnp.asarray(v) for k, v in state.items()}, self.nb)
        with self._lock:
            self._carry = carry

    # ----------------------------------------------------------------- chaos
    def poison_walker(self, i: int):
        """Set walker i's position non-finite (chaos ``nan_walker``): the
        next fused step routes it through the restart gate — reset to its
        trusted state, never a crash."""
        with self._lock:
            self._carry = dict(
                self._carry, x=self._carry["x"].at[i].set(jnp.nan))
