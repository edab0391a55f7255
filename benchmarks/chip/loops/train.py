"""Traffic kind ``train``: committee retraining through
``CommitteeTrainer.train()``.

Set-up builds ``PAL(...)`` with the fused committee trainer, fills its
device replay ring with ``replay_rows`` perturbed lattices labelled by LJ
forces (made on the device from the seed, appended in one transfer), and
drives the trainer through its first ``check_steps`` steps with the
window's own call, ``train(steps=1)``: the first compiles the step.  The
losses, the state after the first step and the state after the last are
read for the check.  The window then calls ``train()`` (``train_steps``
steps per round, each round ending in the trainer's host sync) until
``--seconds`` have passed.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

import harness
import lattice
import program
import reference as ref

BETA1 = 0.9            # the trainer's AdamW defaults (TrainConfig)


@dataclasses.dataclass
class State:
    pal: Any
    p0: Any
    x_ring: np.ndarray
    y_ring: np.ndarray
    losses: np.ndarray
    grad1: Any
    params_end: Any


def make_ring(cfg, rows: int, seed: int):
    """(rows, 3A) perturbed lattices and their LJ forces, on the device in
    one jitted call."""
    base = jnp.asarray(program.base_geometry(cfg), jnp.float32)
    a = cfg["n_atoms"]
    perturb = cfg["geometry"]["perturb"]

    @jax.jit
    def make(key):
        x = (base[None] + perturb * jax.random.normal(
            key, (rows, a, 3))).reshape(rows, 3 * a)
        return x, jax.vmap(lambda r: lattice.lj_forces(r, a))(x)

    return make(jax.random.PRNGKey(seed))


def setup(ctx) -> State:
    cfg, t = ctx.cfg, ctx.traffic
    with ctx.spans("setup.weights"):
        cparams = harness.arch(cfg).program.make_weights(
            cfg, ctx.seed_for("weights"))
        x, y = make_ring(cfg, t["replay_rows"], ctx.seed_for("ring"))
        x_ring, y_ring = np.asarray(x), np.asarray(y)
        p0 = jax.tree.map(np.asarray, cparams)
    with ctx.spans("setup.build"):
        pal = program.build_pal(cfg, t, ctx.seed_for("program"), cparams,
                                impl=ctx.impl)
        tr = pal.committee_trainer
        tr.replay.append(x_ring, y_ring)
    with ctx.spans("setup.warmup"):
        losses, grad1 = [], None
        for s in range(t["check_steps"]):
            losses.append(np.asarray(tr.train(steps=1)["loss"]))
            if s == 0:
                mu = tr.state_dict()["cstate"].opt.mu
                grad1 = jax.tree.map(lambda m: np.asarray(m) / (1 - BETA1),
                                     mu)
        params_end = jax.tree.map(np.asarray,
                                  tr.state_dict()["cstate"].params)
    return State(pal=pal, p0=p0, x_ring=x_ring, y_ring=y_ring,
                 losses=np.stack(losses), grad1=grad1,
                 params_end=params_end)


def window(ctx, st: State, seconds: float) -> Dict[str, Any]:
    """Whole train rounds for ``seconds``."""
    pal = st.pal
    tr = pal.committee_trainer
    mon = pal.monitor
    s0 = mon.count("train.fused_steps")
    r0 = mon.count("train.member_rollbacks")
    rounds = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        with ctx.spans("trainer.train"):
            tr.train()
        rounds += 1
        if time.perf_counter() >= deadline:
            break
    elapsed = time.perf_counter() - t0
    steps = mon.count("train.fused_steps") - s0
    per_step = tr.size * tr.batch
    return {
        "work": steps * per_step, "elapsed": elapsed, "steps": steps,
        "rounds": rounds, "attempted": steps * per_step,
        "failed": (mon.count("train.member_rollbacks") - r0) * tr.batch,
        "train_batch": tr.batch,
    }


def release(st: State):
    st.pal = None


def _norms(tree):
    """Norm of every (member, leaf) pair of a stacked (K, ...) tree."""
    return [float(np.linalg.norm(np.asarray(a, np.float64)[k]))
            for a in jax.tree.leaves(tree) for k in range(a.shape[0])]


def _leaf_gap(got, want, grad_ref) -> float:
    """Worst leaf, over members and parameter leaves: the gap between the
    two norms over the larger of the reference's norm and the median
    leaf norm.  Leaves whose first gradient in the reference is under a
    thousandth of the median leaf's (the output bias, which forces do not
    see, moves under Adam by round-off alone) are left out."""
    g_ref = _norms(grad_ref)
    g_med = float(np.median(g_ref))
    pairs = [p for p, r in zip(zip(_norms(got), _norms(want)), g_ref)
             if r >= 1e-3 * g_med]
    med = float(np.median([w for _, w in pairs]))
    worst = 0.0
    for g, w in pairs:
        if not np.isfinite(g):
            return float("inf")
        worst = max(worst, abs(g - w) / max(w, med))
    return worst


def readings(ctx, st: State, dtype=ref.F32, limits=None) -> Dict[str, float]:
    t = ctx.traffic
    steps = t["check_steps"]
    key = jax.random.PRNGKey(ctx.seed_for("program"))
    kw = dict(batch=t["batch"], lr=t["lr"])
    losses_r, grad_r, params_r = ref.train_steps(
        st.p0, st.x_ring, st.y_ring, t["replay_rows"], key, steps, ctx.cfg,
        **kw)
    if np.dtype(dtype) == np.dtype(np.float32):
        losses, grad1, params_end = st.losses, st.grad1, st.params_end
    else:
        losses, grad1, params_end = ref.train_steps(
            st.p0, st.x_ring, st.y_ring, t["replay_rows"], key, steps,
            ctx.cfg, dtype=dtype, **kw)
    delta = lambda p: jax.tree.map(  # noqa: E731
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        p, st.p0)
    loss_gap = float(np.max(np.abs(np.asarray(losses, np.float64)
                                   - losses_r) / np.abs(losses_r)))
    if not np.all(np.isfinite(losses)):
        loss_gap = float("inf")
    return {
        "loss_gap": loss_gap,
        "grad_gap": _leaf_gap(grad1, grad_r, grad_r),
        "update_gap": _leaf_gap(delta(params_end), delta(params_r), grad_r),
    }
