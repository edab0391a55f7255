"""The plain reference the benchmark holds the program to.

Written from the equations, in straightforward ``jax.numpy``.  It
imports nothing of the program under test (no ``repro``) and is given
nothing the program made except the state a checked step starts from:
the weights and the data are made by the benchmark from the seed.

* member forces: ``forces`` of the reference side of the architecture
  the configuration names (``archs/<model>_ref.py``, through
  ``harness.arch``), computed here in blocks of rows;
* committee statistics over K members: mean, ddof=1 std, its max over
  components (``scalar_std``) and its mean (``component_std``);
* the Euler walker advance, the patience/restart update and the
  budget controller of the exploration fleet;
* the per-member force loss mean((F - F_label)^2), bootstrap minibatches,
  gradient clipping by global norm and AdamW without weight decay.

``dtype=float32`` runs under ``jax.default_matmul_precision("highest")``;
``dtype=bfloat16`` is the low-precision control (every input, weight and
intermediate in bfloat16).
"""
from __future__ import annotations

import contextlib
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import harness

F32 = jnp.float32
BF16 = jnp.bfloat16


def precision(dtype):
    """Matmul precision context for a reference dtype."""
    if jnp.dtype(dtype) == jnp.dtype(F32):
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def cast(tree, dtype):
    return jax.tree.map(lambda a: jnp.asarray(a, dtype), tree)


@functools.lru_cache(maxsize=None)
def _committee_fn(cfg_key, dtype):
    cfg = json.loads(cfg_key)
    forces = harness.arch(cfg).reference.forces

    def fn(cparams, x):
        return jax.vmap(lambda p: jax.vmap(
            lambda r: forces(p, r, cfg))(x))(cparams)
    return jax.jit(fn)


def _key(cfg):
    """The whole configuration, as the key of its jitted functions."""
    return json.dumps(cfg, sort_keys=True)


def committee_forces(cparams, x, cfg, dtype=F32, block: int = 128):
    """(K, n, 3A) member forces at rows ``x`` (n, 3A), computed ``block``
    rows at a time so that the reference fits beside nothing else."""
    fn = _committee_fn(_key(cfg), jnp.dtype(dtype).name)
    cp = cast(cparams, dtype)
    out = []
    with precision(dtype):
        for s in range(0, x.shape[0], block):
            xb = jnp.asarray(np.asarray(x[s:s + block]), dtype)
            out.append(np.asarray(fn(cp, xb).astype(F32)))
    return np.concatenate(out, axis=1)


def committee_stats(preds, dtype=F32):
    """(K, n, d) -> mean (n, d), scalar_std (n,), component_std (n,).

    float32: computed in float64 on the host; bfloat16: in bfloat16."""
    if jnp.dtype(dtype) == jnp.dtype(F32):
        p = np.asarray(preds, np.float64)
        mean = p.mean(axis=0)
        std = p.std(axis=0, ddof=1)
    else:
        p = jnp.asarray(preds, dtype)
        mean = jnp.mean(p, axis=0)
        std = jnp.sqrt(jnp.sum((p - mean) ** 2, axis=0) / (p.shape[0] - 1))
        mean, std = (np.asarray(a.astype(F32), np.float64)
                     for a in (mean, std))
    return mean, std.max(axis=-1), std.mean(axis=-1)


# ---------------------------------------------------------------- the fleet
def advance(carry, dt, clip, noise, dtype=F32):
    """Proposals of one Euler fleet step from the carry it starts from.

    Walkers flagged by the last round, or non-finite, restart from x0 and
    propose it unchanged, as every walker does on the first step;
    the others move by dt * clip(f) + noise * N(0, 1), the normal draw
    from the first half of a split of the walker's key."""
    x = jnp.asarray(carry["x"], dtype)
    x0 = jnp.asarray(carry["x0"], dtype)
    f = jnp.asarray(carry["f"], dtype)
    keys = jnp.asarray(carry["key"])
    sub = jax.vmap(lambda k: jax.random.split(k)[0])(keys)
    z = jax.vmap(lambda k: jax.random.normal(k, (x.shape[1],)))(sub)
    bad = ~np.all(np.isfinite(np.asarray(carry["x"])), axis=-1)
    reset = jnp.asarray(np.asarray(carry["flag"]) | bad)[:, None]
    first = int(np.asarray(carry["step"])) == 0
    x = jnp.where(reset, x0, x)
    f = jnp.where(reset, 0.0, f)
    moved = x + dt * jnp.clip(f, -clip, clip) + noise * z.astype(dtype)
    out = x if first else jnp.where(reset, x, moved)
    out = jnp.where(jnp.all(jnp.isfinite(out), axis=-1, keepdims=True),
                    out, x0)
    return np.asarray(out.astype(F32))


def patience_update(counts, restarts, flag, x, mask, patience: int):
    """Counters after one round: reset walkers start from 0; a selected
    walker counts up, an unselected one drops to 0; past ``patience`` it
    is flagged to restart and its counter cleared."""
    bad = ~np.all(np.isfinite(x), axis=-1)
    counts = np.where(flag | bad, 0, counts)
    counts = np.where(mask, counts + 1, 0)
    new_flag = counts > patience
    return (np.where(new_flag, 0, counts), restarts + new_flag,
            new_flag)


def budget_update(state, n_selected: int, n_valid: int, target: float,
                  thr_init: float, kp=0.8, ki=0.15, horizon=16,
                  dtype=np.float64):
    """One step of the multiplicative PI controller that steers the
    selection threshold toward ``target`` selected per round, computed
    in ``dtype`` (float64 on the host; bfloat16 for the control)."""
    c = lambda v: np.asarray(v, dtype)  # noqa: E731
    rate = c(n_selected / max(n_valid, 1))
    err = rate - c(target)
    integral = c(state["integral"]) * c(1.0 - 1.0 / horizon) + err
    thr = np.clip(c(state["threshold"]) * np.exp(c(kp) * err
                                                 + c(ki) * integral),
                  c(thr_init * 1e-3), c(thr_init * 1e3))
    ema = c(state["ema_rate"]) + (rate - c(state["ema_rate"])) \
        * c(1.0 / horizon)
    return {"threshold": float(thr), "integral": float(integral),
            "ema_rate": float(ema), "rounds": int(state["rounds"]) + 1}


# ---------------------------------------------------------------- training
def force_loss(params, x, y, cfg):
    """mean((F(x) - y)^2) over the rows and components of a minibatch."""
    forces = harness.arch(cfg).reference.forces
    pred = jax.vmap(lambda r: forces(params, r, cfg))(x)
    return jnp.mean((pred - y) ** 2)


def draw_indices(key, n_members: int, batch: int, size: int):
    """(K, B) bootstrap rows: member k draws B rows uniformly with
    replacement from the first ``size`` rows, with the k-th key of a
    K-way split of the step key."""
    keys = jax.random.split(key, n_members)
    return np.asarray(jax.vmap(
        lambda k: jax.random.randint(k, (batch,), 0, max(size, 1)))(keys))


@functools.lru_cache(maxsize=None)
def _member_grad_fn(cfg_key, dtype):
    cfg = json.loads(cfg_key)
    return jax.jit(jax.value_and_grad(
        lambda p, x, y: force_loss(p, x, y, cfg)))


def train_steps(cparams, x_ring, y_ring, size: int, key, steps: int, cfg,
                *, batch: int, lr: float, b1=0.9, b2=0.95, eps=1e-8,
                clip=1.0, dtype=F32):
    """``steps`` AdamW steps of every member from ``cparams``.

    Step t (from 0) draws its minibatches with ``fold_in(key, t)``.
    Returns (losses (steps, K), first clipped gradient (stacked tree),
    params after the last step (stacked tree)), all float32 numpy."""
    k = jax.tree.leaves(cparams)[0].shape[0]
    grad_fn = _member_grad_fn(_key(cfg), jnp.dtype(dtype).name)
    members = [cast(jax.tree.map(lambda a: np.asarray(a)[i], cparams), dtype)
               for i in range(k)]
    mu = [jax.tree.map(jnp.zeros_like, p) for p in members]
    nu = [jax.tree.map(jnp.zeros_like, p) for p in members]
    losses, first_grads = [], None
    with precision(dtype):
        for t in range(steps):
            idx = draw_indices(jax.random.fold_in(key, t), k, batch, size)
            row, grads = [], []
            for i in range(k):
                xb = jnp.asarray(np.asarray(x_ring)[idx[i]], dtype)
                yb = jnp.asarray(np.asarray(y_ring)[idx[i]], dtype)
                loss, g = grad_fn(members[i], xb, yb)
                gn = jnp.sqrt(sum(jnp.sum(jnp.square(v))
                                  for v in jax.tree.leaves(g)))
                scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))
                g = jax.tree.map(lambda v: (v * scale).astype(dtype), g)
                c1 = 1.0 - b1 ** (t + 1)
                c2 = 1.0 - b2 ** (t + 1)
                mu[i] = jax.tree.map(lambda m, v: b1 * m + (1 - b1) * v,
                                     mu[i], g)
                nu[i] = jax.tree.map(lambda m, v: b2 * m + (1 - b2) * v * v,
                                     nu[i], g)
                members[i] = jax.tree.map(
                    lambda p, m, v: (p - lr * ((m / c1)
                                               / (jnp.sqrt(v / c2) + eps))
                                     ).astype(dtype),
                    members[i], mu[i], nu[i])
                row.append(float(loss))
                grads.append(g)
            losses.append(row)
            if t == 0:
                first_grads = grads
    stack = lambda trees: jax.tree.map(  # noqa: E731
        lambda *a: np.stack([np.asarray(v, np.float32) for v in a]), *trees)
    return np.asarray(losses), stack(first_grads), stack(members)
