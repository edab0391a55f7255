"""A toy second architecture for the harness's CPU tests: a committee of
pair potentials, E = sum_{i<j} MLP(rbf(d_ij)), one hidden layer of
``width`` tanh units over ``n_basis`` Gaussians of the pair distance.
Its weights are a nested pytree of its own; its reference is
``pair_mlp_ref.py`` beside it.  No cell runs it."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def make_weights(cfg, seed: int):
    k, nb, w = cfg["committee_size"], cfg["n_basis"], cfg["width"]

    @jax.jit
    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"embed": {"w": jax.random.normal(k1, (k, nb, w))
                          / np.sqrt(nb),
                          "b": 0.1 * jax.random.normal(k2, (k, w))},
                "out": {"w": jax.random.normal(k3, (k, w)) / np.sqrt(w),
                        "b": jnp.zeros((k,))}}

    return init(jax.random.PRNGKey(seed))


def _energy(p, coords, cfg):
    """All (A, A) pairs at once, the lower triangle and the diagonal
    masked out."""
    a = coords.shape[0]
    upper = jnp.triu(jnp.ones((a, a), bool), 1)
    diff = coords[:, None, :] - coords[None, :, :]
    d = jnp.sqrt(jnp.sum(diff * diff, axis=-1) + jnp.eye(a))
    centers = jnp.linspace(0.5, cfg["r_cut"], cfg["n_basis"])
    basis = jnp.exp(-((d[..., None] - centers) ** 2))
    h = jnp.tanh(basis @ p["embed"]["w"] + p["embed"]["b"])
    pair = h @ p["out"]["w"] + p["out"]["b"]
    return jnp.sum(jnp.where(upper, pair, 0.0))


def member_functions(cfg):
    a = cfg["n_atoms"]

    def member_forces(p, flat_batch):              # (n, 3A) -> (n, 3A)
        grad = jax.grad(_energy, argnums=1)
        return jax.vmap(lambda f: -grad(p, f.reshape(a, 3), cfg)
                        .reshape(-1))(flat_batch)

    def member_force_loss(p, batch):
        pred = member_forces(p, batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2), {}

    return member_forces, member_force_loss


def fleet_step_flops(cfg, n_walkers: int) -> float:
    """Forward and input gradient of both layers for every pair."""
    pairs = cfg["n_atoms"] * (cfg["n_atoms"] - 1) // 2
    macs = 2 * (cfg["n_basis"] * cfg["width"] + cfg["width"])
    return 2.0 * cfg["committee_size"] * n_walkers * pairs * macs


def train_step_flops(cfg, batch: int) -> float:
    """Three times the fleet's per structure: the force and its
    parameter gradient."""
    return 3.0 * fleet_step_flops(cfg, batch)
