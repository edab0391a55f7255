"""Plain reference of the architecture ``mlp_potential``, written from
its equations in straightforward ``jax.numpy``; it imports nothing of the
program under test.

* descriptor: per atom i, G_ir = sum_{j != i} exp(-gamma (d_ij - c_r)^2)
  f_c(d_ij), with c_r = linspace(0.5, r_cut, n_rbf), gamma =
  (n_rbf / r_cut)^2 and the cosine cutoff f_c(d) = (cos(pi min(d /
  r_cut, 1)) + 1) / 2;
* member energy E = sum_i MLP(G_i), tanh between layers, and forces F =
  -dE/dR.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def descriptor(coords, n_rbf: int, r_cut: float):
    """(A, 3) -> (A, n_rbf) radial descriptor."""
    dt = coords.dtype
    a = coords.shape[0]
    eye = jnp.eye(a, dtype=dt)
    diff = coords[:, None, :] - coords[None, :, :]
    d = jnp.sqrt(jnp.sum(diff * diff, axis=-1) + eye)   # self pairs: 1
    centers = jnp.linspace(0.5, r_cut, n_rbf).astype(dt)
    gamma = jnp.asarray((n_rbf / r_cut) ** 2, dt)
    g = jnp.exp(-gamma * (d[..., None] - centers) ** 2)  # (A, A, n_rbf)
    fc = 0.5 * (jnp.cos(jnp.pi * jnp.minimum(d / r_cut, 1.0)) + 1.0)
    return jnp.sum(g * (fc * (1.0 - eye))[..., None], axis=1)


def energy(params, coords, cfg):
    """Energy of one (A, 3) structure for one member's params."""
    h = descriptor(coords, cfg["n_rbf"], cfg["r_cut"])
    n = len(cfg["hidden"]) + 1
    for i in range(n):
        h = h @ params[f"w{i}"] + params[f"b{i}"]
        if i < n - 1:
            h = jnp.tanh(h)
    return jnp.sum(h)


def forces(params, flat, cfg):
    """(3A,) -> (3A,) forces of one member."""
    coords = flat.reshape(cfg["n_atoms"], 3)
    return -jax.grad(energy, argnums=1)(params, coords, cfg).reshape(-1)
