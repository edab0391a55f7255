"""Plain reference of the toy pair potential ``pair_mlp``: a loop over
the pairs i < j, written apart from the program side."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def energy(p, coords, cfg):
    i, j = np.triu_indices(coords.shape[0], 1)
    d = jnp.linalg.norm(coords[i] - coords[j], axis=-1)       # (P,)
    centers = jnp.linspace(0.5, cfg["r_cut"], cfg["n_basis"]).astype(d.dtype)
    basis = jnp.exp(-jnp.square(d[:, None] - centers[None, :]))
    h = jnp.tanh(basis @ p["embed"]["w"] + p["embed"]["b"])
    return jnp.sum(h @ p["out"]["w"] + p["out"]["b"])


def forces(params, flat, cfg):
    """(3A,) -> (3A,) forces of one member."""
    coords = flat.reshape(cfg["n_atoms"], 3)
    return -jax.grad(energy, argnums=1)(params, coords, cfg).reshape(-1)
