"""Geometries, the Lennard-Jones oracle and the comparison arithmetic.

Copied into the benchmark from ``chip_smoke.py`` (``potential()``'s
lattice sampler and LJ oracle, and ``close``; ``compare_uq``'s rule for
mask flips near the threshold is in ``loops/exchange.py``) so that the
yardstick does not move when that script does.  The LJ energy is the one
the program's own ``lennard_jones`` computes (eps = sigma = 1), written
out here again.
"""
from __future__ import annotations

import numpy as np


def lattice(cells, spacing: float) -> np.ndarray:
    """(A, 3) simple-cubic lattice of ``cells[0] x cells[1] x cells[2]``
    sites at ``spacing``."""
    axes = [np.arange(c, dtype=np.float64) * spacing for c in cells]
    return np.stack(np.meshgrid(*axes, indexing="ij"), -1).reshape(-1, 3)


def geometries(rng: np.random.RandomState, n: int, base: np.ndarray,
               perturb: float) -> np.ndarray:
    """(n, 3A) float32 lattices with every coordinate perturbed by
    ``perturb`` * N(0, 1)."""
    x = base[None] + rng.randn(n, *base.shape) * perturb
    return x.reshape(n, -1).astype(np.float32)


def lj_energy(coords):
    """Lennard-Jones energy (eps = sigma = 1) of one (A, 3) structure."""
    import jax.numpy as jnp

    a = coords.shape[0]
    diff = coords[:, None, :] - coords[None, :, :]
    eye = jnp.eye(a, dtype=coords.dtype)
    d2 = jnp.sum(diff * diff, axis=-1) + eye
    sr6 = (1.0 / d2) ** 3
    return 0.5 * jnp.sum((1.0 - eye) * 4.0 * (sr6 ** 2 - sr6))


def lj_forces(flat, n_atoms: int):
    """(3A,) -> (3A,) LJ forces, the ab initio stand-in that labels the
    replay ring."""
    import jax

    coords = flat.reshape(n_atoms, 3)
    return -jax.grad(lj_energy)(coords).reshape(-1)


def close(got, want, tol):
    """Largest |got - want| relative to the largest |want|, and whether it
    is within ``tol``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    err = float(np.max(np.abs(got - want))) / scale
    return err, err <= tol

