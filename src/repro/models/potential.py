"""The paper's own domain: committee MLP potentials on radial-basis
descriptors (PAL §3.1–3.3).

Energy model: Behler-style per-atom MLP over symmetric radial-basis features
of pairwise distances; total energy = sum of atomic energies; forces =
-grad_R E via jax.grad.  A committee of K such potentials (stacked params +
vmap, DESIGN.md §2) provides query-by-committee uncertainty.

Also ships two analytic "oracles" (Lennard-Jones and Morse cluster
potentials) used as the DFT stand-in ground truth in examples and tests.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.pal_potential import PotentialConfig
from repro.models.common import ParamSpec, init_params

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Descriptors
# ---------------------------------------------------------------------------


def _pair_distances(coords: jnp.ndarray) -> jnp.ndarray:
    """coords (A, 3) -> (A, A) distances with safe diagonal."""
    diff = coords[:, None, :] - coords[None, :, :]
    d2 = jnp.sum(diff * diff, axis=-1)
    a = coords.shape[0]
    d2 = d2 + jnp.eye(a) * 1e6          # mask self-distance out of the RBFs
    return jnp.sqrt(d2 + 1e-12)


def _radial_terms(d: jnp.ndarray, cfg: PotentialConfig):
    """(A, A) distances -> the Gaussians (A, A, n_rbf), the cosine cutoff
    (A, A), and what d/dd of their product needs: centres and gamma."""
    centers = jnp.linspace(0.5, cfg.r_cut, cfg.n_rbf)
    gamma = (cfg.n_rbf / cfg.r_cut) ** 2
    rbf = jnp.exp(-gamma * (d[..., None] - centers) ** 2)   # (A, A, n_rbf)
    fcut = 0.5 * (jnp.cos(jnp.pi * jnp.clip(d / cfg.r_cut, 0, 1)) + 1.0)
    return rbf, fcut, centers, gamma


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def descriptors(coords: jnp.ndarray, cfg: PotentialConfig) -> jnp.ndarray:
    """(A, 3) -> (A, n_rbf) summed Gaussian RBFs with cosine cutoff.

    Differentiated by the hand-written JVP below, in forward and reverse
    mode."""
    rbf, fcut, _, _ = _radial_terms(_pair_distances(coords), cfg)
    return jnp.sum(rbf * fcut[..., None], axis=1)     # (A, n_rbf)


@descriptors.defjvp
def _descriptors_jvp(cfg, primals, tangents):
    """dG[i,r] = sum_j w[i,j,r] dd[i,j] with the single derivative
    w = d(rbf * fcut)/dd.  Autodiff would follow the product rule into
    two (A, A, n_rbf) terms, each reduced over the radial axis in
    reverse mode; one contraction lets the committee's members, which
    share the coordinates, read each w once in one dot."""
    (coords,), (dcoords,) = primals, tangents
    d, dd = jax.jvp(_pair_distances, (coords,), (dcoords,))
    rbf, fcut, centers, gamma = _radial_terms(d, cfg)
    g = jnp.sum(rbf * fcut[..., None], axis=1)
    # the cutoff's slope: 0 beyond r_cut, where the clip is flat
    dfcut = jnp.where(d < cfg.r_cut, -(jnp.pi / (2 * cfg.r_cut))
                      * jnp.sin(jnp.pi * d / cfg.r_cut), 0.0)
    w = rbf * (fcut[..., None] * (-2.0 * gamma) * (d[..., None] - centers)
               + dfcut[..., None])
    dg = jnp.einsum("ijr,ij->ir", w, dd,
                    precision=jax.lax.Precision.HIGHEST)
    return g, dg


# ---------------------------------------------------------------------------
# MLP potential
# ---------------------------------------------------------------------------


def param_specs(cfg: PotentialConfig) -> Params:
    dims = (cfg.n_rbf,) + tuple(cfg.hidden) + (1,)
    s: Params = {}
    for i in range(len(dims) - 1):
        s[f"w{i}"] = ParamSpec((dims[i], dims[i + 1]), (None, None))
        s[f"b{i}"] = ParamSpec((dims[i + 1],), (None,), init="zeros")
    return s


def init(cfg: PotentialConfig, rng) -> Params:
    return init_params(param_specs(cfg), rng)


def init_committee(cfg: PotentialConfig, rng) -> Params:
    keys = jax.random.split(rng, cfg.committee_size)
    members = [init(cfg, k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *members)


def energy(params: Params, coords: jnp.ndarray, cfg: PotentialConfig):
    """(A, 3) -> scalar energy."""
    # the named scopes label the operations in a device profile
    with jax.named_scope("descriptor"):
        h = descriptors(coords, cfg)
    with jax.named_scope("mlp"):
        n = len([k for k in params if k.startswith("w")])
        for i in range(n):
            h = h @ params[f"w{i}"] + params[f"b{i}"]
            if i < n - 1:
                h = jnp.tanh(h)
        return jnp.sum(h)


def energy_forces(params: Params, coords: jnp.ndarray, cfg: PotentialConfig):
    e, g = jax.value_and_grad(energy, argnums=1)(params, coords, cfg)
    return e, -g


def committee_energy_forces(cparams: Params, coords: jnp.ndarray,
                            cfg: PotentialConfig):
    """Stacked params (K, ...) -> (E (K,), F (K, A, 3))."""
    return jax.vmap(lambda p: energy_forces(p, coords, cfg))(cparams)


def batched_committee_energy_forces(cparams: Params, coords: jnp.ndarray,
                                    cfg: PotentialConfig):
    """coords (B, A, 3) -> (E (B, K), F (B, K, A, 3))."""
    def one(c):
        return committee_energy_forces(cparams, c, cfg)
    e, f = jax.vmap(one)(coords)
    return e, f


# ---------------------------------------------------------------------------
# Analytic oracles (ground-truth stand-ins for DFT; see DESIGN.md §2)
# ---------------------------------------------------------------------------


def lennard_jones(coords: jnp.ndarray, eps: float = 1.0, sigma: float = 1.0):
    d = _pair_distances(coords)
    a = coords.shape[0]
    mask = 1.0 - jnp.eye(a)
    sr6 = (sigma / d) ** 6
    e = 0.5 * jnp.sum(mask * 4.0 * eps * (sr6 ** 2 - sr6))
    return e


def lj_energy_forces(coords: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    e, g = jax.value_and_grad(lennard_jones)(coords)
    return e, -g


def morse(coords: jnp.ndarray, de: float = 1.0, a: float = 1.2,
          r0: float = 1.2):
    d = _pair_distances(coords)
    n = coords.shape[0]
    mask = 1.0 - jnp.eye(n)
    e = 0.5 * jnp.sum(mask * de * (1.0 - jnp.exp(-a * (d - r0))) ** 2)
    return e


def morse_energy_forces(coords):
    e, g = jax.value_and_grad(morse)(coords)
    return e, -g


# ---------------------------------------------------------------------------
# Training-side loss (energy + force matching, the standard MLP-potential fit)
# ---------------------------------------------------------------------------


def potential_loss(params: Params, batch, cfg: PotentialConfig,
                   force_weight: float = 10.0):
    """batch: {"coords": (B,A,3), "energy": (B,), "forces": (B,A,3)}."""
    def one(c):
        return energy_forces(params, c, cfg)

    e, f = jax.vmap(one)(batch["coords"])
    e_loss = jnp.mean((e - batch["energy"]) ** 2)
    f_loss = jnp.mean((f - batch["forces"]) ** 2)
    return e_loss + force_weight * f_loss, {"e_mse": e_loss, "f_mse": f_loss}
