"""Plain reference of the architecture ``painn``, written from the
equations of PaiNN (Schütt, Unke and Gastegger, ICML 2021) in
straightforward ``jax.numpy``; it imports nothing of the program under
test, and lays vectors out atom-first, (A, 3, F), where the program puts
the spatial axis first.

* embedding s_i = E[Z_i] (F,), v_i = 0 (3, F);
* pairs i != j: r_ij = x_j - x_i, d_ij = |r_ij|, basis rbf_n(d) =
  sin(n pi d / r_c) / d for n = 1..n_rbf, cutoff f_c(d) = (cos(pi d /
  r_c) + 1) / 2 for d < r_c, else 0; filter W_ij = (rbf(d_ij) W_f + b_f)
  f_c(d_ij), of width 3F per block;
* message of block b: x_ij = phi_b(s_j) * W_ij[b] with phi = Dense(F, F),
  SiLU, Dense(F, 3F), split (x^s, x^vs, x^vv); s_i += sum_j x^s_ij;
  v_i += sum_j x^vv_ij v_j + x^vs_ij r_ij / d_ij;
* update of block b: Uv_i, Vv_i = v_i U, v_i V (no bias, per
  component); a = Dense(2F, F), SiLU, Dense(F, 3F) of [s_i, |Vv_i|],
  |.| = sqrt(sum over components + 1e-8), split (a^vv, a^sv, a^ss);
  v_i += a^vv Uv_i; s_i += a^ss + a^sv <Uv_i, Vv_i>;
* energy E = sum_i Dense(F/2, 1)(SiLU(Dense(F, F/2)(s_i))), forces
  F = -dE/dx.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _net(layers, h):
    """Dense, SiLU, Dense."""
    h = _silu(h @ layers[0]["w"] + layers[0]["b"])
    return h @ layers[1]["w"] + layers[1]["b"]


def energy(params, coords, cfg):
    """Energy of one (A, 3) structure for one member's params."""
    dt = coords.dtype
    a, f, r_cut = cfg["n_atoms"], cfg["features"], cfg["r_cut"]
    off = ~np.eye(a, dtype=bool)
    rij = coords[None, :, :] - coords[:, None, :]           # (A, A, 3)
    d = jnp.sqrt(jnp.sum(rij ** 2, axis=-1) + jnp.eye(a, dtype=dt))  # self: 1
    unit = rij / d[..., None]
    n = jnp.arange(1, cfg["n_rbf"] + 1, dtype=dt)
    rbf = jnp.sin(d[..., None] * n * np.pi / r_cut) / d[..., None]
    fc = jnp.where(off & (d < r_cut),
                   0.5 * (jnp.cos(np.pi * d / r_cut) + 1.0), 0.0)
    w_all = (rbf @ params["filter"]["w"] + params["filter"]["b"]) \
        * fc[..., None]                                      # (A, A, 9F)

    s = params["embed"][np.asarray(cfg["species"])].astype(dt)   # (A, F)
    v = jnp.zeros((a, 3, f), dt)                             # (A, 3, F)
    for b, blk in enumerate(params["blocks"]):
        w = w_all[..., 3 * f * b:3 * f * (b + 1)]
        x = _net(blk["phi"], s)[None, :, :] * w              # (A, A, 3F)
        xs, xvs, xvv = x[..., :f], x[..., f:2 * f], x[..., 2 * f:]
        ds = jnp.sum(xs, axis=1)
        dv = jnp.sum(xvv[:, :, None, :] * v[None, :, :, :]
                     + xvs[:, :, None, :] * unit[..., None], axis=1)
        s, v = s + ds, v + dv

        uv = v @ blk["U"]                                    # (A, 3, F)
        vv = v @ blk["V"]
        norm = jnp.sqrt(jnp.sum(vv ** 2, axis=1) + 1e-8)     # (A, F)
        m = _net(blk["mix"], jnp.concatenate([s, norm], axis=-1))
        avv, asv, ass = m[:, :f], m[:, f:2 * f], m[:, 2 * f:]
        v = v + avv[:, None, :] * uv
        s = s + ass + asv * jnp.sum(uv * vv, axis=1)
    return jnp.sum(_net(params["readout"], s))


def forces(params, flat, cfg):
    """(3A,) -> (3A,) forces of one member."""
    coords = flat.reshape(cfg["n_atoms"], 3)
    return -jax.grad(energy, argnums=1)(params, coords, cfg).reshape(-1)
