"""PaiNN: polarizable atom interaction neural network (Schütt, Unke and
Gastegger, ICML 2021, arXiv:2102.03150), an equivariant message-passing
interatomic potential, as a committee member.

Each atom carries scalar features s (A, F) and equivariant vector
features v (3, A, F); the spatial axis leads, so that the feature axis F
and the atom axis are the two minor dimensions of every vector buffer.
Per interaction block, a message over all ordered atom pairs within the
cutoff (one learned filter per pair from a sine radial basis) and an
update that mixes each atom's s and v; the energy is an atomwise readout
of s, and forces are -dE/dx by reverse mode through every block.

The pair set is dense (A, A) with a cutoff mask: on the small, dense
structures this is run on, nearly every pair lies within the cutoff.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
NORM_EPS = 1e-8                 # under the square root of ||Vv||, as in
                                # SchNetPack: a finite gradient at v = 0


@dataclass(frozen=True)
class PaiNNConfig:
    features: int = 128         # F, scalar and vector feature width
    n_blocks: int = 3           # interaction blocks (message + update)
    n_rbf: int = 20             # sine radial basis functions
    r_cut: float = 5.0          # cosine cutoff radius (Å)
    max_z: int = 10             # embedding rows: atomic numbers below it


def _dense(p, h):
    return h @ p["w"] + p["b"]


def _mlp2(p, h):
    """Dense -> SiLU -> Dense, the two-layer nets of PaiNN."""
    return _dense(p[1], jax.nn.silu(_dense(p[0], h)))


def _geometry(coords, cfg: PaiNNConfig):
    """(A, 3) -> unit vectors r_hat (3, A, A) of r_ij = x_j - x_i, the
    radial basis sin(n pi d / r_c) / d (A, A, n_rbf), n = 1..n_rbf, and
    the cosine cutoff (A, A), zero on the self pair and beyond r_c."""
    a = coords.shape[0]
    xyz = coords.T                                   # (3, A)
    r = xyz[:, None, :] - xyz[:, :, None]            # r[:, i, j] = x_j - x_i
    self_pair = jnp.eye(a, dtype=bool)
    # the self pair's distance is set to 1 so that no 1/0 enters the
    # basis or its gradient; its cutoff is 0
    d = jnp.sqrt(jnp.where(self_pair, 1.0, jnp.sum(r * r, axis=0)))
    n = jnp.arange(1, cfg.n_rbf + 1, dtype=d.dtype)
    rbf = jnp.sin(d[..., None] * (n * jnp.pi / cfg.r_cut)) / d[..., None]
    inside = ~self_pair & (d < cfg.r_cut)
    fcut = jnp.where(inside, 0.5 * (jnp.cos(jnp.pi * d / cfg.r_cut) + 1.0),
                     0.0)
    return r / d, rbf, fcut


def energy(params: Params, coords: jnp.ndarray, species: jnp.ndarray,
           cfg: PaiNNConfig):
    """(A, 3) coordinates and (A,) atomic numbers -> scalar energy."""
    f = cfg.features
    # the named scopes label the operations in a device profile
    with jax.named_scope("painn_filter"):
        r_hat, rbf, fcut = _geometry(coords, cfg)
        # one filter per pair for all blocks, each block reading its own
        # 3F slice: W_ij = (rbf(d_ij) W_f + b_f) f_c(d_ij)
        filt = _dense(params["filter"], rbf) * fcut[..., None]
    s = params["embed"][species]                     # (A, F)
    v = jnp.zeros((3,) + s.shape, s.dtype)           # (3, A, F)
    for b, blk in enumerate(params["blocks"]):
        with jax.named_scope("painn_message"):
            x = _mlp2(blk["phi"], s)[None, :, :] \
                * filt[:, :, 3 * f * b:3 * f * (b + 1)]   # (A_i, A_j, 3F)
            x_s, x_vs, x_vv = jnp.split(x, 3, axis=-1)
            s = s + jnp.sum(x_s, axis=1)
            # products and sums over j, not dots: a dot with the feature
            # axis as its batch axis would let the compiler move the
            # spatial axis onto the minor tiles
            v = v + jnp.sum(x_vv * v[:, None] + x_vs * r_hat[..., None],
                            axis=2)
        with jax.named_scope("painn_update"):
            uv = jnp.einsum("caf,fg->cag", v, blk["U"])
            vv = jnp.einsum("caf,fg->cag", v, blk["V"])
            vnorm = jnp.sqrt(jnp.sum(vv * vv, axis=0) + NORM_EPS)
            a = _mlp2(blk["mix"], jnp.concatenate([s, vnorm], axis=-1))
            a_vv, a_sv, a_ss = jnp.split(a, 3, axis=-1)
            v = v + a_vv[None] * uv
            s = s + a_ss + a_sv * jnp.sum(uv * vv, axis=0)
    with jax.named_scope("painn_readout"):
        return jnp.sum(_mlp2(params["readout"], s))


def energy_forces(params: Params, coords: jnp.ndarray, species: jnp.ndarray,
                  cfg: PaiNNConfig):
    """Energy and forces -dE/dx (A, 3)."""
    e, g = jax.value_and_grad(energy, argnums=1)(params, coords, species,
                                                 cfg)
    return e, -g


def param_shapes(cfg: PaiNNConfig) -> Params:
    """The shape of every parameter of one member: the embedding, the
    shared filter layer, per block the message net phi (F -> F -> 3F), U
    and V (F -> F, no bias) and the update net (2F -> F -> 3F), and the
    readout (F -> F/2 -> 1)."""
    f = cfg.features

    def dense(a, b):
        return {"w": (a, b), "b": (b,)}

    block = {"phi": [dense(f, f), dense(f, 3 * f)], "U": (f, f),
             "V": (f, f), "mix": [dense(2 * f, f), dense(f, 3 * f)]}
    return {"embed": (cfg.max_z, f),
            "filter": dense(cfg.n_rbf, 3 * f * cfg.n_blocks),
            "blocks": [block] * cfg.n_blocks,
            "readout": [dense(f, f // 2), dense(f // 2, 1)]}
