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

The filter is linear in the pair's basis: W_ij = sum_r Bas_ijr W_r, with
Bas_ijr = f_c(d_ij) [rbf_1..R(d_ij), 1], whose last channel carries the
bias.  So a message sum_j W_ij phi_j is computed as
sum_(j, r) Bas_ijr (W_r phi_j): each atom's phi_j scaled by the R + 1
channels' filter weights, (A, R + 1, F) a member, is contracted with the
basis over neighbours and channels at once, one matrix product A (R + 1)
deep.  The only pair-sized tensors are the (A, A, R + 1) basis and, for
the vector messages, r_hat times it, which depend on the coordinates
alone: the members of a committee scoring the same structures share them
and run the product together.  No (A, A, F) filter or message is
written, forward or in reverse, where autodiff's reverse of each product
is again a product, and the basis's cotangent is (A, A, R + 1) a member.

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


def _basis(coords, cfg: PaiNNConfig):
    """(A, 3) -> the pair basis Bas (A, A, R + 1): the sine radial basis
    sin(n pi d / r_c) / d, n = 1..R, and a constant channel, times the
    cosine cutoff of d_ij, which is zero on the self pair and beyond r_c;
    and C = r_hat Bas (3, A, A, R + 1), with r_hat the unit vector of
    r_ij = x_j - x_i."""
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
    bas = jnp.concatenate([rbf, jnp.ones_like(d)[..., None]],
                          axis=-1) * fcut[..., None]
    return bas, (r / d)[..., None] * bas


def _over_pairs(spec, basis, x):
    """sum over the neighbours j and the basis channels r of the pair
    basis times per-atom features: one matrix product, in float32 as the
    elementwise sums it replaces.  The basis keeps its (A, A, R + 1) shape
    in the product."""
    return jnp.einsum(spec, basis, x, precision=jax.lax.Precision.HIGHEST)


def _update(blk, s, v):
    """A block's update: each atom's scalars and vectors mixed through
    ||Vv|| and <Uv, Vv>."""
    uv = jnp.einsum("caf,fg->cag", v, blk["U"])
    vv = jnp.einsum("caf,fg->cag", v, blk["V"])
    vnorm = jnp.sqrt(jnp.sum(vv * vv, axis=0) + NORM_EPS)
    a = _mlp2(blk["mix"], jnp.concatenate([s, vnorm], axis=-1))
    a_vv, a_sv, a_ss = jnp.split(a, 3, axis=-1)
    return s + a_ss + a_sv * jnp.sum(uv * vv, axis=0), v + a_vv[None] * uv


def energy(params: Params, coords: jnp.ndarray, species: jnp.ndarray,
           cfg: PaiNNConfig):
    """(A, 3) coordinates and (A,) atomic numbers -> scalar energy."""
    f = cfg.features
    # the named scopes label the operations in a device profile
    with jax.named_scope("painn_basis"):
        bas, c = _basis(coords, cfg)
        # the filter layer and its bias, (R + 1, 3F) for each block
        w_all = jnp.concatenate([params["filter"]["w"],
                                 params["filter"]["b"][None]], axis=0)
    s = params["embed"][species]                     # (A, F)
    v = jnp.zeros((3,) + s.shape, s.dtype)           # (3, A, F)
    for b, blk in enumerate(params["blocks"]):
        with jax.named_scope("painn_message"):
            w_s, w_vs, w_vv = jnp.split(w_all[:, 3 * f * b:3 * f * (b + 1)],
                                        3, axis=-1)  # (R + 1, F) each
            p_s, p_vs, p_vv = jnp.split(_mlp2(blk["phi"], s), 3, axis=-1)
            # sum_j W_ij phi_j = sum_(j, r) Bas_ijr (W_r phi_j): phi scaled
            # by each channel's filter weights, (A, R + 1, F), contracted
            # with the basis over (j, r).  F stays a free axis of every
            # product, never a batch axis: that would let the compiler
            # move the spatial axis onto the minor tiles
            ds = _over_pairs("ijr,jrf->if", bas, p_s[:, None] * w_s)
            dv = _over_pairs("cijr,jrf->cif", c, p_vs[:, None] * w_vs)
            if b:   # v is zero in block 0, and so is its message
                dv = dv + _over_pairs("ijr,cjrf->cif", bas,
                                      (p_vv * v)[:, :, None] * w_vv)
            s, v = s + ds, v + dv
        with jax.named_scope("painn_update"):
            s, v = _update(blk, s, v)
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
