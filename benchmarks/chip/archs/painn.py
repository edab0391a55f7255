"""Program side of the architecture ``painn``: a committee of PaiNN
equivariant message-passing potentials, run through the program's own
model code, ``repro.models.painn.energy_forces``.

Holds what depends on the architecture: the weights pytree made from the
seed, the member functions the program's committee is built from, and
the operations the algorithm needs per fleet and train step and in the
pair part of the fleet step.  Its plain reference is ``painn_ref.py``
beside it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def painn_config(cfg):
    from repro.models.painn import PaiNNConfig

    return PaiNNConfig(features=cfg["features"], n_blocks=cfg["n_blocks"],
                       n_rbf=cfg["n_rbf"], r_cut=cfg["r_cut"],
                       max_z=cfg["max_z"])


def make_weights(cfg, seed: int):
    """Stacked (K, ...) member weights, float32, made on the device in one
    jitted call: a matrix ~ N(0, 1) * scale / sqrt(fan_in), with
    ``filter_scale`` for the filter layer and ``w_scale`` for the others;
    a bias ~ N(0, 1) * b_scale; the embedding ~ N(0, 1) * embed_scale."""
    from repro.models.painn import param_shapes

    k = cfg["committee_size"]
    sc = cfg["weights"]
    shapes = param_shapes(painn_config(cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths, leaves = zip(*flat)

    def scale(path, shape):
        names = [getattr(p, "key", None) for p in path]
        if names[0] == "embed":
            return sc["embed_scale"]
        if names[-1] == "b":
            return sc["b_scale"]
        w = sc["filter_scale"] if names[0] == "filter" else sc["w_scale"]
        return w / np.sqrt(shape[0])

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            jax.random.normal(kk, (k,) + shape) * scale(path, shape)
            for kk, path, shape in zip(keys, paths, leaves)])

    return init(jax.random.PRNGKey(seed))


def member_functions(cfg):
    """(member_forces, member_force_loss) over the program's model code."""
    from repro.models import painn

    pcfg = painn_config(cfg)
    n_atoms = cfg["n_atoms"]
    species = jnp.asarray(cfg["species"], jnp.int32)

    def member_forces(p, flat_batch):              # (n, 3A) -> (n, 3A)
        def one(flat):
            _, f = painn.energy_forces(p, flat.reshape(n_atoms, 3), species,
                                       pcfg)
            return f.reshape(-1)
        return jax.vmap(one)(flat_batch)

    def member_force_loss(p, batch):
        pred = member_forces(p, batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2), {}

    return member_forces, member_force_loss


# ------------------------------------------------------------------ flops
# A matmul counts once, as its multiply-adds; a multiply-add is 2 FLOPs,
# a lone multiply or add 1.  Recomputation does not count, and neither
# do the activations, norms, the basis's sines or the geometry.
# ``PERF.md`` derives every term.  Per member and structure, with A
# atoms, F features, B blocks and R radial functions:
def dense_macs(cfg):
    """Multiply-adds of the atomwise dense layers for one structure:
    per block phi (F.F + F.3F), U and V on three components (2.3.F.F)
    and the update net (2F.F + F.3F); the readout (F.F/2 + F/2)."""
    a, f = cfg["n_atoms"], cfg["features"]
    block = f * f + f * 3 * f + 6 * f * f + 2 * f * f + f * 3 * f
    return a * (cfg["n_blocks"] * block + f * (f // 2) + f // 2)


def filter_macs(cfg):
    """Multiply-adds of the filter layer over the A.A pairs: R -> 3F.B."""
    return cfg["n_atoms"] ** 2 * cfg["n_rbf"] * 3 * cfg["features"] \
        * cfg["n_blocks"]


def pair_step_flops(cfg, n_walkers: int) -> float:
    """The pair part of one fused fleet step, all K members on
    ``n_walkers`` structures: the filter layer forward and its input
    gradient (2 x 2 FLOPs per multiply-add), the cutoff's product and its
    two cotangents (1 + 1 + 2 per filter value), and per block the
    message, 16 FLOPs per pair and feature forward (phi_j W_ij: 3; the
    scalar sum: 1; the two vector sums over 3 components: 6 + 6), and 33
    in reverse (the sums' cotangents of both operands, 4 x 6; the
    product's: 6 + 3)."""
    a, f, nb = cfg["n_atoms"], cfg["features"], cfg["n_blocks"]
    per_structure = 4 * filter_macs(cfg) + 4 * a * a * 3 * f * nb \
        + (16 + 33) * a * a * f * nb
    return float(cfg["committee_size"] * n_walkers * per_structure)


def fleet_step_flops(cfg, n_walkers: int) -> float:
    """One fused fleet step: the pair part, and every atomwise dense
    layer forward and its input gradient."""
    dense = 2 * 2 * dense_macs(cfg)
    return pair_step_flops(cfg, n_walkers) \
        + float(cfg["committee_size"] * n_walkers * dense)


def train_step_flops(cfg, batch: int) -> float:
    """One fused train step of all K members on B structures each: the
    force (the fleet step's work), then the parameter gradient of the
    force loss through it.  A dense layer then adds the weight gradient
    of its forward and of its input gradient (2), and the cotangents back
    through both chains (2), but the filter layer, whose input is the
    basis that no parameter reaches, only 3; the pair terms of the force,
    forward and reverse, are differentiated once more at twice their
    cost."""
    a, f, nb = cfg["n_atoms"], cfg["features"], cfg["n_blocks"]
    dense = 2 * (6 * dense_macs(cfg) + 5 * filter_macs(cfg))
    pairs = 3 * (4 * a * a * 3 * f * nb + (16 + 33) * a * a * f * nb)
    return float(cfg["committee_size"] * batch * (dense + pairs))
