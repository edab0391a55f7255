"""Program side of the architecture ``mlp_potential``: a committee of
MLP potentials over a radial descriptor, run through the program's own
model code, ``repro.models.potential.energy_forces``.

Holds what depends on the architecture: the weights pytree made from the
seed, the member functions the program's committee is built from, and
the operations the algorithm needs per fleet and train step.  Its plain
reference is ``mlp_potential_ref.py`` beside it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def potential_config(cfg):
    from repro.configs.pal_potential import PotentialConfig

    return PotentialConfig(
        name=cfg["name"], n_atoms=cfg["n_atoms"],
        committee_size=cfg["committee_size"], hidden=tuple(cfg["hidden"]),
        n_rbf=cfg["n_rbf"], r_cut=cfg["r_cut"], dtype=cfg["dtype"])


def make_weights(cfg, seed: int):
    """Stacked (K, ...) member weights, float32, made on the device in one
    jitted call: w_i ~ N(0, 1) * w_scale / sqrt(fan_in), b_i ~ N(0, 1) *
    b_scale."""
    dims = [cfg["n_rbf"], *cfg["hidden"], 1]
    k = cfg["committee_size"]
    ws, bs = cfg["weights"]["w_scale"], cfg["weights"]["b_scale"]

    @jax.jit
    def init(key):
        out = {}
        keys = jax.random.split(key, 2 * (len(dims) - 1))
        for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
            out[f"w{i}"] = jax.random.normal(keys[2 * i], (k, a, b)) \
                * (ws / np.sqrt(a))
            out[f"b{i}"] = jax.random.normal(keys[2 * i + 1], (k, b)) * bs
        return out

    return init(jax.random.PRNGKey(seed))


def member_functions(cfg):
    """(member_forces, member_force_loss) over the program's model code."""
    from repro.models import potential as pot

    pcfg = potential_config(cfg)
    n_atoms = pcfg.n_atoms

    def member_forces(p, flat_batch):              # (n, 3A) -> (n, 3A)
        def one(flat):
            _, f = pot.energy_forces(p, flat.reshape(n_atoms, 3), pcfg)
            return f.reshape(-1)
        return jax.vmap(one)(flat_batch)

    def member_force_loss(p, batch):
        pred = member_forces(p, batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2), {}

    return member_forces, member_force_loss


# ------------------------------------------------------------------ flops
# Each matmul the algorithm needs counts once; recomputation does not
# count, and neither do elementwise work or the exponentials of the
# descriptor.  One multiply-add is 2 FLOPs.  ``PERF.md`` derives every
# term.
def layer_macs(cfg):
    """Multiply-adds of each MLP layer for one atom: d_in * d_out."""
    dims = [cfg["n_rbf"], *cfg["hidden"], 1]
    return [a * b for a, b in zip(dims[:-1], dims[1:])]


def pair_macs(cfg):
    """Multiply-adds of the chain rule from descriptor to pair distances
    for one structure: sum over r of dE/dG_ir * dG_ir/dd_ij for every
    ordered pair (i, j)."""
    a = cfg["n_atoms"]
    return a * a * cfg["n_rbf"]


def fleet_step_flops(cfg, n_walkers: int) -> float:
    """One fused fleet step: for each member and walker, the forward
    energy (one matmul per layer and atom), the input gradient back
    through every layer (one more) and the descriptor chain rule."""
    per_structure = 2 * sum(layer_macs(cfg)) * cfg["n_atoms"] \
        + pair_macs(cfg)
    return 2.0 * cfg["committee_size"] * n_walkers * per_structure


def train_step_flops(cfg, batch: int) -> float:
    """One fused train step of all K members on B structures each: the
    force needs the forward and the input-gradient pass (2 per layer);
    the parameter gradient of the force loss differentiates both: a
    weight gradient of each (2 per layer), the cotangent back through
    the input-gradient chain (1 per layer) and through the forward chain
    above the first layer (1 per layer but the first).  The descriptor
    chain rule runs once forward and once in reverse."""
    macs = layer_macs(cfg)
    per_atom = 5 * sum(macs) + sum(macs[1:])
    per_structure = per_atom * cfg["n_atoms"] + 2 * pair_macs(cfg)
    return 2.0 * cfg["committee_size"] * batch * per_structure
