"""Operations and bytes the algorithm needs, from a configuration's shapes.

Each matmul the algorithm needs counts once; recomputation does not count,
and neither do elementwise work, the exponentials of the descriptor or
the committee statistics.  One multiply-add is 2 FLOPs.  ``PERF.md``
derives every term.
"""
from __future__ import annotations


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


def uq_kernel_bytes(n_members: int, rows: int, d: int) -> float:
    """HBM bytes of one ``committee_uq`` call: the (K, n, d) float32
    predictions read once; the (n, d) mean and the (n, 128) statistics
    slab written once."""
    return 4.0 * (n_members * rows * d + rows * d + rows * 128)
