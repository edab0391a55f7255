"""The system under test, built through the program's own entry points.

Everything here calls into ``repro``: ``PAL(...)`` from a
``PALRunConfig`` builds the acquisition engine, the walker fleet, the
exchange and the committee trainer; the member forward is
``repro.models.potential.energy_forces``.  The benchmark only supplies
what a user supplies: the weights (made on the device from the seed),
the walkers' starting geometries, the oracle and the per-member loss.
"""
from __future__ import annotations

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

import lattice


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


def base_geometry(cfg):
    g = cfg["geometry"]
    return lattice.lattice(g["lattice"], g["spacing"])


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


def build_pal(cfg, traffic, seed: int, cparams, *, impl: str = "pallas",
              fleet_init=None):
    """``PAL(...)`` for one cell; nothing is started, no thread runs.
    The fused committee trainer is always built, as in a campaign (PAL
    needs it or per-member models)."""
    from repro.configs.pal_potential import PALRunConfig
    from repro.core import PAL, CommitteeSpec, UserGene, UserOracle

    base = base_geometry(cfg)
    perturb = cfg["geometry"]["perturb"]
    n_atoms = cfg["n_atoms"]

    class LatticeGenerator(UserGene):
        """A walker's trusted starting geometry."""

        def __init__(self, rank, result_dir):
            super().__init__(rank, result_dir)
            self.x0 = lattice.geometries(
                np.random.RandomState(seed + rank), 1, base, perturb)[0]

        def generate_new_data(self, data_to_gene):
            return False, self.x0

    class LJOracle(UserOracle):
        """Lennard-Jones forces: the ab initio stand-in."""

        def __init__(self, rank, result_dir):
            super().__init__(rank, result_dir)
            self._f = jax.jit(lambda x: lattice.lj_forces(x, n_atoms))

        def run_calc(self, input_for_orcl):
            return input_for_orcl, np.asarray(self._f(input_for_orcl))

    t = traffic
    run_cfg = PALRunConfig(
        result_dir=os.path.join(tempfile.gettempdir(), "pal-chip-bench"),
        uq_impl=impl, seed=seed, uq_mesh=t.get("uq_mesh", ""),
        std_threshold=t.get("std_threshold", 0.05),
        fleet_walkers=t.get("walkers", 0),
        fleet_sampler=t.get("sampler", "euler"),
        fleet_dt=t.get("dt", 0.002), fleet_noise=t.get("noise", 0.01),
        fleet_clip=t.get("clip", 20.0), patience=t.get("patience", 5),
        oracle_budget=t.get("oracle_budget", 0.0),
        exchange_min_interval=t.get("exchange_min_interval", 0.005),
        train_steps=t.get("train_steps", 200),
        train_batch=t.get("batch", 32), train_lr=t.get("lr", 1e-3),
        train_bootstrap=t.get("bootstrap", True),
        train_replay_capacity=t.get("replay_rows", 2048),
        train_memory_policy=t.get("memory_policy", "fp32"))
    forces, loss = member_functions(cfg)
    return PAL(run_cfg, make_generator=LatticeGenerator,
               make_oracle=LJOracle,
               committee=CommitteeSpec(forces, cparams),
               loss_fn=loss,
               fleet_init=fleet_init)
