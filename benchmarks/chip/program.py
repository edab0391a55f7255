"""The system under test, built through the program's own entry points.

Everything here calls into ``repro``: ``PAL(...)`` from a
``PALRunConfig`` builds the acquisition engine, the walker fleet, the
exchange and the committee trainer; the member functions come from the
architecture the configuration names (``harness.arch``).  The benchmark
only supplies what a user supplies: the weights (made on the device from
the seed), the walkers' starting geometries, the oracle and the
per-member loss.
"""
from __future__ import annotations

import os
import tempfile

import jax
import numpy as np

import harness
import lattice


def base_geometry(cfg):
    g = cfg["geometry"]
    return lattice.lattice(g["lattice"], g["spacing"])


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
    forces, loss = harness.arch(cfg).program.member_functions(cfg)
    return PAL(run_cfg, make_generator=LatticeGenerator,
               make_oracle=LJOracle,
               committee=CommitteeSpec(forces, cparams),
               loss_fn=loss,
               fleet_init=fleet_init)
