"""PAL quickstart — the paper's workflow in ~100 lines (photodynamics-style,
§3.1): a committee of MLP potentials drives parallel MD-like generators;
uncertain geometries go to an analytic 'DFT' oracle; the fused committee
trainer continuously refits; weights flow back to the prediction committee.
Patience policy included (§2.2).

Prediction runs on the unified acquisition engine: a ``CommitteeSpec``
hands PAL the per-member forward + stacked params, and the committee
forward, uncertainty statistics, and selection rules execute as ONE fused
device dispatch per exchange iteration (``PALRunConfig.uq_impl``).

Training is the same story: ``loss_fn=`` turns on the shared
``training/committee_trainer.CommitteeTrainer`` — all K members advance in
one vmapped dispatch per step on per-member bootstrap minibatches drawn
from a device-resident replay ring, and refreshed weights hand off to the
engine device-to-device (no hand-rolled retrain loop, no packed host
round trip).

  PYTHONPATH=src python examples/quickstart.py [--timeout 45]
"""
import argparse
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.configs.pal_potential import PALRunConfig, PotentialConfig
from repro.core import PAL, CommitteeSpec, UserGene, UserOracle
from repro.core import committee as cmte
from repro.launch.platform import enable_compile_cache
from repro.models import potential as pot

PCFG = PotentialConfig(n_atoms=6, committee_size=4, hidden=(64, 64), n_rbf=24)


class MDGenerator(UserGene):
    """One MD trajectory: Euler steps on committee-mean forces; restarts to
    the last trusted geometry when the controller flags high uncertainty
    past patience (it then receives data_to_gene=None)."""

    def __init__(self, rank, result_dir):
        super().__init__(rank, result_dir)
        rng = np.random.RandomState(rank)
        lattice = np.stack(np.meshgrid([0, 1.3], [0, 1.3], [0, 1.3]),
                           -1).reshape(-1, 3)[:PCFG.n_atoms]
        self.x0 = (lattice + rng.randn(PCFG.n_atoms, 3) * 0.05).astype(
            np.float32)
        self.x = self.x0.copy()
        self.rng = rng
        self.steps = 0
        self.restarts = 0

    def generate_new_data(self, data_to_gene):
        self.steps += 1
        if self.steps > 200_000:        # effectively timeout-bounded
            return True, self.x.reshape(-1)
        if data_to_gene is None and self.steps > 1:
            self.x = self.x0.copy()              # patience exceeded: restart
            self.restarts += 1
        elif data_to_gene is not None:
            forces = np.clip(data_to_gene.reshape(PCFG.n_atoms, 3), -20, 20)
            self.x = self.x + 0.002 * forces \
                + self.rng.randn(*self.x.shape).astype(np.float32) * 0.01
        return False, self.x.reshape(-1).astype(np.float32)


class LJOracle(UserOracle):
    """Analytic Lennard-Jones cluster = the 'DFT' ground truth stand-in."""

    def __init__(self, rank, result_dir):
        super().__init__(rank, result_dir)
        # jit once: unjitted op-by-op dispatch starves behind the busy
        # exchange/training threads on the single host device
        self._ef = jax.jit(pot.lj_energy_forces)

    def run_calc(self, input_for_orcl):
        coords = jnp.asarray(input_for_orcl.reshape(PCFG.n_atoms, 3))
        _, f = self._ef(coords)
        return input_for_orcl, np.asarray(f).reshape(-1).astype(np.float32)


def member_forces(p, flat_batch):                # (n, 3A) -> (n, 3A)
    """ONE committee member's force field over a batch of flat coords —
    the apply_fn of the CommitteeSpec AND the forward inside the loss."""
    def one(flat):
        _, f = pot.energy_forces(p, flat.reshape(PCFG.n_atoms, 3), PCFG)
        return f.reshape(-1)
    return jax.vmap(one)(flat_batch)


def member_force_loss(p, batch):
    """Per-member training loss for the fused committee trainer: MSE on
    oracle forces over the minibatch ``{"x": coords, "y": forces}``."""
    pred = member_forces(p, batch["x"])
    return jnp.mean((pred - batch["y"]) ** 2), {}


def make_committee_spec(n_members: int, seed_offset: int = 0
                        ) -> CommitteeSpec:
    """Fused-engine committee: per-member force field over flat coords."""
    cparams = cmte.stack_members([
        pot.init(PCFG, jax.random.PRNGKey(i + seed_offset))
        for i in range(n_members)])
    return CommitteeSpec(member_forces, cparams)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeout", type=float, default=45.0,
                    help="run budget in seconds (CI smoke uses a short one)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    cfg = PALRunConfig(
        result_dir=tempfile.mkdtemp(prefix="pal_quickstart_"),
        gene_process=8, orcl_process=4, pred_process=4, ml_process=4,
        retrain_size=16, std_threshold=0.25, patience=5,
        weight_sync_every=1, checkpoint_every=10.0,
        train_steps=400, train_batch=64, train_lr=1e-3)
    pal = PAL(cfg, make_generator=MDGenerator, make_oracle=LJOracle,
              committee=make_committee_spec(PCFG.committee_size),
              loss_fn=member_force_loss)
    print("running PAL (8 MD generators, 4-NN committee, 4 LJ oracles, "
          f"fused acquisition engine uq_impl={cfg.uq_impl}, "
          "fused committee trainer)...")
    token = pal.run(timeout=args.timeout)
    rep = pal.report()
    print(f"stopped by: {token}")
    print(f"exchange iterations : {rep['counters'].get('exchange.iterations')}")
    print(f"labeled by oracle   : {rep['labeled_total']}")
    print(f"retrain rounds      : {rep['counters'].get('train.retrains')}")
    print(f"fused train steps   : {rep['train_fused_steps']}")
    print(f"device weight hands : {rep['device_weight_refreshes']} "
          f"(packed host bytes: {pal.engine.refresh_host_bytes})")
    print(f"generator restarts  : "
          f"{sum(g.restarts for g in pal.generators)}")
    print(f"AL checkpoints      : {pal.checkpointer.saves}")
    assert rep["labeled_total"] > 0 and rep["device_weight_refreshes"] > 0
    assert pal.engine.refresh_host_bytes == 0
    print("OK")


if __name__ == "__main__":
    main()
