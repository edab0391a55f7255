#!/usr/bin/env python3
"""Chip smoke test: the PAL campaign loop on a TPU, end to end.

Everything runs in ONE process (a chip belongs to one process at a time).

Default (one chip):

1. Device check: exits non-zero unless ``jax.devices()[0].platform`` is
   ``"tpu"``. There is no CPU or interpret-mode fallback.
2. Campaign: ``PAL(...)`` on the published potential configuration,
   ``PotentialConfig()`` (K=4 members, hidden (128, 128), 32 radial
   features, 8 atoms), with the Pallas UQ kernel (``uq_impl='pallas'``),
   the fused committee trainer, a device-resident fleet of
   ``N_WALKERS`` walkers, queue-batched serving and Lennard-Jones
   oracles.  It runs ``RUN_SECONDS`` while a client thread submits served
   requests, checkpointing the fleet carry, the trainer state and the
   replay ring along the way.  Every counter of the main path must move,
   and no loop may crash or escalate.
3. Correctness on a seeded batch:
   - the ``pallas`` and ``xla`` statistics, computed on the chip from the
     SAME chip predictions, against ``ref.committee_uq_ref`` run on the
     host CPU over those predictions (``UQ_TOL``);
   - the chip forward against a float32 host forward, and the fused
     ``pallas``/``xla`` engines against the reference statistics of that
     host forward (``FWD_TOL``: TPU float32 matmuls use reduced precision
     by default).
   The mask may differ from the reference only on rows whose reference
   ``scalar_std`` lies within the tolerance of the threshold.

``--chips 4`` runs only the mesh phase: fused scoring on a (4, 1) data
mesh and a (1, 4) committee mesh, and one ``CommitteeTrainer`` step on
(1, 4), each against the one-device result (``MESH_TOL``).

Weights are random, made from ``SEED``.  The last line of standard output
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
and is printed only when every check passed; a summary of the measured
numbers goes to ``chiprun_out/chip_smoke.json``.

Usage:  python3 chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

SEED = 0
N_WALKERS = 256          # exploration fleet size (one shape bucket)
RUN_SECONDS = 90.0       # campaign window, compiles included
SERVE_REQUESTS = 32      # client requests of SERVE_ROWS rows each
SERVE_ROWS = 4
ORACLE_BUDGET = 0.02     # selected fraction per round: random members
                         # disagree everywhere, so a static threshold
                         # would send every walker to the oracles
N_CHECK = 256            # rows of the seeded correctness batch
# tolerances, each relative to the largest |value| of the reference
UQ_TOL = 1e-5            # same predictions: kernel vs reference arithmetic
FWD_TOL = 2e-2           # chip forward (reduced-precision matmuls) vs host
MESH_TOL = 1e-4          # sharded vs one-device program on the chip


def device_or_exit():
    """The TPU this run is for, or a non-zero exit: never a fallback."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform={dev.platform!r}",
              file=sys.stderr)
        sys.exit(2)
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"device: kind={info['kind']} count={info['count']}", flush=True)
    return info


# ---------------------------------------------------------------- the model
def potential():
    """Member forward, per-member loss, the oracle and the geometry sampler
    of the published potential configuration."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.pal_potential import PotentialConfig
    from repro.core import UserGene, UserOracle
    from repro.models import potential as pot

    cfg = PotentialConfig()
    lattice = np.stack(np.meshgrid([0, 1.3], [0, 1.3], [0, 1.3]),
                       -1).reshape(-1, 3)[:cfg.n_atoms]

    def geometries(rng, n):
        """(n, 3A) perturbed-lattice geometries."""
        x = lattice[None] + rng.randn(n, cfg.n_atoms, 3) * 0.05
        return x.reshape(n, -1).astype(np.float32)

    def member_forces(p, flat_batch):           # (n, 3A) -> (n, 3A)
        def one(flat):
            _, f = pot.energy_forces(p, flat.reshape(cfg.n_atoms, 3), cfg)
            return f.reshape(-1)
        return jax.vmap(one)(flat_batch)

    def member_force_loss(p, batch):
        pred = member_forces(p, batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2), {}

    class LatticeGenerator(UserGene):
        """A walker's trusted starting geometry."""

        def __init__(self, rank, result_dir):
            super().__init__(rank, result_dir)
            self.x0 = geometries(np.random.RandomState(SEED + rank), 1)[0]

        def generate_new_data(self, data_to_gene):
            return False, self.x0

    class LJOracle(UserOracle):
        """Lennard-Jones forces: the ab initio stand-in."""

        def __init__(self, rank, result_dir):
            super().__init__(rank, result_dir)
            self._ef = jax.jit(pot.lj_energy_forces)

        def run_calc(self, input_for_orcl):
            coords = jnp.asarray(input_for_orcl.reshape(cfg.n_atoms, 3))
            _, f = self._ef(coords)
            return input_for_orcl, np.asarray(f).reshape(-1)

    def committee(seed):
        return pot.init_committee(cfg, jax.random.PRNGKey(seed))

    return dict(cfg=cfg, geometries=geometries, member_forces=member_forces,
                loss=member_force_loss, generator=LatticeGenerator,
                oracle=LJOracle, committee=committee)


def close(got, want, tol):
    """Largest |got - want| relative to the largest |want|, and whether it
    is within ``tol``."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    err = float(np.max(np.abs(got - want))) / scale
    return err, err <= tol


def compare_uq(got, ref_out, threshold, tol, scale):
    """(mean, scalar_std, component_std) within ``tol`` of the reference;
    the mask equal except on rows within ``tol * scale`` of the
    threshold.  Returns (errors, ok)."""
    import numpy as np

    mean, sstd, cstd, mask = (np.asarray(o) for o in got[:4])
    r_mean, r_sstd, r_cstd, r_mask = (np.asarray(o) for o in ref_out[:4])
    errs = {}
    ok = True
    for name, a, b in (("mean", mean, r_mean), ("scalar_std", sstd, r_sstd),
                       ("component_std", cstd, r_cstd)):
        err = float(np.max(np.abs(a.astype(np.float64) - b))) / scale
        errs[name] = err
        ok &= err <= tol
    near = np.abs(r_sstd - threshold) <= tol * scale
    flips = (mask.astype(bool) != r_mask.astype(bool))
    errs["mask_flips"] = int(flips.sum())
    errs["mask_flips_off_threshold"] = int((flips & ~near).sum())
    ok &= errs["mask_flips_off_threshold"] == 0
    return errs, bool(ok)


# ----------------------------------------------------------- campaign phase
def campaign_phase(m, *, impl="pallas", n_walkers=N_WALKERS,
                   seconds=RUN_SECONDS, result_dir=None):
    """PAL at full width through its normal entry point; returns a summary
    and raises AssertionError on any failed check."""
    import numpy as np

    from repro.configs.pal_potential import PALRunConfig
    from repro.core import PAL, CommitteeSpec

    result_dir = result_dir or os.path.join(HERE, "results", "chip_smoke")
    shutil.rmtree(result_dir, ignore_errors=True)
    cfg = PALRunConfig(
        result_dir=result_dir, uq_impl=impl, seed=SEED,
        orcl_process=4, retrain_size=20,
        fleet_walkers=n_walkers, oracle_budget=ORACLE_BUDGET,
        serve_uq=True, serve_max_batch=64,
        checkpoint_every_iters=200)
    pal = PAL(cfg, make_generator=m["generator"], make_oracle=m["oracle"],
              committee=CommitteeSpec(m["member_forces"],
                                      m["committee"](SEED)),
              loss_fn=m["loss"])

    served, errors = [], []

    def client():
        rng = np.random.RandomState(SEED + 7)
        for _ in range(SERVE_REQUESTS):
            rows = m["geometries"](rng, SERVE_ROWS)
            try:
                mean, _ = pal.serve_queue.submit(
                    list(rows), client="smoke").result(timeout=seconds)
                served.append(np.asarray(mean))
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(repr(e))
            time.sleep(seconds / (2 * SERVE_REQUESTS))

    t0 = time.perf_counter()
    th = threading.Thread(target=client, name="smoke-client", daemon=True)
    th.start()
    token = pal.run(timeout=seconds)
    th.join(timeout=30.0)
    wall = time.perf_counter() - t0
    rep = pal.report()
    c = rep["counters"]
    predict = rep["timers"].get("exchange.predict", {})
    summary = {
        "wall_s": wall,
        "stop": rep["stop"],
        "labeled_total": rep["labeled_total"],
        "device_weight_refreshes": rep["device_weight_refreshes"],
        "train_fused_steps": rep["train_fused_steps"],
        "fleet_steps": rep["fleet"]["steps"],
        "fleet_nan_resets": rep["fleet"]["nan_resets"],
        "served_answers": len(served),
        "serve_errors": errors,
        "serve_queue_dispatches": rep["serve_queue_dispatches"],
        "checkpoints": pal.checkpointer.saves,
        "refresh_host_bytes": pal.engine.refresh_host_bytes,
        "thread_crashes": c.get("runtime.thread_crashes", 0),
        "escalations": c.get("supervisor.escalations", 0),
        "exchange_predict_timer": predict,
        "oracle_rate": rep["oracle_rate"],
    }
    print("campaign:", json.dumps(summary, default=str), flush=True)
    finite = all(a.shape == (SERVE_ROWS, 3 * m["cfg"].n_atoms)
                 and np.isfinite(a).all() for a in served)
    checks = {
        "labeled_total > 0": summary["labeled_total"] > 0,
        "device_weight_refreshes > 0":
            summary["device_weight_refreshes"] > 0,
        "train_fused_steps > 0": summary["train_fused_steps"] > 0,
        "fleet_steps > 0": summary["fleet_steps"] > 0,
        "served answers > 0, finite, of the right shape":
            len(served) > 0 and finite and not errors,
        "checkpoints > 0": summary["checkpoints"] > 0,
        "refresh_host_bytes == 0": summary["refresh_host_bytes"] == 0,
        "thread_crashes == 0": summary["thread_crashes"] == 0,
        "escalations == 0": summary["escalations"] == 0,
        "stopped by the run timeout":
            (token.origin, token.reason) == ("runtime", "timeout"),
    }
    summary["checks"] = checks
    failed = [k for k, v in checks.items() if not v]
    assert not failed, f"campaign checks failed: {failed}"
    return summary


# -------------------------------------------------------- correctness phase
def correctness_phase(m, impls=("pallas", "xla")):
    """Chip statistics and forward against host float32 references."""
    import jax
    import numpy as np

    from repro.core import acquisition as acq
    from repro.core.committee import make_committee_apply
    from repro.kernels import ops, ref

    cpu = jax.devices("cpu")[0]
    cparams = m["committee"](SEED + 1)
    x = m["geometries"](np.random.RandomState(SEED + 2), N_CHECK)
    apply = jax.jit(make_committee_apply(m["member_forces"]))
    ref_uq = jax.jit(ref.committee_uq_ref, static_argnums=1)

    preds = apply(cparams, x)                                  # chip
    preds_h = np.asarray(preds)
    with jax.default_device(cpu):
        host_preds = np.asarray(apply(jax.device_put(cparams, cpu), x))
        # threshold at the median disagreement: both mask classes occur
        r_same = [np.asarray(o) for o in ref_uq(preds_h, 0.0)]
        threshold = float(np.median(r_same[1]))
        r_same = [np.asarray(o) for o in ref_uq(preds_h, threshold)]
        r_host = [np.asarray(o) for o in ref_uq(host_preds, threshold)]

    out = {"threshold": threshold}
    fwd_err, fwd_ok = close(preds_h, host_preds, FWD_TOL)
    out["forward_vs_host_f32"] = fwd_err
    ok = fwd_ok
    scale_same = float(np.max(np.abs(preds_h)))
    scale_host = float(np.max(np.abs(host_preds)))
    for impl in impls:
        kern = jax.jit(lambda p, impl=impl: ops.committee_uq(
            p, threshold, impl=impl))
        errs, k_ok = compare_uq(kern(preds), r_same, threshold, UQ_TOL,
                                scale_same)
        out[f"{impl}_kernel_vs_ref"] = errs
        eng = acq.FusedEngine(m["member_forces"], cparams, threshold,
                              impl=impl)
        t0 = time.perf_counter()
        eng.score(x, advance=False)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        uq = eng.score(x, advance=False)
        again = time.perf_counter() - t0
        errs, e_ok = compare_uq(
            (uq.mean, uq.scalar_std, uq.component_std, uq.mask), r_host,
            threshold, FWD_TOL, scale_host)
        out[f"{impl}_engine_vs_host_ref"] = errs
        out[f"{impl}_engine_first_call_s"] = first
        out[f"{impl}_engine_second_call_s"] = again
        ok &= k_ok and e_ok
    print("correctness:", json.dumps(out), flush=True)
    assert ok, f"correctness checks failed: {out}"
    return out


# ---------------------------------------------------------------- mesh phase
def mesh_phase(m, impl="pallas"):
    """Fused scoring on (4, 1) and (1, 4) meshes and one committee-trainer
    step on (1, 4), each against the same program on one device."""
    import jax
    import numpy as np

    from repro.core import acquisition as acq
    from repro.launch.mesh import make_scaleout_mesh
    from repro.models import potential as pot
    from repro.training.committee_trainer import CommitteeTrainer

    cparams = m["committee"](SEED + 1)
    x = m["geometries"](np.random.RandomState(SEED + 2), N_CHECK)
    threshold = 1.0
    base = acq.FusedEngine(m["member_forces"], cparams, threshold,
                           impl=impl).score(x, advance=False)
    base_out = (base.mean, base.scalar_std, base.component_std, base.mask)
    scale = float(np.max(np.abs(base.mean)))
    out, ok = {}, True
    for shape in ((4, 1), (1, 4)):
        eng = acq.FusedEngine(m["member_forces"], cparams, threshold,
                              impl=impl, mesh=make_scaleout_mesh(*shape))
        uq = eng.score(x, advance=False)
        got = (uq.mean, uq.scalar_std, uq.component_std, uq.mask)
        errs, s_ok = compare_uq(got, base_out, threshold, MESH_TOL, scale)
        errs["bit_identical"] = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(got, base_out))
        out[f"score_{shape[0]}x{shape[1]}"] = errs
        ok &= s_ok

    rng = np.random.RandomState(SEED + 3)
    xs = m["geometries"](rng, 128)
    ys = np.asarray(jax.jit(jax.vmap(
        lambda c: pot.lj_energy_forces(c.reshape(-1, 3))[1].reshape(-1)))(
            xs))
    params = {}
    for name, mesh in (("one", None), ("1x4", make_scaleout_mesh(1, 4))):
        tr = CommitteeTrainer(m["loss"], cparams, batch=32,
                              replay_capacity=256, mesh=mesh, seed=SEED)
        tr.add_blocks(list(zip(xs, ys)))
        tr.train(steps=1)
        params[name] = jax.tree.map(np.asarray, tr.snapshot_cparams())
    errs = [close(b, a, MESH_TOL) for a, b in zip(
        jax.tree.leaves(params["one"]), jax.tree.leaves(params["1x4"]))]
    out["train_step_1x4"] = {"max_err": max(e for e, _ in errs),
                             "bit_identical": all(e == 0 for e, _ in errs)}
    ok &= all(o for _, o in errs)
    print("mesh:", json.dumps(out), flush=True)
    assert ok, f"mesh checks failed: {out}"
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    device = device_or_exit()
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {device['count']}", file=sys.stderr)
        sys.exit(2)
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.platform import enable_compile_cache

    summary = {"device": device,
               "compile_cache": enable_compile_cache()}
    m = potential()
    t0 = time.perf_counter()
    if args.chips == 4:
        summary["mesh"] = mesh_phase(m)
    else:
        summary["campaign"] = campaign_phase(m)
        summary["correctness"] = correctness_phase(m)
    summary["seconds"] = time.perf_counter() - t0
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    name = "chip_smoke.json" if args.chips == 1 else "chip_smoke_4.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
