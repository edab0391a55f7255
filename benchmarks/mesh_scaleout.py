"""Benchmark: production-mesh scale-out of the fused committee paths.

Runs on a REAL 8-device mesh emulated on the host CPU
(``--xla_force_host_platform_device_count=8`` via
``launch/platform.ensure_host_devices`` — set before the first jax import,
so every sharding/collective/donation path executes exactly as on
hardware).  Four claims, written to ``BENCH_mesh_scaleout.json``:

* **headline** ``speedup_mesh8_vs_legacy_1dev`` — fused single-dispatch
  scoring on the (8 data x 1 model) mesh vs the seed's per-member
  sequential LegacyEngine on one device, at the production batch size.
  This is the same fused-vs-sequential framing every other gate in this
  repo uses, and it genuinely exercises the 8-device SPMD path.
* **weak scaling** — fixed rows-per-device, throughput ratio at 1/2/4/8
  devices.  On a single physical core the emulated devices time-slice, so
  the ratio is dispatch-overhead bound (~1x-1.4x here); on real multi-chip
  hardware it tracks device count.  Recorded as a tolerance-gated curve,
  no absolute floor.
* **committee-axis curve** — the (1 x 8) model-axis mesh that shards the
  K=8 committee one member per device (the PAL paper's "prediction
  processes" laid out across a mesh axis).
* **parity flags** — score / score_after (exploration fleet) / train /
  serving must be BIT-IDENTICAL between the unsharded engine and the
  (8, 1) mesh, including stateful-rule state and the fleet carry.  Any
  False here means a resharding path silently changed numerics.

Usage:  PYTHONPATH=src python benchmarks/mesh_scaleout.py [--quick] [--out F]
(Needs a fresh process — raises if a jax backend with <8 devices already
initialized; ``python -m benchmarks.run --only mesh`` runs it in-process
before anything else touches jax.)
"""
from __future__ import annotations

import sys

from repro.launch.platform import ensure_host_devices

ensure_host_devices(8)

import argparse                  # noqa: E402
import json                      # noqa: E402
import statistics                # noqa: E402
import time                      # noqa: E402

import jax                       # noqa: E402
import jax.numpy as jnp          # noqa: E402
import numpy as np               # noqa: E402

from repro.core import acquisition as acq          # noqa: E402
from repro.core import committee as cmte           # noqa: E402
from repro.launch.mesh import make_scaleout_mesh   # noqa: E402

try:
    from benchmarks.run import bench_meta
except ImportError:              # running as a script from benchmarks/
    from run import bench_meta

K = 8
D = 6
HIDDEN = 64
THRESHOLD = 0.35
ROWS_HEADLINE = 4096     # fused-mesh advantage grows with rows; 4096 sits
ROWS_COMMITTEE = 512     # comfortably past the 2x gate on a 1-core host
ROWS_PER_DEVICE = 64


def _init_member(seed):
    r = np.random.RandomState(seed)
    return {"w1": jnp.asarray(r.randn(D, HIDDEN).astype(np.float32) * 0.3),
            "w2": jnp.asarray(r.randn(HIDDEN, D).astype(np.float32) * 0.3)}


def _apply(p, x):
    return jnp.tanh(x @ p["w1"]) @ p["w2"]


def _make_legacy(cparams):
    """Seed path: K per-member jitted predicts + float64 host statistics."""
    members = [cmte.member(cparams, i) for i in range(K)]
    fns = [jax.jit(lambda x, p=m: _apply(p, x)) for m in members]

    def predict_all(list_data):
        x = np.asarray(list_data, dtype=np.float32)
        # one host->device upload and one device->host download PER
        # member — the seed exchange loop's K separate predict calls
        # (same accounting as committee_uq.bench_sequential)
        return np.stack([np.asarray(f(jnp.asarray(x))) for f in fns])

    return acq.LegacyEngine(predict_all, THRESHOLD)


def _tput(engine, rows, reps, warmup, as_list=False):
    rng = np.random.RandomState(0)
    x = rng.randn(rows, D).astype(np.float32)
    data = list(x) if as_list else x
    for _ in range(warmup):
        engine.score(data, advance=False)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        engine.score(data, advance=False)
        ts.append(time.perf_counter() - t0)
    med = statistics.median(ts)
    return rows / med, med


def _fused(cparams, mesh):
    return acq.FusedEngine(_apply, cparams, THRESHOLD, impl="xla", mesh=mesh)


def _uq_equal(a, b):
    return all(np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f)))
               for f in ("mean", "scalar_std", "component_std", "mask"))


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def parity_score(cparams, mesh8, rng):
    """Bit-identity of score() incl. stateful-rule advancement."""
    from repro.configs.pal_potential import PALRunConfig
    from repro.core.budget import rules_from_config

    cfg = PALRunConfig(std_threshold=THRESHOLD, oracle_budget=0.3,
                       reweight_buckets=32)

    def mk(mesh):
        return acq.FusedEngine(_apply, cparams, THRESHOLD,
                               rules=rules_from_config(cfg), impl="xla",
                               mesh=mesh)

    e0, e8 = mk(None), mk(mesh8)
    ok = True
    for _ in range(3):
        xs = rng.randn(61, D).astype(np.float32)
        ok &= _uq_equal(e0.score(list(xs)), e8.score(list(xs)))
    return ok and _tree_equal(e0.state_dict(), e8.state_dict())


def parity_score_after(cparams, mesh8, rng):
    """Fleet advance+score+select: outputs + carry bit-identical."""
    from repro.exploration.fleet import FleetConfig, WalkerFleet

    fc = FleetConfig(sampler="langevin", dt=0.002, noise=0.01, clip=20.0,
                     friction=0.1, patience=3, seed=7)
    x0 = rng.randn(24, D).astype(np.float32)
    fl0 = WalkerFleet(_fused(cparams, None), x0, fc)
    fl8 = WalkerFleet(_fused(cparams, mesh8), x0, fc)
    ok = True
    for _ in range(4):
        o0, o8 = fl0.step(), fl8.step()
        ok &= o0.n_selected == o8.n_selected
        ok &= np.array_equal(o0.selected, o8.selected)
        ok &= np.array_equal(np.asarray(o0.mean), np.asarray(o8.mean))
    c0, c8 = fl0.state_dict(), fl8.state_dict()
    return ok and all(np.array_equal(c0[k], c8[k]) for k in c0)


def parity_train(cparams, mesh8, rng):
    """Fused K-member training step: losses + params bit-identical."""
    from repro.training.committee_trainer import CommitteeTrainer

    def loss_fn(params, batch):
        pred = _apply(params, batch["x"])
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {"loss": loss}

    xs = rng.randn(64, D).astype(np.float32)
    ys = rng.randn(64, D).astype(np.float32)

    def mk(mesh):
        tr = CommitteeTrainer(loss_fn, cparams, steps=3, batch=16, lr=1e-3,
                              bootstrap=True, replay_capacity=128, mesh=mesh,
                              seed=3)
        tr.add_blocks(list(zip(xs, ys)))
        return tr

    t0, t8 = mk(None), mk(mesh8)
    m0, m8 = t0.train(), t8.train()
    return (np.array_equal(m0["loss"], m8["loss"])
            and _tree_equal(jax.tree.map(np.asarray, t0.snapshot_cparams()),
                            jax.tree.map(np.asarray, t8.snapshot_cparams())))


def parity_serving(cparams, mesh8, rng):
    """Queue-batched serving on the mesh answers bit-identically."""
    from repro.serving.engine import CommitteeServer
    from repro.serving.queue import QueueConfig, ServingQueue

    qc = QueueConfig(max_batch=32, max_wait_ms=20.0)
    q0 = ServingQueue(CommitteeServer(_fused(cparams, None)), qc)
    q8 = ServingQueue(CommitteeServer(_fused(cparams, mesh8)), qc)
    try:
        reqs = [rng.randn(3, D).astype(np.float32) for _ in range(8)]
        f0 = [q0.submit(list(r)) for r in reqs]
        f8 = [q8.submit(list(r)) for r in reqs]
        ok = True
        for a, b in zip(f0, f8):
            ua, ub = a.result(timeout=60), b.result(timeout=60)
            ok &= np.array_equal(np.asarray(ua[0]), np.asarray(ub[0]))
        return ok
    finally:
        q0.close()
        q8.close()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", "--quick", dest="smoke", action="store_true",
                    help="few timing reps (CI smoke); same shapes")
    ap.add_argument("--out", default="BENCH_mesh_scaleout.json")
    args = ap.parse_args(argv)
    if jax.device_count() < 8:
        raise RuntimeError(
            f"mesh_scaleout needs 8 devices, found {jax.device_count()} — "
            "run it as its own invocation (python -m benchmarks.run --only "
            "mesh, or this script)")
    reps = 10 if args.smoke else 40
    warmup = 3 if args.smoke else 8

    rng = np.random.RandomState(0)
    cparams = cmte.stack_members([_init_member(i) for i in range(K)])
    mesh8 = make_scaleout_mesh(8, 1)

    # --- headline: fused 8-device mesh vs sequential legacy on 1 device
    tp_leg, t_leg = _tput(_make_legacy(cparams), ROWS_HEADLINE, reps,
                          warmup, as_list=True)
    tp_f1, t_f1 = _tput(_fused(cparams, None), ROWS_HEADLINE, reps, warmup)
    tp_m8, t_m8 = _tput(_fused(cparams, mesh8), ROWS_HEADLINE, reps, warmup)
    headline = tp_m8 / tp_leg
    print(f"headline rows={ROWS_HEADLINE}: legacy {t_leg * 1e3:.2f} ms, "
          f"fused(1dev) {t_f1 * 1e3:.2f} ms, fused(8x1 mesh) "
          f"{t_m8 * 1e3:.2f} ms -> mesh8/legacy {headline:.2f}x "
          f"(mesh8/fused1 {tp_m8 / tp_f1:.2f}x)", flush=True)

    # --- weak scaling: fixed rows/device, data axis 1 -> 8
    weak = {}
    tp_base = None
    for nd in (1, 2, 4, 8):
        mesh = None if nd == 1 else make_scaleout_mesh(nd, 1)
        tp, med = _tput(_fused(cparams, mesh), ROWS_PER_DEVICE * nd,
                        reps, warmup)
        tp_base = tp_base or tp
        weak[str(nd)] = {"rows": ROWS_PER_DEVICE * nd,
                         "ms": med * 1e3, "rows_per_s": tp,
                         "ratio_vs_1dev": tp / tp_base}
        print(f"weak scaling {nd} dev: rows={ROWS_PER_DEVICE * nd} "
              f"{med * 1e3:.2f} ms  ratio {tp / tp_base:.2f}x", flush=True)

    # --- committee axis: one member per device on the (1, 8) mesh
    tp_c1, t_c1 = _tput(_fused(cparams, None), ROWS_COMMITTEE, reps, warmup)
    tp_c8, t_c8 = _tput(_fused(cparams, make_scaleout_mesh(1, 8)),
                        ROWS_COMMITTEE, reps, warmup)
    print(f"committee axis rows={ROWS_COMMITTEE}: 1dev {t_c1 * 1e3:.2f} ms, "
          f"(1x8) mesh {t_c8 * 1e3:.2f} ms  ratio {tp_c8 / tp_c1:.2f}x",
          flush=True)

    # --- parity flags (bit-identity vs the unsharded engine)
    flags = {
        "parity_score": bool(parity_score(cparams, mesh8, rng)),
        "parity_score_after": bool(parity_score_after(cparams, mesh8, rng)),
        "parity_train": bool(parity_train(cparams, mesh8, rng)),
        "parity_serving": bool(parity_serving(cparams, mesh8, rng)),
    }
    print("parity:", " ".join(f"{k.split('_', 1)[1]}={v}"
                              for k, v in flags.items()), flush=True)

    report = {
        "meta": bench_meta(mesh_shape="8x1"),
        "config": {"K": K, "in_dim": D, "hidden": HIDDEN,
                   "threshold": THRESHOLD, "rows_headline": ROWS_HEADLINE,
                   "rows_per_device": ROWS_PER_DEVICE,
                   "rows_committee_axis": ROWS_COMMITTEE, "reps": reps},
        "legacy_1dev": {"ms": t_leg * 1e3, "rows_per_s": tp_leg},
        "fused_1dev": {"ms": t_f1 * 1e3, "rows_per_s": tp_f1},
        "fused_mesh8_data": {"ms": t_m8 * 1e3, "rows_per_s": tp_m8},
        "speedup_mesh8_vs_legacy_1dev": headline,
        "speedup_mesh8_vs_fused_1dev": tp_m8 / tp_f1,
        "weak_scaling": {"curve": weak,
                         "ratio_8dev": weak["8"]["ratio_vs_1dev"]},
        "committee_axis": {"mesh": "1x8", "ms": t_c8 * 1e3,
                           "rows_per_s": tp_c8,
                           "ratio_vs_1dev": tp_c8 / tp_c1},
        **flags,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    if not all(flags.values()):
        print("PARITY FAILURE — a mesh path changed numerics",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
