"""Multi-device bit-identity: every fused path on a REAL 8-device mesh.

Runs only when the process already has >= 8 devices (the CI ``mesh`` job
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; locally:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_mesh_parity.py

).  The contract under test: sharding is a LAYOUT decision, not a
numerics decision — ``FusedEngine.score`` / ``score_after`` (exploration
fleet), the ``CommitteeTrainer`` step, and the ``ServingQueue`` dispatch
must produce bit-identical results on the (8 data x 1 model) scale-out
mesh, including stateful-rule state, checkpoint round-trips of sharded
state, and the device-resident fleet carry.

Known exception (asserted, with tolerance): on the (1 x 8) COMMITTEE-axis
mesh every device runs its own members' forward, and XLA:CPU rounds those
per-device matmuls differently from the one batched K-member matmul of the
unsharded program (forward outputs differ by up to ~4e-7 absolute).  So
the trainer's params drift at the ~1 ULP level per step (the grad+Adam
chain also fuses differently under SPMD partitioning), and scoring is
bounded rather than bit-identical: std outputs within a few ULP, the mean
within the same absolute bound as the trainer (its relative error is only
large where the mean itself is near zero), the rule state within that
bound too, and the selection mask equal.  No sharding constraint can pin the member matmuls
without gathering the committee, which would undo the committee axis.
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.pal_potential import PALRunConfig
from repro.core import acquisition as acq
from repro.core.budget import rules_from_config
from repro.core.committee import stack_members
from repro.launch.mesh import make_scaleout_mesh

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")

K, D, HID = 8, 6, 16
THRESHOLD = 0.35


def _init_member(seed):
    r = np.random.RandomState(seed)
    return {"w1": jnp.asarray(r.randn(D, HID).astype(np.float32) * 0.3),
            "w2": jnp.asarray(r.randn(HID, D).astype(np.float32) * 0.3)}


def _apply(p, x):
    return jnp.tanh(x @ p["w1"]) @ p["w2"]


@pytest.fixture(scope="module")
def cparams():
    return stack_members([_init_member(i) for i in range(K)])


def _engine(cparams, mesh, with_rules=False):
    rules = None
    if with_rules:
        cfg = PALRunConfig(std_threshold=THRESHOLD, oracle_budget=0.3,
                           reweight_buckets=32)
        rules = rules_from_config(cfg)
    return acq.FusedEngine(_apply, cparams, THRESHOLD, rules=rules,
                           impl="xla", mesh=mesh)


def _uq_equal(a, b):
    return all(np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f)))
               for f in ("mean", "scalar_std", "component_std", "mask"))


def _assert_uq_ulp_bounded(a, b):
    """Committee-axis scoring bound (module docstring): the selection is
    identical, the statistics agree to the trainer's committee-axis
    tolerance."""
    np.testing.assert_array_equal(np.asarray(b.mask), np.asarray(a.mask))
    for f in ("mean", "scalar_std", "component_std"):
        np.testing.assert_allclose(np.asarray(getattr(b, f)),
                                   np.asarray(getattr(a, f)),
                                   rtol=1e-5, atol=1e-6)


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


@pytest.mark.parametrize("shape", [(8, 1), (1, 8)], ids=["data8", "model8"])
def test_score_bitidentical_with_stateful_rules(cparams, shape):
    """4 advancing rounds: outputs AND BudgetRule/RollingReweightRule
    state stay bit-identical to the unsharded engine on the data-axis
    mesh; on the committee-axis mesh the statistics and the controller
    state are ULP-bounded (see the module docstring) while the selection
    mask stays identical."""
    e0 = _engine(cparams, None, with_rules=True)
    e8 = _engine(cparams, make_scaleout_mesh(*shape), with_rules=True)
    committee_axis = shape[1] > 1
    rng = np.random.RandomState(1)
    for _ in range(4):
        xs = rng.randn(61, D).astype(np.float32)
        a, b = e0.score(list(xs)), e8.score(list(xs))
        if committee_axis:
            _assert_uq_ulp_bounded(a, b)
        else:
            assert _uq_equal(a, b)
    s0, s8 = e0.state_dict(), e8.state_dict()
    if committee_axis:
        for x, y in zip(jax.tree.leaves(s0), jax.tree.leaves(s8)):
            np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                       rtol=1e-5, atol=1e-6)
    else:
        assert _tree_equal(s0, s8)


def test_score_ndarray_fastpath_matches_list(cparams):
    e8 = _engine(cparams, make_scaleout_mesh(8, 1))
    rng = np.random.RandomState(2)
    x = rng.randn(33, D).astype(np.float32)
    assert _uq_equal(e8.score(x, advance=False),
                     e8.score(list(x), advance=False))


def test_rule_state_checkpoint_roundtrip_on_mesh(cparams):
    """state_dict taken from a mesh engine restores onto a fresh mesh
    engine (replicated placement) and scoring continues bit-identically."""
    mesh = make_scaleout_mesh(8, 1)
    rng = np.random.RandomState(3)
    e8 = _engine(cparams, mesh, with_rules=True)
    for _ in range(3):
        e8.score(list(rng.randn(21, D).astype(np.float32)))
    e8b = _engine(cparams, mesh, with_rules=True)
    e8b.load_state_dict(e8.state_dict())
    xs = rng.randn(19, D).astype(np.float32)
    assert _uq_equal(e8.score(list(xs)), e8b.score(list(xs)))
    assert _tree_equal(e8.state_dict(), e8b.state_dict())


def test_zero_extra_host_bytes_on_mesh(cparams):
    """The mesh engine must move exactly the bytes the unsharded engine
    moves: input up, (mean, sstd, cstd, mask) down — resharding happens
    device-side, never via a host bounce."""
    e0 = _engine(cparams, None)
    e8 = _engine(cparams, make_scaleout_mesh(8, 1))
    rng = np.random.RandomState(4)
    for n in (16, 33, 64):
        e0.score(rng.randn(n, D).astype(np.float32), advance=False)
    rng = np.random.RandomState(4)
    for n in (16, 33, 64):
        e8.score(rng.randn(n, D).astype(np.float32), advance=False)
    assert e8.bytes_to_device == e0.bytes_to_device
    assert e8.bytes_to_host == e0.bytes_to_host


def test_fleet_score_after_and_carry_parity(cparams):
    """Device-resident fleet: 4 fused advance+score+select steps plus the
    carry checkpoint round-trip, all bit-identical on the mesh."""
    from repro.exploration.fleet import FleetConfig, WalkerFleet

    mesh = make_scaleout_mesh(8, 1)
    fc = FleetConfig(sampler="langevin", dt=0.002, noise=0.01, clip=20.0,
                     friction=0.1, patience=3, seed=7)
    x0 = np.random.RandomState(5).randn(24, D).astype(np.float32)
    fl0 = WalkerFleet(_engine(cparams, None), x0, fc)
    fl8 = WalkerFleet(_engine(cparams, mesh), x0, fc)
    for _ in range(4):
        o0, o8 = fl0.step(), fl8.step()
        assert o0.n_selected == o8.n_selected
        assert np.array_equal(o0.selected, o8.selected)
        assert np.array_equal(np.asarray(o0.mean), np.asarray(o8.mean))
    c0, c8 = fl0.state_dict(), fl8.state_dict()
    assert all(np.array_equal(c0[k], c8[k]) for k in c0)

    # carry restore re-places onto the mesh and continues bit-identically
    fl8b = WalkerFleet(_engine(cparams, mesh), x0, fc)
    fl8b.load_state_dict(c8)
    oa, ob = fl0.step(), fl8b.step()
    assert np.array_equal(np.asarray(oa.mean), np.asarray(ob.mean))


def _make_trainer(cparams, mesh, steps=3):
    from repro.training.committee_trainer import CommitteeTrainer

    def loss_fn(params, batch):
        pred = _apply(params, batch["x"])
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {"loss": loss}

    rng = np.random.RandomState(6)
    xs = rng.randn(64, D).astype(np.float32)
    ys = rng.randn(64, D).astype(np.float32)
    tr = CommitteeTrainer(loss_fn, cparams, steps=steps, batch=16, lr=1e-3,
                          bootstrap=True, replay_capacity=128, mesh=mesh,
                          seed=3)
    tr.add_blocks(list(zip(xs, ys)))
    return tr


def test_trainer_bitidentical_on_data_axis_mesh(cparams):
    """Losses, params, AND optimizer moments after 3 fused steps on the
    (8, 1) mesh match the unsharded trainer bit for bit; a sharded
    TrainState checkpoint restores onto a fresh mesh trainer and the next
    round stays bit-identical too."""
    mesh = make_scaleout_mesh(8, 1)
    t0, t8 = _make_trainer(cparams, None), _make_trainer(cparams, mesh)
    m0, m8 = t0.train(), t8.train()
    assert np.array_equal(m0["loss"], m8["loss"])
    assert _tree_equal(jax.tree.map(np.asarray, t0.snapshot_cparams()),
                       jax.tree.map(np.asarray, t8.snapshot_cparams()))

    t8b = _make_trainer(cparams, mesh)
    t8b.load_state_dict(t8.state_dict())
    m0b, m8b = t0.train(), t8b.train()
    assert np.array_equal(m0b["loss"], m8b["loss"])
    assert _tree_equal(jax.tree.map(np.asarray, t0.snapshot_cparams()),
                       jax.tree.map(np.asarray, t8b.snapshot_cparams()))


def test_trainer_model_axis_ulp_bounded(cparams):
    """Committee-axis (1, 8) mesh: XLA fuses grad+Adam differently under
    SPMD partitioning, so params may drift by ~1 ULP per step (fp32).
    Pin the bound tightly — a real resharding bug shows up orders of
    magnitude above it."""
    t0 = _make_trainer(cparams, None)
    tm = _make_trainer(cparams, make_scaleout_mesh(1, 8))
    m0, mm = t0.train(), tm.train()
    np.testing.assert_allclose(np.asarray(mm["loss"]),
                               np.asarray(m0["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(t0.snapshot_cparams()),
                    jax.tree.leaves(tm.snapshot_cparams())):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)


def test_serving_queue_parity(cparams):
    from repro.serving.engine import CommitteeServer
    from repro.serving.queue import QueueConfig, ServingQueue

    qc = QueueConfig(max_batch=16, max_wait_ms=20.0)
    rng = np.random.RandomState(8)
    reqs = [rng.randn(3, D).astype(np.float32) for _ in range(8)]
    with ServingQueue(CommitteeServer(_engine(cparams, None)), qc) as q0, \
            ServingQueue(CommitteeServer(
                _engine(cparams, make_scaleout_mesh(8, 1))), qc) as q8:
        f0 = [q0.submit(list(r)) for r in reqs]
        f8 = [q8.submit(list(r)) for r in reqs]
        for a, b in zip(f0, f8):
            ua, ub = a.result(timeout=60), b.result(timeout=60)
            assert np.array_equal(np.asarray(ua[0]), np.asarray(ub[0]))


def test_k3_committee_on_8way_mesh_warns_and_matches(caplog):
    """A K=3 committee over the 8-way model axis cannot shard the
    committee dim: the layout must degrade LOUDLY (warn_fallbacks names
    the chosen layout) and still score bit-identically."""
    cp3 = stack_members([_init_member(i) for i in range(3)])
    with caplog.at_level(logging.WARNING, logger="repro.sharding.rules"):
        e3 = acq.FusedEngine(_apply, cp3, THRESHOLD, impl="xla",
                             mesh=make_scaleout_mesh(1, 8))
    assert any("sharding fallback" in r.getMessage()
               for r in caplog.records), caplog.records
    e0 = acq.FusedEngine(_apply, cp3, THRESHOLD, impl="xla", mesh=None)
    xs = np.random.RandomState(9).randn(32, D).astype(np.float32)
    assert _uq_equal(e0.score(xs, advance=False),
                     e3.score(xs, advance=False))


def test_resolve_mesh_grid_form():
    cfg = PALRunConfig(uq_mesh="8x1")
    mesh = acq.resolve_mesh(cfg)
    assert dict(mesh.shape) == {"data": 8, "model": 1}
    cfg = PALRunConfig(uq_mesh="2x4")
    assert dict(acq.resolve_mesh(cfg).shape) == {"data": 2, "model": 4}
    with pytest.raises(ValueError):
        acq.resolve_mesh(PALRunConfig(uq_mesh="3z"))
