"""CPU tests of the on-chip benchmark's yardstick: the analytic FLOP and
byte counts, the trace reduction, the reference against the program's
model code, and the loading of cells, configurations, architectures and
peaks."""
import ast
import glob
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import flops  # noqa: E402
import harness  # noqa: E402
import lattice  # noqa: E402
import program  # noqa: E402
import reference as ref  # noqa: E402
import xplane  # noqa: E402

TINY = dict(name="tiny", model="mlp_potential", n_atoms=4, committee_size=3,
            hidden=[5, 6], n_rbf=7, r_cut=3.0, dtype="float32",
            geometry=dict(lattice=[2, 2, 1], spacing=1.3, perturb=0.05),
            weights=dict(w_scale=1.0, b_scale=0.1))


# ------------------------------------------------------------------ flops
def test_fleet_flops_by_hand():
    # layers 7x5, 5x6, 6x1: 35 + 30 + 6 = 71 MACs per atom; forward and
    # input gradient: 142 per atom, 568 for 4 atoms; pair chain rule
    # 4 * 4 * 7 = 112; 680 MACs per structure and member
    assert flops.fleet_step_flops(TINY, 10) == 2.0 * 3 * 10 * 680


def test_train_flops_by_hand():
    # per atom 5 * 71 + (30 + 6) = 391 MACs, 1564 for 4 atoms, plus the
    # pair chain rule twice (224): 1788 per structure and member
    assert flops.train_step_flops(TINY, 2) == 2.0 * 3 * 2 * 1788


def test_uq_kernel_bytes_by_hand():
    assert flops.uq_kernel_bytes(4, 8, 24) == 4.0 * (4 * 8 * 24 + 8 * 24
                                                     + 8 * 128)


# ---------------------------------------------------------------- xplane
def test_union_and_clip():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_reduce_synthetic():
    ms = 1e6
    devices = {"/device:TPU:0": [("a", 1 * ms, 2 * ms),
                                 ("%k = f32[8] custom-call(x)", 2 * ms, 2 * ms),
                                 ("a", 6 * ms, 1 * ms)],
               "/device:TPU:1": [("a", 0, 10 * ms)]}
    spans = [("window", 0, 10 * ms), ("exchange.step", 0, 5 * ms),
             ("exchange.step", 5 * ms, 5 * ms)]
    s = xplane.reduce(devices, spans)
    assert s["window_s"] == pytest.approx(0.010)
    # device 0 busy [1,4) + [6,7) = 4 ms, device 1 all 10 ms: mean 7 ms
    assert s["busy_s"] == pytest.approx(0.007)
    # per-op seconds averaged over the 2 devices
    assert s["ops"]["a"] == pytest.approx((2 + 1 + 10) * 1e-3 / 2)
    assert s["ops"]["%k = f32[8] custom-call(x)"] == pytest.approx(1e-3)
    assert s["device_ops"][0][0] == "a"
    assert s["device_ops"][1][0] == "%k custom-call f32[8]"
    # device 0 idles [0,1) and [4,5) in the first step, [5,6), [7,10) in
    # the second; halved by the device average
    gaps = dict(s["idle_gaps"])
    assert gaps["exchange.step"] == pytest.approx(3e-3)
    assert xplane.op_seconds(s, lambda n: "custom-call" in n) \
        == pytest.approx(1e-3)


def test_reduce_recorded_trace():
    """A recorded trace of a few fleet steps on one TPU v5 lite: every
    device operation lies in the window, busy time is their union."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "fleet256.xplane.pb")
    devices, spans = xplane.load(path, harness.SPAN_NAMES)
    s = xplane.reduce(devices, spans)
    assert s["devices"] == 1
    assert 0 < s["busy_s"] <= s["window_s"]
    assert sum(s["ops"].values()) >= s["busy_s"] * (1 - 1e-9)
    steps = [n for n, _, _ in spans if n == "exchange.step"]
    assert len(steps) >= 1
    assert sum(t for _, t in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6)


# ------------------------------------------------------------- reference
@pytest.fixture(scope="module")
def tiny():
    cp = harness.arch(TINY).program.make_weights(TINY, 3)
    x = lattice.geometries(np.random.RandomState(0), 5,
                           program.base_geometry(TINY), 0.05)
    return cp, x


def test_reference_forces_match_program(tiny):
    cp, x = tiny
    forces, _ = harness.arch(TINY).program.member_functions(TINY)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.vmap(forces, in_axes=(0, None))(cp, x))
    want = ref.committee_forces(cp, x, TINY)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_reference_loss_gradient_matches_program(tiny):
    cp, x = tiny
    _, loss = harness.arch(TINY).program.member_functions(TINY)
    y = np.asarray(jax.vmap(lambda r: lattice.lj_forces(r, 4))(x))
    p = jax.tree.map(lambda a: a[0], cp)
    with jax.default_matmul_precision("highest"):
        g_prog = jax.grad(lambda q: loss(q, {"x": x, "y": y})[0])(p)
        g_ref = jax.grad(lambda q: ref.force_loss(q, x, y, TINY))(p)
    for k in g_ref:
        np.testing.assert_allclose(g_prog[k], g_ref[k], rtol=1e-4,
                                   atol=1e-6)
    assert float(jnp.abs(g_ref["b2"]).max()) < 1e-6 * float(
        jnp.abs(g_ref["w0"]).max())   # forces do not see the output bias


def test_reference_stats_match_kernel_reference(tiny):
    from repro.kernels import ref as kref

    cp, x = tiny
    preds = ref.committee_forces(cp, x, TINY)
    mean, sstd, cstd = ref.committee_stats(preds)
    k_mean, k_sstd, k_cstd, _, _ = kref.committee_uq_ref(
        jnp.asarray(preds), 0.1)
    np.testing.assert_allclose(mean, k_mean, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sstd, k_sstd, rtol=1e-5)
    np.testing.assert_allclose(cstd, k_cstd, rtol=1e-5)


def test_reference_budget_and_patience_match_program():
    from repro.core.budget import BudgetRule
    from repro.exploration.fleet import PatienceRestart

    rule = BudgetRule(target=0.02, thr_init=0.05)
    state = rule.init_state()
    for n_sel in (30, 7, 0, 2):
        prog = rule.controller.update(state, n_sel / 256, *rule._bounds())
        want = ref.budget_update(state, n_sel, 256, 0.02, 0.05)
        for k in ("threshold", "integral", "ema_rate"):
            assert float(prog[k]) == pytest.approx(want[k], rel=1e-5,
                                                   abs=1e-7)
        state = prog
    counts = np.array([0, 3, 5, 5, 2])
    restarts = np.array([0, 1, 0, 2, 0])
    flag = np.array([False, False, False, False, True])
    mask = np.array([True, True, True, False, True])
    x = np.zeros((5, 3))
    c, r, f = PatienceRestart(5).apply(
        jnp.where(jnp.asarray(flag), 0, counts), restarts, mask)
    wc, wr, wf = ref.patience_update(counts, restarts, flag, x, mask, 5)
    np.testing.assert_array_equal(c, wc)
    np.testing.assert_array_equal(r, wr)
    np.testing.assert_array_equal(f, wf)


def test_reference_advance_matches_fleet_sampler():
    from repro.exploration.fleet import FleetConfig, make_sampler

    n, d = 6, 12
    rng = np.random.RandomState(1)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(5), jnp.arange(n))
    carry = {"x": rng.randn(n, d).astype(np.float32),
             "x0": rng.randn(n, d).astype(np.float32),
             "f": 30 * rng.randn(n, d).astype(np.float32),
             "key": np.asarray(keys), "step": np.int32(3),
             "flag": np.array([0, 1, 0, 0, 0, 0], bool)}
    cfg = FleetConfig()
    sub = jax.vmap(jax.random.split)(keys)[:, 0]
    moved = np.asarray(make_sampler(cfg)(
        jnp.asarray(carry["x"]), None, jnp.asarray(carry["f"]), sub)[0])
    want = ref.advance(carry, cfg.dt, cfg.clip, cfg.noise)
    np.testing.assert_allclose(want[0], moved[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(want[1], carry["x0"][1])   # restarted


# ---------------------------------------------------- cells and configs
def test_every_cell_has_its_files():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for m in names:
        assert os.path.exists(os.path.join(HERE, "metrics", m + ".py")), m
    for cell in bench["workloads"]:
        spec = harness.cell_spec(cell["name"])
        assert spec["cfg"]["name"] == cell["config"]
        harness.arch(spec["cfg"])
        assert os.path.exists(os.path.join(
            HERE, "loops", spec["traffic"]["loop"] + ".py"))
        assert spec["end_to_end"] and spec["per_layer"]
        assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
        for m in spec["per_layer"]:
            assert any(e["name"] == m["moves"] for e in spec["end_to_end"])
    for conf in bench["configs"]:
        cfg = harness.load_json(os.path.join(harness.ROOT, conf["file"]))
        assert cfg["name"] == conf["name"] and cfg["source"] == conf["source"]
        assert cfg["reduced"] == conf["reduced"]


@pytest.mark.parametrize("model", [None, "no_such_model"])
def test_missing_or_unknown_model_raises(model):
    cfg = {k: v for k, v in TINY.items() if k != "model"}
    if model is not None:
        cfg["model"] = model
    with pytest.raises(KeyError, match=r"known: \[.*'mlp_potential'"):
        harness.arch(cfg)


def test_reference_sides_import_nothing_of_the_program():
    refs = sorted(glob.glob(os.path.join(HERE, "archs", "*_ref.py"))
                  + glob.glob(os.path.join(HERE, "tests", "data",
                                           "*_ref.py")))
    assert os.path.join(HERE, "archs", "mlp_potential_ref.py") in refs
    for path in refs:
        with open(path) as f:
            tree = ast.parse(f.read())
        mods = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names]
        mods += [n.module or "" for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)]
        assert mods and not [m for m in mods if m.split(".")[0] in (
            "repro", "program", "harness")], (path, mods)


def test_unknown_workload_and_device_raise():
    with pytest.raises(KeyError):
        harness.cell_spec("no-such-cell")
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_seeds_are_distinct_and_large_seeds_work():
    seeds = [0, 1, 2 ** 31 + 5, 2 ** 32 + 7, 7]
    got = [harness.derive_seed(s, "weights") for s in seeds]
    assert len(set(got)) == len(seeds)
    assert all(0 <= g < 2 ** 31 for g in got)
    assert harness.derive_seed(9, "a") == harness.derive_seed(9, "a")
    assert harness.derive_seed(9, "a") != harness.derive_seed(9, "b")


def test_memory_peak_counts_reserved_temporaries():
    stats = [{"peak_bytes_in_use": 30, "peak_bytes_reserved": 1400},
             {"peak_bytes_in_use": 50}, {}]
    assert harness.memory_peak_bytes(stats) == 1430
    assert harness.memory_peak_bytes([]) == 0


def test_no_tpu_is_an_error():
    with pytest.raises(harness.NoDevice):
        harness.device_info(1, require_tpu=True)
    with pytest.raises(harness.NoDevice):
        harness.device_info(len(jax.devices()) + 1, require_tpu=False)


def test_limits_name_the_loop_readings():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for cell in bench["workloads"]:
        spec = harness.cell_spec(cell["name"])
        assert all(isinstance(v, (int, float)) and v >= 0
                   for v in spec["limits"].values())
        json.dumps(spec["limits"])


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 77])
def test_fleet_threshold_starts_above_the_committee_std(seed):
    """The budget controller comes down onto the std distribution from
    above; started below it, it selects every walker, winds up and then
    selects nothing for dozens of steps, past the warm-up."""
    spec = harness.cell_spec("fleet.mlp-pot-ani1x-widths")
    cfg = spec["cfg"]
    cparams = harness.arch(cfg).program.make_weights(
        cfg, harness.derive_seed(seed, "weights"))
    x = lattice.geometries(
        np.random.RandomState(harness.derive_seed(seed, "walkers")), 8,
        program.base_geometry(cfg), cfg["geometry"]["perturb"])
    _, sstd, _ = ref.committee_stats(
        ref.committee_forces(cparams, x, cfg, ref.F32), ref.F32)
    assert float(np.max(sstd)) < spec["traffic"]["std_threshold"]
