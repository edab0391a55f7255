"""CPU tests of the PaiNN architecture in the on-chip benchmark: its FLOP
counts by hand, the program's forces and force-loss gradient against the
plain reference (``archs/painn_ref.py``), the pair-metric reader, the
fleet cell's initial threshold, and a tiny PaiNN committee
(``data/painn-tiny.json``) through both loops of the harness: a sound run
is ``correct`` (in the train loop the first clipped gradient that
``CommitteeTrainer`` applies is held to the reference's ``jax.grad`` by
``grad_gap``), and its bfloat16 control is not."""
import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import lattice  # noqa: E402
import program  # noqa: E402
import reference as ref  # noqa: E402

DATA = os.path.join(HERE, "tests", "data")
PAINN_TINY = harness.load_json(os.path.join(DATA, "painn-tiny.json"))
CELLS = {"exchange": "fleet-mem.painn-md17-widths",
         "train": "train.mlp-pot-ani1x-widths"}
TINY_TRAFFIC = {"exchange": dict(walkers=16, warmup_steps=4,
                                 std_threshold=0.002),
                "train": dict(replay_rows=64, batch=4, train_steps=5)}


# ------------------------------------------------------------------ flops
def test_painn_flops_by_hand():
    cfg = dict(PAINN_TINY, n_atoms=4, features=4, n_blocks=2, n_rbf=3)
    prog = harness.arch(cfg).program
    # filter layer over 4 x 4 pairs, 3 -> 3F x 2 blocks = 24: 1152 MACs;
    # forward and input gradient, 2 FLOPs each: 4608.  The cutoff's
    # product and its cotangents on 16 x 24 values: 4 x 384 = 1536.
    # Messages: (16 + 33) FLOPs x 16 pairs x 4 features x 2 blocks = 6272
    assert prog.pair_step_flops(cfg, 5) == 2 * 5 * (4608 + 1536 + 6272)
    # atomwise, per atom: per block phi 16 + 48, U and V 2 x 3 x 16, the
    # update net 32 + 48: 240 MACs; readout 4 x 2 + 2 x 1 = 10; two
    # blocks and 4 atoms: 4 x 490 = 1960 MACs, forward and input
    # gradient: 7840 FLOPs
    assert prog.fleet_step_flops(cfg, 5) == 2 * 5 * (12416 + 7840)
    # train: dense 2 x (6 x 1960 + 5 x 1152) = 35040; the pair terms
    # three times: 3 x (1536 + 6272) = 23424
    assert prog.train_step_flops(cfg, 3) == 2 * 3 * (35040 + 23424)


# -------------------------------------------------------------- reference
@pytest.fixture(scope="module")
def painn_tiny():
    cp = harness.arch(PAINN_TINY).program.make_weights(PAINN_TINY, 3)
    x = lattice.geometries(np.random.RandomState(0), 5,
                           program.base_geometry(PAINN_TINY), 0.05)
    return cp, x


def test_painn_reference_forces_match_program(painn_tiny):
    """The program (vectors spatial-axis first, products and sums over
    pairs) and the reference (atom-first, broadcast sums) in float32 at
    ``highest``: they add the same terms in another order, so agree to
    float32 round-off over two blocks of 7-neighbour sums, about 1e-6 of
    the largest force; 1e-5 leaves a tenfold margin."""
    cp, x = painn_tiny
    forces, _ = harness.arch(PAINN_TINY).program.member_functions(PAINN_TINY)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.vmap(forces, in_axes=(0, None))(cp, x))
    want = ref.committee_forces(cp, x, PAINN_TINY)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * np.abs(want).max())


def test_painn_reference_loss_gradient_matches_program(painn_tiny):
    """The force loss's parameter gradient differentiates the forces once
    more, through both blocks; float32 round-off as above, on every leaf
    relative to the largest gradient element of the tree, since leaves
    such as the output bias, which forces do not see, are round-off
    themselves."""
    cp, x = painn_tiny
    _, loss = harness.arch(PAINN_TINY).program.member_functions(PAINN_TINY)
    y = np.asarray(jax.vmap(lambda r: lattice.lj_forces(r, 8))(x))
    p = jax.tree.map(lambda a: a[1], cp)
    with jax.default_matmul_precision("highest"):
        g_prog = jax.grad(lambda q: loss(q, {"x": x, "y": y})[0])(p)
        g_ref = jax.grad(lambda q: ref.force_loss(q, x, y, PAINN_TINY))(p)
    top = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(g_ref))
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * top)


# ----------------------------------------------------------------- reader
def test_painn_pair_mfu_reads_the_pair_operations():
    reader = harness.load_module(os.path.join(
        HERE, "metrics", "painn_pair_mfu.fleet.py"), "metric_painn_pair")
    cfg = harness.cell_spec("fleet-mem.painn-md17-widths")["cfg"]
    ops = {"%fusion.1 = f32[8,16,64,64,1152]{4,3,2,1,0} fusion(f32[16,64,"
           "64,20]{3,2,1,0} %p)": 0.30,
           "%fusion.2 = f32[8,16,3,64,128]{4,3,2,1,0} fusion(f32[8,16,64,"
           "64,384]{4,3,2,1,0} %x)": 0.20,
           "%fusion.3 = f32[8,16,3,64,128]{4,3,2,1,0} fusion(f32[8,164,64]"
           "{2,1,0} %y)": 0.25}
    rec = {"trace": {"ops": ops}, "traffic": {"loop": "exchange"},
           "peaks": {"bf16_flops_per_s": 197e12}, "cfg": cfg, "chips": 1,
           "window": {"steps": 10, "step_flops_walkers": 16}}
    want = 100.0 * harness.arch(cfg).program.pair_step_flops(cfg, 16) \
        * 10 / (0.50 * 197e12)
    assert reader.read(rec) == pytest.approx(want)
    # an architecture without a pair part reads nothing
    rec["cfg"] = harness.cell_spec("fleet.mlp-pot-ani1x-widths")["cfg"]
    assert reader.read(rec) is None


# ------------------------------------------------------------------ cells
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 77])
def test_painn_fleet_threshold_starts_above_the_committee_std(seed):
    """As for the MLP fleet cell: the budget controller comes down onto
    the std distribution from above, so the initial threshold lies above
    the random committee's largest std on the cell's own walkers."""
    spec = harness.cell_spec("fleet-mem.painn-md17-widths")
    cfg = spec["cfg"]
    cparams = harness.arch(cfg).program.make_weights(
        cfg, harness.derive_seed(seed, "weights"))
    x = lattice.geometries(
        np.random.RandomState(harness.derive_seed(seed, "walkers")), 8,
        program.base_geometry(cfg), cfg["geometry"]["perturb"])
    _, sstd, _ = ref.committee_stats(
        ref.committee_forces(cparams, x, cfg, ref.F32), ref.F32)
    assert float(np.max(sstd)) < spec["traffic"]["std_threshold"]


def run(loop, seed):
    spec = copy.deepcopy(harness.cell_spec(CELLS[loop]))
    spec["cfg"] = copy.deepcopy(PAINN_TINY)
    spec["traffic"].update(TINY_TRAFFIC[loop])
    return harness.run_spec(spec, seed, 0.3, False, impl="xla",
                            require_tpu=False, compile_cache=False,
                            keep_state=True)


@pytest.mark.parametrize("loop", ["exchange", "train"])
def test_painn_is_correct_and_its_control_is_not(loop):
    result, _, st, ctx = run(loop, 2 ** 31 + 11)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert result["diagnostics"]["compiles_in_window"] == 0
    names = {m["name"] for m in harness.cell_spec(CELLS[loop])["end_to_end"]}
    assert set(result["metrics"]) == names
    mod = harness.load_module(os.path.join(HERE, "loops", loop + ".py"),
                              "loop_test_painn_" + loop)
    limits = harness.cell_spec(CELLS[loop])["limits"]
    control = mod.readings(ctx, st, dtype=ref.BF16, limits=limits)
    assert any(control[k] > limits[k] for k in limits), control
