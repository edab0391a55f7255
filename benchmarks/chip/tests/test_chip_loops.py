"""Each traffic loop of the on-chip benchmark, driven on the CPU at a tiny
size through the harness (set-up, a short window, the check), with the
look for a chip skipped: a sound run is ``correct``; the control (the
reference in bfloat16 in the program's place) and each planted fault of
``faults.py`` are not.  The same holds for a toy second architecture
(``data/pair_mlp.py``), which enters through files alone."""
import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import faults  # noqa: E402
import harness  # noqa: E402
import reference as ref  # noqa: E402

DATA = os.path.join(HERE, "tests", "data")
TINY_CFG = dict(model="mlp_potential", n_atoms=8, committee_size=4,
                hidden=[16, 16], n_rbf=16, r_cut=6.0,
                geometry=dict(lattice=[2, 2, 2], spacing=1.3, perturb=0.05))
TINY_TRAFFIC = {"exchange": dict(walkers=16, warmup_steps=4, std_threshold=0.002),
                "train": dict(replay_rows=64, batch=4, train_steps=5)}
CELLS = {"exchange": "fleet.mlp-pot-ani1x-widths",
         "train": "train.mlp-pot-ani1x-widths"}
TOY_CFG = dict(name="pair-mlp-toy", model="pair_mlp", n_atoms=8,
               committee_size=4, n_basis=12, width=16, r_cut=4.0,
               dtype="float32",
               geometry=dict(lattice=[2, 2, 2], spacing=1.3, perturb=0.05))
# Every number compared in tiny runs of the loops, as the commit before
# the architecture moved into ``archs/`` read them: the move changes none.
EXACT = dict(mask_flips_off_threshold=0.0, react_mismatch=0.0,
             selected_mismatch=0.0)
BEFORE = {
    ("exchange", 2 ** 31 + 11): dict(
        advance_gap=1.0331900312535852e-08,
        force_gap=2.0315412281103803e-07,
        std_gap=1.4032526307929815e-07,
        budget_gap=1.2225760745966658e-08, **EXACT),
    ("exchange", 5): dict(
        advance_gap=7.831383423279383e-08,
        force_gap=2.3301960867774345e-07,
        std_gap=1.3615429238552105e-07,
        budget_gap=4.844804652309668e-08, **EXACT),
    ("train", 2 ** 31 + 11): dict(
        loss_gap=9.057550133766429e-08,
        grad_gap=4.048967835170644e-08,
        update_gap=4.1795635479425536e-07),
    ("train", 5): dict(
        loss_gap=1.3382126350290005e-07,
        grad_gap=5.896969886995745e-08,
        update_gap=3.1506779000134165e-07),
}


def tiny_spec(loop, cfg=None):
    spec = copy.deepcopy(harness.cell_spec(CELLS[loop]))
    if cfg is None:
        spec["cfg"].update(TINY_CFG)
    else:
        spec["cfg"] = copy.deepcopy(cfg)
    spec["traffic"].update(TINY_TRAFFIC[loop])
    return spec


def run(loop, seed, keep_state=False, cfg=None):
    return harness.run_spec(tiny_spec(loop, cfg), seed, 0.3, False,
                            impl="xla", require_tpu=False,
                            compile_cache=False, keep_state=keep_state)


@pytest.mark.parametrize("loop", ["exchange", "train"])
def test_sound_run_is_correct_and_control_is_not(loop):
    check_sound_and_control(loop)


@pytest.mark.parametrize("loop", ["exchange", "train"])
def test_toy_architecture_is_correct_and_its_control_is_not(loop,
                                                            monkeypatch):
    monkeypatch.setattr(harness, "ARCHS", DATA)
    check_sound_and_control(loop, TOY_CFG)


@pytest.mark.parametrize("loop,seed", sorted(BEFORE))
def test_readings_are_those_before_the_move(loop, seed):
    _, readings, _, _ = run(loop, seed)
    assert {k: float(v) for k, v in readings.items()} == BEFORE[loop, seed]


def check_sound_and_control(loop, cfg=None):
    result, readings, st, ctx = run(loop, 2 ** 31 + 11, keep_state=True,
                                    cfg=cfg)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert result["diagnostics"]["compiles_in_window"] == 0
    names = {m["name"] for m in harness.cell_spec(CELLS[loop])["end_to_end"]}
    assert set(result["metrics"]) == names
    mod = harness.load_module(os.path.join(HERE, "loops", loop + ".py"),
                              "loop_test_" + loop)
    limits = harness.cell_spec(CELLS[loop])["limits"]
    control = mod.readings(ctx, st, dtype=ref.BF16, limits=limits)
    assert any(control[k] > limits[k] for k in limits), control


@pytest.mark.parametrize("loop", ["exchange", "train"])
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_not_correct(loop, fault):
    with faults.planted(fault, loop):
        result, _, _, _ = run(loop, 5)
    assert not result["correct"], (fault, result["checks"])
