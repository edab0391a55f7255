"""Each traffic loop of the on-chip benchmark, driven on the CPU at a tiny
size through the harness (set-up, a short window, the check), with the
look for a chip skipped: a sound run is ``correct``; the control (the
reference in bfloat16 in the program's place) and each planted fault of
``faults.py`` are not."""
import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import faults  # noqa: E402
import harness  # noqa: E402
import reference as ref  # noqa: E402

TINY_CFG = dict(n_atoms=8, committee_size=4, hidden=[16, 16], n_rbf=16,
                r_cut=6.0,
                geometry=dict(lattice=[2, 2, 2], spacing=1.3, perturb=0.05))
TINY_TRAFFIC = {"exchange": dict(walkers=16, warmup_steps=4, std_threshold=0.002),
                "train": dict(replay_rows=64, batch=4, train_steps=5)}
CELLS = {"exchange": "fleet.mlp-pot-ani1x-widths",
         "train": "train.mlp-pot-ani1x-widths"}


def tiny_spec(loop):
    spec = copy.deepcopy(harness.cell_spec(CELLS[loop]))
    spec["cfg"].update(TINY_CFG)
    spec["traffic"].update(TINY_TRAFFIC[loop])
    return spec


def run(loop, seed, keep_state=False):
    return harness.run_spec(tiny_spec(loop), seed, 0.3, False, impl="xla",
                            require_tpu=False, compile_cache=False,
                            keep_state=keep_state)


@pytest.mark.parametrize("loop", ["exchange", "train"])
def test_sound_run_is_correct_and_control_is_not(loop):
    result, readings, st, ctx = run(loop, 2 ** 31 + 11, keep_state=True)
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
