"""The four-chip fleet cell ``fleet-dp4.mlp-pot-ani1x-widths`` (the fleet
of cell ``fleet.mlp-pot-ani1x-widths`` on a (4,1) data mesh, 1024 walkers
per chip), driven at a tiny size on four virtual CPU devices in one child
process (the device count is fixed when JAX starts): a sound run is
``correct``, and one with the exchange between chips left out, or with
any other fault of ``faults.py`` planted, is not."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import faults  # noqa: E402

CHILD = r"""
import contextlib, copy, json, sys
sys.path.insert(0, sys.argv[1])
import faults, harness
spec = copy.deepcopy(harness.cell_spec("fleet-dp4.mlp-pot-ani1x-widths"))
assert spec["cell"]["chips"] == 4 and spec["traffic"]["uq_mesh"] == "4x1"
spec["cfg"].update(n_atoms=8, committee_size=4, hidden=[16, 16], n_rbf=16,
                   geometry=dict(lattice=[2, 2, 2], spacing=1.3,
                                 perturb=0.05))
# the budget rule has to select a few walkers on each chip (with none
# selected every chip's rate is 0 and leaving the exchange out changes
# nothing): at these widths the committee's std is 1.3-1.9, so the
# threshold starts above it and the controller comes down onto it within
# the warm-up, as the cell's does; started below, it winds up and selects
# nothing past the warm-up under some faults
spec["traffic"].update(walkers=64, std_threshold=3.0)
out = {}
for fault in (None,) + faults.FAULTS + faults.MESH_FAULTS:
    plant = faults.planted(fault, "exchange", 4) if fault \
        else contextlib.nullcontext()
    with plant:
        res, _, _, _ = harness.run_spec(spec, 91, 0.3, False, impl="xla",
                                        require_tpu=False,
                                        compile_cache=False)
    out[str(fault)] = {"correct": res["correct"], "checks": res["checks"],
                       "count": res["device"]["count"]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def out():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", CHILD, HERE], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mesh_cell_sound_and_exchange_left_out(out):
    assert out["None"]["count"] == 4
    assert out["None"]["correct"], out["None"]["checks"]
    assert not out["exchange_left_out"]["correct"], out["exchange_left_out"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_mesh_cell_planted_fault_is_not_correct(out, fault):
    assert not out[fault]["correct"], (fault, out[fault]["checks"])
