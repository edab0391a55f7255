"""The four-chip fleet mix (``traffic/fleet-dp4.json``: the fleet of cell
``fleet.mlp-pot-ani1x-widths`` on a (4,1) data mesh, 1024 walkers per
chip), driven on four virtual CPU devices in a child process (the device
count is fixed when JAX starts): a sound run is ``correct``, and one with
the exchange between chips left out is not.  The mix has no cell in
``BENCHMARK.json`` until it is measured on four chips."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))

CHILD = r"""
import contextlib, copy, json, sys
sys.path.insert(0, sys.argv[1])
import faults, harness
name = "fleet-dp4.mlp-pot-ani1x-widths"
spec = copy.deepcopy(harness.cell_spec("fleet.mlp-pot-ani1x-widths"))
spec["cell"] = dict(spec["cell"], name=name, traffic="fleet-dp4", chips=4)
spec["traffic"] = harness.load_json(harness.HERE + "/traffic/fleet-dp4.json")
spec["limits"] = harness.load_json(harness.HERE + f"/limits/{name}.json")
spec["cfg"].update(n_atoms=8, committee_size=4, hidden=[16, 16], n_rbf=16,
                   geometry=dict(lattice=[2, 2, 2], spacing=1.3,
                                 perturb=0.05))
# a threshold low enough that the budget rule selects a few walkers on
# each chip: with none selected, every chip's rate is 0 and leaving the
# exchange out changes nothing
spec["traffic"].update(walkers=64, std_threshold=0.002)
out = {}
for fault in (None, "exchange_left_out"):
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


def test_mesh_cell_sound_and_exchange_left_out():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", CHILD, HERE], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["None"]["count"] == 4
    assert out["None"]["correct"], out["None"]["checks"]
    assert not out["exchange_left_out"]["correct"], out["exchange_left_out"]
