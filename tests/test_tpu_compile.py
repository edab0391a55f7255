"""Compile the main path's Pallas kernel for a TPU v5e that is described,
not attached.

The TPU compiler ships with jaxlib's TPU support and compiles for a chip
described by ``jax.experimental.topologies``: what it refuses here (block
shapes off the (8, 128) tiling, 1-D vector layouts, unpartitionable
kernels) it would refuse on the chip.  Nothing runs, so these tests say
nothing about results or times; interpret-mode parity lives in
tests/test_committee_uq.py.

The topology is described inside a module fixture, never at import, and
every compile happens in this process: only one process may load the TPU
library at a time, and it keeps it until it exits.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.pal_potential import PotentialConfig
from repro.core import acquisition as acq
from repro.kernels import committee_uq as cuq
from repro.models import potential as pot
from repro.sharding.rules import MeshRules, committee_shardings


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("K,n,d", [
    (4, 8, 24),        # PotentialConfig(): 8 atoms x 3 force components,
    (4, 256, 24),      # at the smallest engine bucket, a fleet bucket,
    (4, 1024, 24),     # and a multi-block bucket
    (8, 256, 1),       # energies: one output component
])
def test_committee_uq_compiles_for_v5e(one_chip, K, n, d):
    preds = jax.ShapeDtypeStruct((K, n, d), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda p: cuq.committee_uq(p, 0.05)).lower(
        preds).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _member_forces(p, flat_batch, cfg=PotentialConfig()):
    def one(flat):
        _, f = pot.energy_forces(p, flat.reshape(cfg.n_atoms, 3), cfg)
        return f.reshape(-1)
    return jax.vmap(one)(flat_batch)


@pytest.mark.parametrize("shape", [None, (4, 1), (1, 4)],
                         ids=["one_chip", "data4", "committee4"])
def test_fused_pallas_score_compiles_on_v5e_meshes(topo, shape):
    """The whole fused score dispatch (committee forward + Pallas UQ +
    rules) at PotentialConfig() widths, on one chip and on the (4, 1)
    data and (1, 4) committee meshes of a 2x2 host: the kernel must sit
    inside a shard_map there, because the compiler cannot partition it."""
    cfg = PotentialConfig()
    nb, d = 256, 3 * cfg.n_atoms
    cp_shape = jax.eval_shape(
        lambda: pot.init_committee(cfg, jax.random.PRNGKey(0)))
    cparams = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cp_shape)
    eng = acq.FusedEngine(_member_forces, cparams, 0.05, impl="pallas")
    if shape is None:
        one = SingleDeviceSharding(topo.devices[0])
        cp_sh = jax.tree.map(lambda _: one, cp_shape)
        x_sh = scalar_sh = one
    else:
        # the engine places its parameters itself; a described chip holds
        # no arrays, so hand it the mesh and pass shapes instead
        mesh = Mesh(np.array(topo.devices[:4]).reshape(shape),
                    ("data", "model"))
        eng.mesh = mesh
        eng._mesh_rules = MeshRules(mesh, None)
        cp_sh = committee_shardings(eng._mesh_rules, cp_shape)
        x_sh = eng._batch_sharding(nb)
        scalar_sh = NamedSharding(mesh, P())
    args = (
        jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), cp_shape, cp_sh),
        jax.ShapeDtypeStruct((nb, d), jnp.float32, sharding=x_sh),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar_sh),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar_sh),
        (),
    )
    text = eng._compiled_locked(nb).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the program and the kernel carry the names a device trace shows
    assert "HloModule jit_engine_score" in text
    assert re.search(r"%committee_uq[.\d]* = [^\n]*tpu_custom_call", text)


def test_fleet_member_forces_hold_under_one_pair_tensor(one_chip):
    """The fleet's member forces at the fleet cell's widths (the ANI-1x
    ensemble's 8 members, 384-160-128-96-1, 64 atoms, r_cut 5.2) for 64
    walkers, the committee sharing the walkers' coordinates: the
    descriptor's chain rule needs at most 1.5 f32 (N, A, A, n_rbf) pair
    tensors of temporaries.  Autodiff's product rule held two of them,
    one per branch, each contracted with the members' cotangents."""
    cfg = PotentialConfig(n_atoms=64, committee_size=8, hidden=(160, 128, 96),
                          n_rbf=384, r_cut=5.2)
    n = 64
    cp_shape = jax.eval_shape(
        lambda: pot.init_committee(cfg, jax.random.PRNGKey(0)))
    cparams = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), cp_shape)
    x = jax.ShapeDtypeStruct((n, 3 * cfg.n_atoms), jnp.float32,
                             sharding=one_chip)
    forces = jax.vmap(lambda p, xb: _member_forces(p, xb, cfg),
                      in_axes=(0, None))
    compiled = jax.jit(forces).lower(cparams, x).compile()
    pair_tensor = 4 * n * cfg.n_atoms * cfg.n_atoms * cfg.n_rbf
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 1.5 * pair_tensor, temp / pair_tensor


def test_painn_fleet_step_fits_with_vectors_off_the_minor_tiles(one_chip):
    """The whole fused fleet step (``jit_engine_step_score``) of the
    benchmark's PaiNN fleet cell: 8 members at the published widths (F=128,
    3 blocks, 20 radial functions), 64 atoms, the cell's walker count.
    Its temporaries leave at least a tenth of the v5e's 16 GB free, the
    rule the cell's walker count was chosen by.  No buffer that carries
    the feature axis or a pair of atom axes holds the spatial axis among
    its two minor (tiled) dimensions, where 3 would pad to 8 sublanes or
    128 lanes."""
    import json

    from repro.exploration.fleet import FleetConfig, WalkerFleet
    from repro.models import painn

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "chip", "traffic",
                           "fleet-mem.json")) as f:
        n = json.load(f)["walkers"]
    cfg = painn.PaiNNConfig()
    k, a, f = 8, 64, cfg.features
    species = jnp.asarray(np.arange(a) % 4 * 2 + 1)     # H, N, ...

    def member_forces(p, flat_batch):
        return jax.vmap(lambda x: painn.energy_forces(
            p, x.reshape(a, 3), species, cfg)[1].reshape(-1))(flat_batch)

    shapes = painn.param_shapes(cfg)
    cparams = jax.tree.map(lambda s: jnp.zeros((k,) + s, jnp.float32),
                           shapes, is_leaf=lambda x: isinstance(x, tuple))
    eng = acq.FusedEngine(member_forces, cparams, 1.0, impl="pallas")
    fleet = WalkerFleet(eng, np.zeros((n, 3 * a), np.float32),
                        FleetConfig())
    step = eng._step_compiled_locked("fleet", fleet.nb, fleet._step_fn,
                                     fleet._react_fn)
    args = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v), jnp.asarray(v).dtype,
                                       sharding=one_chip),
        (cparams, fleet._carry, np.int32(n), np.int32(0), eng.rule_state))
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes <= 0.9 * 16e9
    text = compiled.as_text()
    assert "HloModule jit_engine_step_score" in text
    misplaced = []
    for dims, layout in re.findall(r"\[([\d,]+)\]\{([\d,]+)", text):
        dims = [int(d) for d in dims.split(",")]
        minor = [dims[int(i)] for i in layout.split(",")[:2]]
        pairs = any(x == y == a for x, y in zip(dims, dims[1:]))
        if 3 in minor and len(dims) > 2 and (f in dims or pairs):
            misplaced.append((dims, layout))
    assert not misplaced, misplaced[:5]
