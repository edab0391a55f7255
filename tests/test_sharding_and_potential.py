"""Sharding-rule resolution (divisibility fallback, axis reuse) and the
paper's-own-domain potential model (descriptor invariances, force
consistency)."""
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import base as ax
from repro.configs.pal_potential import PotentialConfig
from repro.models import potential as pot
from repro.sharding.rules import MeshRules, merged_rules


class FakeMesh:
    """MeshRules only touches .shape for pspec resolution."""

    def __init__(self, shape):
        self.shape = dict(shape)


def _rules(mesh_shape, overrides=None):
    return MeshRules(FakeMesh(mesh_shape), overrides)


# ---------------------------------------------------------------------------
# rule resolution
# ---------------------------------------------------------------------------


def test_basic_tp_resolution():
    r = _rules({"data": 16, "model": 16})
    spec = r.pspec((ax.EMBED, ax.MLP), dims=(1024, 4096), name="wi")
    assert spec == P(None, "model")
    assert not r.fallbacks


def test_divisibility_fallback_drops_axis():
    r = _rules({"data": 16, "model": 16})
    # minicpm: 36 heads don't divide 16
    spec = r.pspec((ax.EMBED, ax.HEADS, ax.HEAD_DIM), dims=(2304, 36, 64))
    assert spec == P(None, None, None)
    assert len(r.fallbacks) == 1
    assert "36 % 16" in r.fallbacks[0].reason


def test_mesh_axis_reuse_fallback():
    r = _rules({"data": 16, "model": 16},
               {ax.SEQ: ("model",)})
    # seq takes 'model' first; heads then falls back
    spec = r.pspec((ax.BATCH, ax.SEQ, ax.HEADS, ax.HEAD_DIM),
                   dims=(256, 4096, 32, 128))
    assert spec == P("data", "model", None, None)
    assert any("mesh axis reuse" in f.reason for f in r.fallbacks)


def test_missing_mesh_axis_is_dropped():
    r = _rules({"data": 16, "model": 16})   # no 'pod' on single-pod mesh
    spec = r.pspec((ax.BATCH, None), dims=(256, 128))
    assert spec == P("data", None)
    r2 = _rules({"pod": 2, "data": 16, "model": 16})
    spec2 = r2.pspec((ax.BATCH, None), dims=(256, 128))
    assert spec2 == P(("pod", "data"), None)


def test_batch_one_falls_back_unsharded():
    r = _rules({"data": 16, "model": 16})
    spec = r.pspec((ax.BATCH, ax.CACHE_SEQ), dims=(1, 524288))
    assert spec == P(None, None)          # default cache_seq unsharded
    r2 = _rules({"data": 16, "model": 16}, {ax.CACHE_SEQ: ("data",)})
    spec2 = r2.pspec((ax.BATCH, ax.CACHE_SEQ), dims=(1, 524288))
    assert spec2 == P(None, "data")       # long_500k override


def test_merged_rules_override_order():
    rules = merged_rules({ax.EXPERTS: ()}, {ax.EXPERTS: ("model",)})
    assert rules[ax.EXPERTS] == ("model",)


# ---------------------------------------------------------------------------
# potential model (the paper's own domain)
# ---------------------------------------------------------------------------

CFG = PotentialConfig(n_atoms=6, committee_size=3, hidden=(32,), n_rbf=16)


def _coords(seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(6, 3) * 1.4)


def test_descriptor_translation_invariant():
    c = _coords()
    d1 = pot.descriptors(c, CFG)
    d2 = pot.descriptors(c + 5.0, CFG)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-5)


def test_descriptor_rotation_invariant():
    c = _coords()
    theta = 0.7
    R = jnp.asarray([[np.cos(theta), -np.sin(theta), 0],
                     [np.sin(theta), np.cos(theta), 0],
                     [0, 0, 1.0]])
    d1 = pot.descriptors(c, CFG)
    d2 = pot.descriptors(c @ R.T, CFG)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-4)


def test_descriptor_permutation_equivariant():
    c = _coords()
    perm = np.array([2, 0, 1, 5, 4, 3])
    d1 = pot.descriptors(c, CFG)
    d2 = pot.descriptors(c[perm], CFG)
    np.testing.assert_allclose(np.asarray(d1[perm]), np.asarray(d2),
                               atol=1e-5)


def test_energy_invariant_forces_equivariant():
    params = pot.init(CFG, jax.random.PRNGKey(0))
    c = _coords()
    e1, f1 = pot.energy_forces(params, c, CFG)
    e2, f2 = pot.energy_forces(params, c + 3.0, CFG)
    assert float(e1) == pytest.approx(float(e2), abs=1e-4)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=1e-4)
    # translation invariance => forces sum to ~0
    np.testing.assert_allclose(np.asarray(f1.sum(0)), 0.0, atol=1e-4)


def test_lj_forces_match_finite_difference():
    c = _coords(1)
    e, f = pot.lj_energy_forces(c)
    eps = 1e-4
    for i, j in [(0, 0), (2, 1), (5, 2)]:
        cp = c.at[i, j].add(eps)
        cm = c.at[i, j].add(-eps)
        fd = -(pot.lennard_jones(cp) - pot.lennard_jones(cm)) / (2 * eps)
        assert float(f[i, j]) == pytest.approx(float(fd), rel=2e-2, abs=1e-3)


def test_committee_disagreement_nonzero_for_different_members():
    cp = pot.init_committee(CFG, jax.random.PRNGKey(0))
    e, f = pot.committee_energy_forces(cp, _coords(), CFG)
    assert e.shape == (3,)
    assert float(jnp.std(e)) > 0


def test_potential_loss_decreases_under_training():
    from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

    params = pot.init(CFG, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    # well-separated geometries: perturbed lattice (overlapping atoms make
    # the LJ labels blow up and the fit meaningless)
    lattice = np.stack(np.meshgrid([0, 1.3], [0, 1.3], [0, 1.3]),
                       -1).reshape(-1, 3)[:6]
    coords = jnp.asarray(lattice[None] + rng.randn(16, 6, 3) * 0.08)
    e, f = jax.vmap(pot.lj_energy_forces)(coords)
    batch = {"coords": coords, "energy": e, "forces": f}
    state = adamw_init(params)
    cfg_o = AdamWConfig(weight_decay=0.0)

    @jax.jit
    def step(params, state):
        (l, m), g = jax.value_and_grad(
            pot.potential_loss, has_aux=True)(params, batch, CFG)
        p2, s2 = adamw_update(g, state, params, jnp.float32(3e-3), cfg_o)
        return p2, s2, l

    losses = []
    for _ in range(60):
        params, state, l = step(params, state)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.5


# ---------------------------------------------------------------------------
# the descriptor's hand-written JVP against autodiff of the same descriptor
# ---------------------------------------------------------------------------

JVP_CFG = PotentialConfig(n_atoms=8, committee_size=4, hidden=(32, 16),
                          n_rbf=24)


def _autodiff_descriptors(coords, cfg):
    """The descriptor as written before its JVP: differentiated by
    autodiff, which follows the product rule term by term."""
    d = pot._pair_distances(coords)
    centers = jnp.linspace(0.5, cfg.r_cut, cfg.n_rbf)
    gamma = (cfg.n_rbf / cfg.r_cut) ** 2
    rbf = jnp.exp(-gamma * (d[..., None] - centers) ** 2)
    fcut = 0.5 * (jnp.cos(jnp.pi * jnp.clip(d / cfg.r_cut, 0, 1)) + 1.0)
    return jnp.sum(rbf * fcut[..., None], axis=1)


def _structures(seed, n, split=0.0):
    """n perturbed 2x2x2 lattices of 8 atoms, spacing 1.3; ``split``
    moves the four atoms at y = 1.3 a further ``split`` along y."""
    rng = np.random.RandomState(seed)
    lat = np.stack(np.meshgrid([0, 1.3], [0, 1.3], [0, 1.3]),
                   -1).reshape(-1, 3)
    lat[4:, 1] += split
    return jnp.asarray(lat[None] + rng.randn(n, 8, 3) * 0.1,
                       dtype=jnp.float32)


def _member_forces(p, coords):
    """(n, A, 3) -> (n, A, 3) forces of one member."""
    return jax.vmap(lambda c: pot.energy_forces(p, c, JVP_CFG)[1])(coords)


def _force_loss_grad(member_axis):
    """Gradient of a force loss with respect to each member's parameters
    under the committee vmap: coordinates shared by the members (the
    fleet) or drawn per member (the trainer's bootstrap rows)."""
    def loss(p, coords, target):
        return jnp.mean((_member_forces(p, coords) - target) ** 2)
    return jax.vmap(jax.grad(loss), in_axes=(0, member_axis, member_axis))


def _jvp_case(case):
    """The quantity ``case`` names, for the descriptor in the module."""
    cp = pot.init_committee(JVP_CFG, jax.random.PRNGKey(0))
    p0 = jax.tree.map(lambda a: a[0], cp)
    c = _structures(1, 1)[0]

    def energy(x):
        return pot.energy(p0, x, JVP_CFG)

    def forces(cp, x):
        return pot.committee_energy_forces(cp, x, JVP_CFG)[1]

    if case == "forces":
        fn, args = forces, (cp, c)
    elif case == "forces_beyond_cutoff":
        # the pairs across the split lie just beyond r_cut, in the tail
        # of the outermost Gaussians
        far = _structures(2, 1, split=JVP_CFG.r_cut - 1.1)[0]
        fn, args = forces, (cp, far)
    elif case == "force_loss_grad_shared":
        x = _structures(3, 5)
        fn, args = _force_loss_grad(None), (cp, x, jnp.ones_like(x))
    elif case == "force_loss_grad_per_member":
        x = _structures(4, 4 * 5).reshape(4, 5, 8, 3)
        fn, args = _force_loss_grad(0), (cp, x, jnp.ones_like(x))
    elif case == "energy_jvp":
        fn, args = (lambda x, t: jax.jvp(energy, (x,), (t,))), \
            (c, _structures(5, 1)[0])
    elif case == "energy_jacfwd":
        fn, args = jax.jacfwd(energy), (c,)
    elif case == "force_jacfwd":
        fn, args = jax.jacfwd(lambda x: forces(cp, x)), (c,)
    else:
        raise ValueError(case)
    return jax.jit(fn)(*args)


@pytest.mark.parametrize("case", [
    "forces", "forces_beyond_cutoff", "force_loss_grad_shared",
    "force_loss_grad_per_member", "energy_jvp", "energy_jacfwd",
    "force_jacfwd"])
def test_descriptor_jvp_matches_autodiff(case):
    """Forces, the force loss's parameter gradient (second order, as the
    trainer takes it) and forward-mode derivatives through the
    hand-written JVP agree with autodiff of the same descriptor to 1e-5
    of the largest value of each leaf; self-pairs and pairs beyond the
    cutoff give finite values."""
    got = _jvp_case(case)
    with mock.patch.object(pot, "descriptors", _autodiff_descriptors):
        want = _jvp_case(case)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert np.all(np.isfinite(g))
        assert np.max(np.abs(g - w)) <= 1e-5 * np.max(np.abs(w)), case


def _hlo_instructions(text):
    """name -> (shape, op, rest of the line) of every instruction of an
    HLO module's text, fusion bodies included."""
    pat = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                     r"(\w[\w-]*)\((.*)$")
    out = {}
    for line in text.splitlines():
        m = pat.match(line)
        if m:
            dims = tuple(int(x) for x in m.group(2).split(",") if x)
            out[m.group(1)] = (dims, m.group(3), m.group(4))
    return out


def _operand_dims(ins, rest, i):
    name = re.findall(r"%([\w.\-]+)", rest)[i]
    return ins[name][0]


def _attr(rest, key):
    m = re.search(key + r"=\{([\d,]*)\}", rest)
    return [int(x) for x in m.group(1).split(",") if x] if m else []


def test_member_forces_contract_radial_axis_in_one_dot():
    """Under the committee vmap with shared coordinates (the fleet's
    ``in_axes=(0, None)``), the chain rule from descriptor to pair
    distances is one dot over the radial axis with the members free, and
    nothing reduces the radial axis of a pair tensor."""
    cfg = JVP_CFG
    n, a, k, r = 16, cfg.n_atoms, cfg.committee_size, cfg.n_rbf
    cp = pot.init_committee(cfg, jax.random.PRNGKey(0))
    x = _structures(6, n)
    text = jax.jit(jax.vmap(_member_forces, in_axes=(0, None))).lower(
        cp, x).compile().as_text()
    ins = _hlo_instructions(text)
    radial_dots, radial_reduces = [], []
    for name, (dims, op, rest) in ins.items():
        if op == "dot":
            lhs = _operand_dims(ins, rest, 0)
            contracted = [lhs[i] for i in _attr(rest, "lhs_contracting_dims")]
            if len(lhs) >= 4 and r in contracted:
                radial_dots.append(dims)
        elif op == "reduce":
            src = _operand_dims(ins, rest, 0)
            if len(src) >= 4 and any(src[i] == r
                                     for i in _attr(rest, "dimensions")):
                radial_reduces.append(name)
    assert len(radial_dots) == 1, radial_dots
    assert sorted(radial_dots[0]) == sorted((n, a, k, a))
    assert not radial_reduces


def test_partial_subset_fallback_keeps_usable_axes():
    """('model','data') with 'data' taken degrades to ('model',), not to
    replicated (the jamba dense-FFN 256-way sharding case)."""
    r = _rules({"data": 16, "model": 16}, {ax.MLP: ("model", "data")})
    spec = r.pspec((ax.BATCH, None, ax.MLP), dims=(32, 4096, 24576))
    assert spec == P("data", None, "model")
    # weights (no batch): both axes usable
    spec_w = r2 = _rules({"data": 16, "model": 16},
                         {ax.MLP: ("model", "data")}).pspec(
        (ax.EMBED, ax.MLP), dims=(8192, 24576))
    assert spec_w == P(None, ("model", "data"))
