"""PaiNN (``repro.models.painn``): the symmetries its equations promise,
at a small size with seeded random weights, and the published member's
parameter count.  Its agreement with the plain reference of the on-chip
benchmark is tested beside that reference (benchmarks/chip/tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import painn

CFG = painn.PaiNNConfig(features=16, n_blocks=2, n_rbf=8, r_cut=5.0)
SPECIES = jnp.array([6, 1, 1, 8, 7, 1, 6, 1])


def init(cfg, seed, scale=0.85):
    shapes = painn.param_shapes(cfg)
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        jax.random.normal(k, s) * scale / np.sqrt(s[0])
        for k, s in zip(keys, leaves)])


@pytest.fixture(scope="module")
def setup():
    rng = np.random.RandomState(0)
    grid = np.stack(np.meshgrid(*[np.arange(2)] * 3, indexing="ij"),
                    -1).reshape(8, 3)
    x = jnp.asarray(1.3 * grid + 0.1 * rng.randn(8, 3), jnp.float32)
    return init(CFG, 1), x


def rotation(seed):
    q, r = np.linalg.qr(np.random.RandomState(seed).randn(3, 3))
    q = q * np.sign(np.diag(r))
    if np.linalg.det(q) < 0:
        q[:, 0] = -q[:, 0]
    return jnp.asarray(q, jnp.float32)


# float32 through two blocks of 7-neighbour sums: the same structure in
# another frame or order sums in another order, so the results agree to
# a few ulps of the largest value, not bit for bit
RTOL, ATOL = 1e-4, 1e-4


@pytest.mark.parametrize("seed", [0, 1])
def test_energy_is_invariant_and_forces_rotate(setup, seed):
    params, x = setup
    rot = rotation(seed)
    with jax.default_matmul_precision("highest"):
        e, f = painn.energy_forces(params, x, SPECIES, CFG)
        e_r, f_r = painn.energy_forces(params, x @ rot.T + 0.7, SPECIES,
                                       CFG)
    assert np.isfinite(float(e)) and float(jnp.abs(f).max()) > 1e-3
    np.testing.assert_allclose(e_r, e, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(f_r, f @ rot.T, rtol=RTOL,
                               atol=ATOL * float(jnp.abs(f).max()))


def test_permuting_atoms_permutes_forces(setup):
    params, x = setup
    perm = np.random.RandomState(3).permutation(8)
    with jax.default_matmul_precision("highest"):
        e, f = painn.energy_forces(params, x, SPECIES, CFG)
        e_p, f_p = painn.energy_forces(params, x[perm], SPECIES[perm], CFG)
    np.testing.assert_allclose(e_p, e, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(f_p, f[perm], rtol=RTOL,
                               atol=ATOL * float(jnp.abs(f).max()))


def test_atoms_beyond_the_cutoff_do_not_interact(setup):
    """Two clusters farther apart than r_cut: each one's forces are those
    it has alone."""
    params, x = setup
    far = jnp.concatenate([x[:4], x[4:] + jnp.array([20.0, 0.0, 0.0])])
    with jax.default_matmul_precision("highest"):
        _, f = painn.energy_forces(params, far, SPECIES, CFG)
        _, f_a = painn.energy_forces(params, x[:4], SPECIES[:4], CFG)
    np.testing.assert_allclose(f[:4], f_a, rtol=1e-5, atol=1e-6)


def test_published_member_has_577537_parameters():
    """F=128, 3 blocks, 20 radial functions, readout F -> F/2 -> 1, an
    embedding of the 10 atomic numbers below 10."""
    shapes = painn.param_shapes(painn.PaiNNConfig())
    sizes = [int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple))]
    assert sum(sizes) == 577_537
