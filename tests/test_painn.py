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


# ---------------------------------------------- the filter-first order
PUBLISHED = painn.PaiNNConfig()                 # F=128, 3 blocks, R=20


def dense_filter_energy(params, coords, species, cfg):
    """The order the model replaced: one (A, A, 3F x blocks) filter
    W_ij = (rbf(d_ij) W_f + b_f) f_c(d_ij) for every pair, multiplied by
    phi_j and summed over j, elementwise."""
    f, a = cfg.features, coords.shape[0]
    r = coords.T[:, None, :] - coords.T[:, :, None]       # x_j - x_i
    self_pair = jnp.eye(a, dtype=bool)
    d = jnp.sqrt(jnp.where(self_pair, 1.0, jnp.sum(r * r, axis=0)))
    n = jnp.arange(1, cfg.n_rbf + 1, dtype=d.dtype)
    rbf = jnp.sin(d[..., None] * (n * jnp.pi / cfg.r_cut)) / d[..., None]
    fcut = jnp.where(~self_pair & (d < cfg.r_cut),
                     0.5 * (jnp.cos(jnp.pi * d / cfg.r_cut) + 1.0), 0.0)
    filt = (rbf @ params["filter"]["w"] + params["filter"]["b"]) \
        * fcut[..., None]
    s = params["embed"][species]
    v = jnp.zeros((3,) + s.shape, s.dtype)
    for b, blk in enumerate(params["blocks"]):
        x = painn._mlp2(blk["phi"], s)[None] \
            * filt[:, :, 3 * f * b:3 * f * (b + 1)]
        x_s, x_vs, x_vv = jnp.split(x, 3, axis=-1)
        s = s + jnp.sum(x_s, axis=1)
        v = v + jnp.sum(x_vv * v[:, None] + x_vs * (r / d)[..., None], axis=2)
        s, v = painn._update(blk, s, v)
    return jnp.sum(painn._mlp2(params["readout"], s))


@pytest.fixture(scope="module")
def published():
    """Members at the published widths, the filter layer at 0.15 of the
    others' scale (as in the on-chip benchmark's PaiNN configuration), so
    that three blocks of ~59-neighbour sums stay O(1), and 64 atoms of
    H, C, N and O."""
    params = init(PUBLISHED, 5)
    params["filter"] = jax.tree.map(lambda w: w * 0.15 / 0.85,
                                    params["filter"])
    return params, jnp.asarray(np.arange(64) % 4 * 2 + 1)


def lattice(seed):
    """4 x 4 x 4 sites 1.3 apart, each moved by N(0, 0.05^2)."""
    grid = np.stack(np.meshgrid(*[np.arange(4)] * 3, indexing="ij"),
                    -1).reshape(64, 3)
    return jnp.asarray(1.3 * grid + 0.05 * np.random.RandomState(seed)
                       .randn(64, 3), jnp.float32)


def dense_filter_forces(params, x, species, cfg):
    e, g = jax.value_and_grad(dense_filter_energy, argnums=1)(
        params, x, species, cfg)
    return e, -g


@pytest.mark.parametrize("seed", [0, 1])
def test_neighbour_first_energy_and_forces_match_the_filter_first(
        published, seed):
    """The same sums in another order, in float32: they agree to
    round-off, 1e-5 of the energy and of the largest force."""
    params, species = published
    x = lattice(seed)
    with jax.default_matmul_precision("highest"):
        e, f = jax.jit(painn.energy_forces, static_argnums=3)(
            params, x, species, PUBLISHED)
        e_d, f_d = jax.jit(dense_filter_forces, static_argnums=3)(
            params, x, species, PUBLISHED)
    assert float(jnp.abs(f_d).max()) > 0.1
    np.testing.assert_allclose(e, e_d, rtol=1e-5)
    np.testing.assert_allclose(f, f_d, rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(f_d).max()))


@pytest.mark.parametrize("seed", [0, 1])
def test_neighbour_first_force_loss_gradient_matches_the_filter_first(
        published, seed):
    """The trainer's route: the parameter gradient of a force loss
    differentiates the forces once more.  Each leaf agrees with the
    filter-first order's to 1e-4 of its norm."""
    params, species = published
    x = lattice(seed)
    target = jnp.asarray(np.random.RandomState(10 + seed).randn(64, 3),
                         jnp.float32)

    def loss(forces):
        return jax.jit(jax.grad(lambda p: jnp.mean(
            (forces(p, x, species, PUBLISHED)[1] - target) ** 2)))(params)

    with jax.default_matmul_precision("highest"):
        got, want = loss(painn.energy_forces), loss(dense_filter_forces)
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        w_norm = float(jnp.linalg.norm(w))
        assert float(jnp.linalg.norm(g - w)) <= 1e-4 * w_norm, \
            jax.tree_util.keystr(path)
