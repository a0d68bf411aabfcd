"""affine_register compiles once per volume shape, computes what the
closure formulation it replaced computed, and samples with one gather a
voxel from a corner table, as the eight-gather sampler it replaced did."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import builtin_pipelines, trace
from repro.core.pipelines import (_affine_grid, _corner_table, _register,
                                  _trilinear)


def _eight_gather_trilinear(vol, coords):
    """The sampler the corner table replaced, frozen as the oracle: one
    gather of single voxels per corner, eight a sample."""
    x, y, z = coords[..., 0], coords[..., 1], coords[..., 2]
    x0, y0, z0 = (jnp.clip(jnp.floor(c).astype(jnp.int32), 0, s - 2)
                  for c, s in zip((x, y, z), vol.shape))
    dx, dy, dz = x - x0, y - y0, z - z0
    out = 0.0
    for ix, wx in ((x0, 1 - dx), (x0 + 1, dx)):
        for iy, wy in ((y0, 1 - dy), (y0 + 1, dy)):
            for iz, wz in ((z0, 1 - dz), (z0 + 1, dz)):
                out = out + vol[ix, iy, iz] * wx * wy * wz
    return out


@pytest.fixture()
def cold_register():
    """Start from no compiled program: no in-memory entry of ``_register``
    and no persistent cache, which could hand back a program another test
    wrote and so hide the first compile."""
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    _register.clear_cache()
    try:
        yield builtin_pipelines()["affine_register"].fn
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


def _smooth_volume(shape, seed):
    """An off-centre blob on a linear ramp plus low noise: smooth enough
    that the fit's gradient steps move the affine."""
    rng = np.random.default_rng(seed)
    axes = [np.linspace(-1, 1, s) for s in shape]
    gx, gy, gz = np.meshgrid(*axes, indexing="ij")
    c = rng.uniform(-0.2, 0.2, 3)
    blob = np.exp(-3 * ((gx - c[0]) ** 2 + (gy - c[1]) ** 2
                        + (gz - c[2]) ** 2))
    vol = 100 * blob + 10 * (1 + 0.2 * gx) + rng.normal(0, 0.5, shape)
    return vol.astype(np.float32)


def _backend_compiles():
    return trace.counters().get("compile.backend.n", 0)


def test_register_compiles_once_per_shape(cold_register):
    fn = cold_register
    grown = []
    for seed in range(3):
        before = _backend_compiles()
        out = fn({"T1w": _smooth_volume((24, 24, 16), seed)}, steps=2,
                 lr=5e-3)
        jax.block_until_ready(out)
        grown.append(_backend_compiles() - before)
    assert grown == [1, 0, 0]
    before = _backend_compiles()
    jax.block_until_ready(fn({"T1w": _smooth_volume((20, 24, 16), 3)},
                             steps=2, lr=5e-3))
    assert _backend_compiles() - before == 1


def _closure_register(inputs, steps=60, lr=5e-3):
    """The formulation ``_register`` replaced: normalisation and atlas run
    eagerly, and the step is a jit closed over both arrays."""
    moving = jnp.asarray(inputs["T1w"], jnp.float32)
    moving = (moving - moving.mean()) / (moving.std() + 1e-6)
    shape = moving.shape
    grids = [jnp.linspace(-1, 1, s) for s in shape]
    gx, gy, gz = jnp.meshgrid(*grids, indexing="ij")
    atlas = jnp.exp(-4 * (gx ** 2 + gy ** 2 + gz ** 2))
    atlas = (atlas - atlas.mean()) / (atlas.std() + 1e-6)

    def loss(theta):
        warped = _eight_gather_trilinear(moving, _affine_grid(shape, theta))
        return jnp.mean((warped - atlas) ** 2)

    theta = jnp.concatenate([jnp.eye(3), jnp.zeros((3, 1))], 1)
    g = jax.jit(jax.value_and_grad(loss))

    def body(theta, _):
        val, grad = g(theta)
        return theta - lr * grad, val
    theta, losses = jax.lax.scan(body, theta, jnp.arange(steps))
    warped = _eight_gather_trilinear(moving, _affine_grid(shape, theta))
    return {"T1w_reg": warped, "affine": theta, "reg_loss": losses}


@pytest.mark.parametrize("seed", [0, 1])
def test_register_agrees_with_closure_formulation(seed):
    """Both run the same float32 arithmetic; only XLA's fusion and the
    order of its reductions (mean, std, the loss's mean) differ, so each
    output moves by a few float32 roundings: about 1e-8 in the affine and
    3e-7 of the warp's range at this size. The affine's entries are of
    order 1, so 1e-5 is about a hundred float32 epsilons, yet a
    six-thousandth of what the ten steps move it (about 6e-2), so a
    changed step rule fails. A warped voxel moves by its coordinate's
    difference times the volume's slope: 1e-3 of the warp's range leaves
    that rounding a thousandfold room and stays twenty times inside the
    chip benchmark's warp limit (2e-2). The loss is a mean of squares of
    order 1, compared to 1e-5 of itself on the same reasoning."""
    vol = _smooth_volume((32, 28, 20), seed)
    got = builtin_pipelines()["affine_register"].fn({"T1w": vol}, steps=10,
                                                    lr=5e-3)
    want = _closure_register({"T1w": vol}, steps=10, lr=5e-3)
    got = {k: np.asarray(v) for k, v in got.items()}
    want = {k: np.asarray(v) for k, v in want.items()}
    assert np.abs(want["affine"] - np.eye(3, 4)).max() > 1e-4  # it moved
    np.testing.assert_allclose(got["affine"], want["affine"], rtol=0,
                               atol=1e-5)
    np.testing.assert_allclose(got["reg_loss"], want["reg_loss"], rtol=1e-5)
    span = float(np.ptp(want["T1w_reg"]))
    assert np.abs(got["T1w_reg"] - want["T1w_reg"]).max() <= 1e-3 * span


# Near the identity every sample lies inside the volume; the second affine
# scales by 1.3 and shifts by 0.4 of the half-width, so a large share of
# samples lies outside and the clip to the edge cells acts.
_AFFINES = {
    "near_identity": np.eye(3, 4) + np.array([[0.02, -0.03, 0.01, 0.05],
                                               [0.01, 0.03, -0.02, -0.04],
                                               [-0.02, 0.01, 0.02, 0.03]]),
    "outside": np.concatenate([1.3 * np.eye(3), 0.4 * np.ones((3, 1))], 1),
}


@pytest.mark.parametrize("affine", sorted(_AFFINES))
@pytest.mark.parametrize("shape", [(24, 20, 16), (31, 27, 19), (16, 33, 9)])
def test_corner_table_sampler_matches_eight_gathers(shape, affine):
    """The table holds the same float32 corner values, and the sampler
    sums the same eight products with the same weights in the same order;
    only XLA's fusion can reorder a rounding, which moves a sample by a
    few float32 epsilons of the volume's range (0 here on the CPU, 4.8e-7
    with other data), so 1e-6 of the range holds it on every backend,
    while two swapped corners move a sample by 5e-2 of the range or more.
    The gradient with respect to theta flows only through the weights,
    which are computed as before (it reads 0 apart here), so it is held to
    1e-6 of its largest entry on the same reasoning."""
    vol = jnp.asarray(_smooth_volume(shape, 5))
    target = jnp.asarray(_smooth_volume(shape, 6)[::-1])
    theta = jnp.asarray(_AFFINES[affine], jnp.float32)
    coords = _affine_grid(shape, theta)
    if affine == "outside":
        inside = np.all((np.asarray(coords) >= 0)
                        & (np.asarray(coords) <= np.array(shape) - 1), -1)
        assert inside.mean() < 0.5                # the clip acts
    table = _corner_table(vol)

    def new(th):
        return _trilinear(table, shape, _affine_grid(shape, th))

    def old(th):
        return _eight_gather_trilinear(vol, _affine_grid(shape, th))

    got, want = jax.jit(new)(theta), jax.jit(old)(theta)
    span = float(jnp.max(vol) - jnp.min(vol))
    assert float(jnp.abs(got - want).max()) <= 1e-6 * span

    def grad(sample):
        return jax.jit(jax.grad(
            lambda th: jnp.mean((sample(th) - target) ** 2) / span ** 2))(theta)
    g_new, g_old = grad(new), grad(old)
    assert float(jnp.abs(g_old).max()) > 0
    assert float(jnp.abs(g_new - g_old).max()) \
        <= 1e-6 * float(jnp.abs(g_old).max())


def test_register_gathers_once_per_sample():
    """One gather in the scan's body (the loss's sampler; its gradient
    reuses the gathered rows) and one in the final warp, and no scatter:
    the corner table is not differentiated. The eight-gather sampler held
    16 gathers here, so a return to it fails."""
    text = _register.lower(jnp.zeros((24, 20, 16), jnp.float32), 5e-3,
                           steps=2).as_text()
    assert text.count('"stablehlo.gather"(') == 2
    assert text.count('"stablehlo.scatter"(') == 0
