"""affine_register compiles once per volume shape, and computes what the
closure formulation it replaced computed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import builtin_pipelines, trace
from repro.core.pipelines import _affine_grid, _register, _trilinear


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
        warped = _trilinear(moving, _affine_grid(shape, theta))
        return jnp.mean((warped - atlas) ** 2)

    theta = jnp.concatenate([jnp.eye(3), jnp.zeros((3, 1))], 1)
    g = jax.jit(jax.value_and_grad(loss))

    def body(theta, _):
        val, grad = g(theta)
        return theta - lr * grad, val
    theta, losses = jax.lax.scan(body, theta, jnp.arange(steps))
    warped = _trilinear(moving, _affine_grid(shape, theta))
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
