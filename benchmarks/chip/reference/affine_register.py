"""Plain reference of ``affine_register``: gradient descent on a 3x4 affine
that warps the normalised scan onto a Gaussian atlas, by trilinear sampling.

float32, every contraction at HIGHEST precision, the whole fit one jitted
program. ``lowp=True`` is the control: the volumes in bfloat16 and the grid
contraction at HIGH (three passes), the step below what the stage states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

OUTPUTS = ("T1w_reg", "affine", "reg_loss")
# Largest |program - reference| / max |reference| per output; readings in
# PERF.md.
LIMITS = {"affine": 2e-4, "loss": 2e-4, "warp": 2e-2}


def _coords(shape, theta, precision):
    axes = [jnp.linspace(-1.0, 1.0, s, dtype=jnp.float32) for s in shape]
    gx, gy, gz = jnp.meshgrid(*axes, indexing="ij")
    hom = jnp.stack([gx, gy, gz, jnp.ones_like(gx)], axis=-1)
    mapped = jnp.einsum("xyzk,jk->xyzj", hom, theta, precision=precision)
    half = (jnp.asarray(shape, jnp.float32) - 1.0) / 2.0
    return (mapped + 1.0) * half


def _sample(vol, c):
    base = [jnp.clip(jnp.floor(c[..., a]).astype(jnp.int32), 0,
                     vol.shape[a] - 2) for a in range(3)]
    frac = [(c[..., a] - base[a]).astype(vol.dtype) for a in range(3)]
    out = jnp.zeros(c.shape[:-1], vol.dtype)
    for corner in range(8):
        bits = [(corner >> a) & 1 for a in range(3)]
        w = 1
        for a in range(3):
            w = w * (frac[a] if bits[a] else 1 - frac[a])
        out = out + vol[base[0] + bits[0], base[1] + bits[1],
                        base[2] + bits[2]] * w
    return out


def _standardise(v):
    return (v - v.mean()) / (v.std() + 1e-6)


@functools.partial(jax.jit, static_argnames=("steps", "lowp"))
def _fit(vol, lr, *, steps: int, lowp: bool):
    dtype = jnp.bfloat16 if lowp else jnp.float32
    prec = jax.lax.Precision.HIGH if lowp else jax.lax.Precision.HIGHEST
    shape = vol.shape
    moving = _standardise(vol.astype(jnp.float32)).astype(dtype)
    axes = [jnp.linspace(-1.0, 1.0, s, dtype=jnp.float32) for s in shape]
    gx, gy, gz = jnp.meshgrid(*axes, indexing="ij")
    atlas = _standardise(jnp.exp(-4.0 * (gx ** 2 + gy ** 2 + gz ** 2)))
    atlas = atlas.astype(dtype)

    def loss(theta):
        warped = _sample(moving, _coords(shape, theta, prec))
        return jnp.mean(jnp.square(warped.astype(jnp.float32)
                                   - atlas.astype(jnp.float32)))

    def step(theta, _):
        val, grad = jax.value_and_grad(loss)(theta)
        return theta - lr * grad, val

    theta0 = jnp.concatenate([jnp.eye(3, dtype=jnp.float32),
                              jnp.zeros((3, 1), jnp.float32)], axis=1)
    theta, losses = jax.lax.scan(step, theta0, None, length=steps)
    warped = _sample(moving, _coords(shape, theta, prec))
    return warped.astype(jnp.float32), theta, losses


def reference(inputs: dict, stage: dict, lowp: bool = False) -> dict:
    with jax.default_matmul_precision("highest"):
        warped, theta, losses = _fit(jnp.asarray(inputs["T1w"], jnp.float32),
                                     jnp.float32(stage["lr"]),
                                     steps=int(stage["steps"]), lowp=lowp)
    return {"T1w_reg": np.asarray(warped), "affine": np.asarray(theta),
            "reg_loss": np.asarray(losses)}


def _rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    scale = float(np.max(np.abs(want))) or 1.0
    return float(np.max(np.abs(got - want))) / scale


def gaps(got: dict, want: dict, stage: dict) -> dict:
    return {"affine": _rel(got["affine"], want["affine"]),
            "loss": _rel(got["reg_loss"], want["reg_loss"]),
            "warp": _rel(got["T1w_reg"], want["T1w_reg"])}
