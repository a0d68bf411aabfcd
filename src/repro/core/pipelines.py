"""Content-addressed processing pipelines (paper §2.3).

The paper runs 16 black-box Singularity pipelines (FreeSurfer, PreQual,
SLANT, UNesT, ...). Here a pipeline is a pure-JAX function plus a canonical
config; its SHA-256 digest plays the role of the container image digest —
same digest => byte-reproducible outputs. Three representative neuroimaging
stages are implemented in JAX (the paper's compute is the pipeline *content*;
the contribution is the orchestration around it):

  * bias_correct — N4-style low-order polynomial bias-field estimation
  * affine_register — gradient-descent affine registration to an atlas
  * segment_unest — UNesT-like patch-transformer tissue segmentation
    (backbone = configs/paper_unest.py)
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from pathlib import Path
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import trace


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    name: str
    version: str
    required_suffixes: Sequence[str]       # e.g. ("T1w",) or ("T1w", "dwi")
    config: Dict[str, object]

    def digest(self) -> str:
        blob = json.dumps({"name": self.name, "version": self.version,
                           "config": self.config}, sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]


class Pipeline:
    """A stage: ``fn`` maps input arrays to output arrays, which may stay
    on the device; :meth:`run` is the host boundary that returns numpy."""

    def __init__(self, spec: PipelineSpec,
                 fn: Callable[[Dict[str, np.ndarray]], Dict[str, object]]):
        self.spec = spec
        self.fn = fn

    @property
    def name(self) -> str:
        return self.spec.name

    def digest(self) -> str:
        return self.spec.digest()

    def run(self, inputs: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """``fn``, then its outputs copied to the host (``stage.to_host``
        also waits for the device where ``fn`` returns before its outputs
        are ready)."""
        with trace.span("stage.call", stage=self.name):
            out = self.fn(inputs)
        with trace.span("stage.to_host", stage=self.name,
                        bytes=sum(getattr(v, "nbytes", 0)
                                  for v in out.values())):
            return {k: np.asarray(v) for k, v in out.items()}


# ---------------------------------------------------------------------------
# bias-field correction (N4-style)
# ---------------------------------------------------------------------------
# The geometric and least-squares contractions below are a handful of terms
# wide, so they run at HIGHEST precision: at the TPU's default one-pass
# bfloat16 a [-1, 1] grid coordinate loses ~2^-9 of its value, a quarter of
# a voxel across a 256-voxel axis.
HIGHEST = jax.lax.Precision.HIGHEST
# Relative singular-value cut-off of the polynomial fit. lstsq's default,
# eps * max(M, N), exceeds the basis' smallest singular-value ratio (~0.22)
# beyond ~1.9M voxels, where it zeroed the whole fit at acquisition size.
LSTSQ_RCOND = 1e-5


def _poly_basis(shape, order):
    grids = [jnp.linspace(-1, 1, s) for s in shape]
    gx, gy, gz = jnp.meshgrid(*grids, indexing="ij")
    basis = []
    for i in range(order + 1):
        for j in range(order + 1 - i):
            for k in range(order + 1 - i - j):
                basis.append(gx ** i * gy ** j * gz ** k)
    return jnp.stack(basis, -1)                      # (X,Y,Z,nb)


@jax.jit
def _fit_bias(logv, basis):
    A = basis.reshape(-1, basis.shape[-1])
    b = logv.reshape(-1)
    coef, *_ = jnp.linalg.lstsq(A, b, rcond=LSTSQ_RCOND)
    return jnp.matmul(A, coef, precision=HIGHEST).reshape(logv.shape)


def _bias_correct_fn(inputs):
    vol = jnp.asarray(inputs["T1w"], jnp.float32)
    logv = jnp.log(jnp.clip(vol, 1e-3))
    basis = _poly_basis(vol.shape, order=2)
    field = _fit_bias(logv - jnp.mean(logv), basis)
    corrected = jnp.exp(logv - field)
    return {"T1w_biascorr": corrected, "bias_field": jnp.exp(field)}


# ---------------------------------------------------------------------------
# affine registration to a synthetic atlas
# ---------------------------------------------------------------------------

def _affine_grid(shape, theta):
    """theta: (3,4) affine. Returns warped sampling coords (X,Y,Z,3) in voxels."""
    grids = [jnp.linspace(-1, 1, s) for s in shape]
    gx, gy, gz = jnp.meshgrid(*grids, indexing="ij")
    coords = jnp.stack([gx, gy, gz, jnp.ones_like(gx)], -1)     # (X,Y,Z,4)
    warped = jnp.einsum("xyzk,jk->xyzj", coords, theta,
                        precision=HIGHEST)                      # (X,Y,Z,3)
    scale = (jnp.array(shape, jnp.float32) - 1) / 2
    return (warped + 1) * scale


def _trilinear(vol, coords):
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


def _register_fn(inputs, steps=60, lr=5e-3):
    moving = jnp.asarray(inputs["T1w"], jnp.float32)
    moving = (moving - moving.mean()) / (moving.std() + 1e-6)
    # synthetic atlas: centered sphere intensity prior
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


# ---------------------------------------------------------------------------
# UNesT-like segmentation (transformer backbone over 3D patches)
# ---------------------------------------------------------------------------

def _segment_fn(inputs, n_classes=4, patch=4, seed=0):
    from ..configs import get_config
    from ..models import init_params
    from ..models.model import _txf_stack, rmsnorm

    vol = jnp.asarray(inputs["T1w"], jnp.float32)
    X, Y, Z = vol.shape
    cfg = get_config("paper-unest").reduced(vocab_size=max(n_classes, 8))
    params = init_params(cfg, jax.random.PRNGKey(seed))
    px, py, pz = X // patch, Y // patch, Z // patch
    patches = vol[:px * patch, :py * patch, :pz * patch] \
        .reshape(px, patch, py, patch, pz, patch) \
        .transpose(0, 2, 4, 1, 3, 5).reshape(px * py * pz, patch ** 3)
    patches = (patches - patches.mean()) / (patches.std() + 1e-6)
    proj = jax.random.normal(jax.random.PRNGKey(seed + 1),
                             (patch ** 3, cfg.d_model)) / patch ** 1.5
    x = (patches @ proj)[None]                       # (1, npatch, D)
    x, _, _ = _txf_stack(cfg, params, x.astype(jnp.bfloat16),
                         jnp.arange(x.shape[1]), None,
                         remat=False, collect_cache=False)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x,
                        params["lm_head"].astype(x.dtype))[0, :, :n_classes]
    seg = jnp.argmax(logits, -1).reshape(px, py, pz)
    seg_full = jnp.repeat(jnp.repeat(jnp.repeat(seg, patch, 0), patch, 1), patch, 2)
    return {"segmentation": seg_full.astype(jnp.int32),
            "class_logits": logits.astype(jnp.float32)}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def builtin_pipelines() -> Dict[str, Pipeline]:
    return {
        # 1.1: the fit's rcond and HIGHEST-precision contractions changed
        # what bias_correct and affine_register compute, so the new digest
        # re-plans every derivative an older version committed
        "bias_correct": Pipeline(
            PipelineSpec("bias_correct", "1.1", ("T1w",), {"order": 2}),
            _bias_correct_fn),
        "affine_register": Pipeline(
            PipelineSpec("affine_register", "1.1", ("T1w",),
                         {"steps": 60, "lr": 5e-3}),
            _register_fn),
        "segment_unest": Pipeline(
            PipelineSpec("segment_unest", "1.0", ("T1w",),
                         {"n_classes": 4, "patch": 4}),
            _segment_fn),
        "dwi_prequal": Pipeline(
            PipelineSpec("dwi_prequal", "1.0", ("T1w", "dwi"),
                         {"denoise": "pca"}),
            lambda inputs: {
                "dwi_denoised": _pca_denoise(np.asarray(inputs["dwi"]))}),
    }


def _pca_denoise(dwi: np.ndarray, keep: int = 3) -> np.ndarray:
    """MP-PCA-flavoured denoising: truncated SVD over the volume dimension."""
    X, Y, Z, V = dwi.shape
    flat = dwi.reshape(-1, V).astype(np.float32)
    mu = flat.mean(0)
    u, s, vt = np.linalg.svd(flat - mu, full_matrices=False)
    s[keep:] = 0.0
    return ((u * s) @ vt + mu).reshape(X, Y, Z, V)
