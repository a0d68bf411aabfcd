"""Content-addressed processing pipelines (paper §2.3).

The paper runs 16 black-box Singularity pipelines (FreeSurfer, PreQual,
SLANT, UNesT, ...). Here a pipeline is a pure-JAX function plus a canonical
config; its SHA-256 digest plays the role of the container image digest —
same digest => byte-reproducible outputs. Three representative neuroimaging
stages are implemented in JAX (the paper's compute is the pipeline *content*;
the contribution is the orchestration around it):

  * bias_correct — N4-style low-order polynomial bias-field estimation
  * affine_register — gradient-descent affine registration to an atlas
  * segment_unest — a language-model-shaped transformer over 3D patches
    (backbone = configs/paper_unest.py), 4 tissue classes
  * unest_wholebrain — the published UNesT whole-brain model (133 labels,
    sliding 96³ windows; core/unest.py)
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
from pathlib import Path
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import trace, unest


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


def _corner_table(vol):
    """The volume's trilinear cells as rows: row ``(x0·(Y−1) + y0)·(Z−1)
    + z0`` holds the 8 corners of cell ``(x0, y0, z0)`` in the order
    ``_trilinear`` sums them (x, then y, then z, each low then high), so a
    sample fetches its whole neighbourhood with one gather of a row where
    it would take eight of single voxels."""
    X, Y, Z = vol.shape
    corners = [vol[a:a + X - 1, b:b + Y - 1, c:c + Z - 1]
               for a in (0, 1) for b in (0, 1) for c in (0, 1)]
    return jnp.stack(corners, -1).reshape(-1, 8)


def _trilinear(table, shape, coords):
    """Sample the volume of ``shape`` whose ``_corner_table`` is ``table``
    at voxel coordinates ``coords`` (..., 3), clipped to its cells."""
    x, y, z = coords[..., 0], coords[..., 1], coords[..., 2]
    x0, y0, z0 = (jnp.clip(jnp.floor(c).astype(jnp.int32), 0, s - 2)
                  for c, s in zip((x, y, z), shape))
    dx, dy, dz = x - x0, y - y0, z - z0
    rows = table[(x0 * (shape[1] - 1) + y0) * (shape[2] - 1) + z0]
    out, k = 0.0, 0
    for wx in (1 - dx, dx):
        for wy in (1 - dy, dy):
            for wz in (1 - dz, dz):
                out = out + rows[..., k] * wx * wy * wz
                k += 1
    return out


@functools.partial(jax.jit, static_argnames=("steps",))
def _register(moving, lr, *, steps):
    """The whole registration as one program per volume shape. The volume
    is an argument, never a closed-over constant: a closure would embed it
    in the program as a literal, and every new volume would compile anew.
    Its corner table is built once, before the steps; the gradient does
    not reach it, so the backward pass gathers and scatters nothing."""
    moving = (moving - moving.mean()) / (moving.std() + 1e-6)
    # synthetic atlas: centered sphere intensity prior
    shape = moving.shape
    grids = [jnp.linspace(-1, 1, s) for s in shape]
    gx, gy, gz = jnp.meshgrid(*grids, indexing="ij")
    atlas = jnp.exp(-4 * (gx ** 2 + gy ** 2 + gz ** 2))
    atlas = (atlas - atlas.mean()) / (atlas.std() + 1e-6)
    table = _corner_table(moving)

    def loss(theta):
        warped = _trilinear(table, shape, _affine_grid(shape, theta))
        return jnp.mean((warped - atlas) ** 2)

    theta = jnp.concatenate([jnp.eye(3), jnp.zeros((3, 1))], 1)

    def body(theta, _):
        val, grad = jax.value_and_grad(loss)(theta)
        return theta - lr * grad, val
    theta, losses = jax.lax.scan(body, theta, jnp.arange(steps))
    warped = _trilinear(table, shape, _affine_grid(shape, theta))
    return {"T1w_reg": warped, "affine": theta, "reg_loss": losses}


def _register_fn(inputs, steps=60, lr=5e-3):
    return _register(jnp.asarray(inputs["T1w"], jnp.float32), lr,
                     steps=steps)


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
# UNesT whole-brain segmentation (core/unest.py)
# ---------------------------------------------------------------------------
# Every published and assumed setting, JSON-equal to the benchmark
# configuration's (lists, not tuples): the decoder plan, overlap, batch and
# weight draws are assumed (core/unest.py), and so are constant blending and
# z-score normalisation, which the stage always does.
UNEST_WHOLEBRAIN = {
    "classes": 133, "patch": 4, "embed_dims": [128, 256, 512],
    "depths": [2, 2, 8], "num_heads": [4, 8, 16], "mlp_ratio": 4,
    "feature_size": 32, "roi": 96, "overlap": 0.7, "sw_batch": 4,
    "ln_eps": 1e-6, "in_eps": 1e-5, "leaky_slope": 0.01, "weight_seed": 0,
}


def _unest_fn(inputs, classes=133, patch=4, embed_dims=(128, 256, 512),
              depths=(2, 2, 8), num_heads=(4, 8, 16), mlp_ratio=4,
              feature_size=32, roi=96, overlap=0.7, sw_batch=4,
              ln_eps=1e-6, in_eps=1e-5, leaky_slope=0.01, weight_seed=0):
    cfg = unest.UNesTConfig(
        classes=classes, patch=patch, embed_dims=tuple(embed_dims),
        depths=tuple(depths), num_heads=tuple(num_heads),
        mlp_ratio=mlp_ratio, feature_size=feature_size, roi=roi,
        overlap=overlap, sw_batch=sw_batch, ln_eps=ln_eps, in_eps=in_eps,
        leaky_slope=leaky_slope)
    return unest.segment(inputs["T1w"], cfg, weight_seed)


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
        # 1.2: affine_register runs as one jitted program, which moves its
        # outputs at rounding level, so 1.1's derivatives are planned again.
        # 1.3: it samples through a corner table (one gather a voxel, not
        # eight), whose fusion again moves outputs at rounding level
        "affine_register": Pipeline(
            PipelineSpec("affine_register", "1.3", ("T1w",),
                         {"steps": 60, "lr": 5e-3}),
            _register_fn),
        "segment_unest": Pipeline(
            PipelineSpec("segment_unest", "1.0", ("T1w",),
                         {"n_classes": 4, "patch": 4}),
            _segment_fn),
        "unest_wholebrain": Pipeline(
            PipelineSpec("unest_wholebrain", "1.0", ("T1w",),
                         dict(UNEST_WHOLEBRAIN)),
            _unest_fn),
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
