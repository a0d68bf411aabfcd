"""The work a stage or kernel needs, counted from shapes.

Each count is the least that any implementation must do, so a share of the
roofline built on it cannot pass 100%. The hand-worked numbers at the
cells' shapes are in ``tests/test_chipbench_work.py``.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

F32 = 4


def n_voxels(shape: Sequence[int]) -> int:
    return int(np.prod([int(s) for s in shape], dtype=np.int64))


# -- affine_register ----------------------------------------------------------
# Per voxel and gradient step, the forward pass maps the voxel's coordinate
# through the 3x4 affine (9 multiply-adds: 18 FLOPs), interpolates the 8
# neighbours with 7 linear blends (3 FLOPs each: 21) and forms the squared
# residual against the atlas (3 FLOPs): 42. The gradient with respect to the
# 12 affine entries needs at least as much again: 84 per voxel per step. The
# final warp is one forward interpolation (18 + 21 = 39 per voxel).
#
# Bytes: each step must read the moving volume once (4 B per voxel; the
# atlas is a closed-form function of the coordinate, so an implementation
# need not read it). Normalising the input reads it once more, and the final
# warp reads the volume and writes the warped one.
REG_STEP_FLOPS_PER_VOXEL = 84
REG_WARP_FLOPS_PER_VOXEL = 39


def register_work(shape: Sequence[int], steps: int):
    """(FLOPs, bytes) of one ``affine_register`` unit."""
    n = n_voxels(shape)
    flops = n * (steps * REG_STEP_FLOPS_PER_VOXEL + REG_WARP_FLOPS_PER_VOXEL)
    nbytes = n * F32 * (steps + 1 + 2)
    return flops, nbytes


# -- segment_unest ------------------------------------------------------------

def segment_tokens(shape: Sequence[int], patch: int) -> int:
    return int(np.prod([int(s) // patch for s in shape], dtype=np.int64))


def segment_flops(shape: Sequence[int], seg: dict) -> int:
    """FLOPs of one backbone forward over a volume: the patch projection,
    per layer the q/k/v/o projections, attention under its causal mask (the
    unmasked half only: query i attends to keys 0..i), the two MLP
    matrices, and the class head over the classes kept."""
    T = segment_tokens(shape, seg["patch"])
    D, H, KV, Dh = seg["d_model"], seg["n_heads"], seg["n_kv_heads"], \
        seg["d_head"]
    F, L, C = seg["d_ff"], seg["n_layers"], seg["n_classes"]
    P = seg["patch"] ** 3
    proj = 2 * T * P * D
    qkvo = 2 * T * D * (H * Dh + 2 * KV * Dh) + 2 * T * H * Dh * D
    pairs = T * (T + 1) // 2                      # causal (query, key) pairs
    attn = 2 * 2 * H * Dh * pairs                 # scores and weighted sum
    mlp = 2 * 2 * T * D * F
    head = 2 * T * D * C
    return int(proj + L * (qkvo + attn + mlp) + head)


# -- QA + checksum fold (kernels/checksum) ------------------------------------

def fold_bytes(payload_bytes: int) -> int:
    """The fold reads every payload byte once; its carry is a few KiB."""
    return int(payload_bytes)
