"""The least work of one ``unest_wholebrain`` unit, counted from shapes.

FLOPs: a multiply-add is 2. Per window: every conv and matmul (the patch
embedding, q/k/v/o and the MLP of each transformer layer, the block
aggregations, the decoder's convs, transposed convs and 1³ convs, the class
head), block attention (scores and the weighted sum, 2·T²·D each per block
of T tokens), and the elementwise terms at a count per element: LayerNorm
7 (mean, centre, square, sum, scale, gain, bias), instance norm 5, bias,
activation (LeakyReLU, GELU), residual add 1 each, softmax 3 per score,
max-pool 26 comparisons per output (27 inputs). Per unit: the windows,
normalising the volume (3 per voxel), adding each window's logits into the
buffer (1 per logit) and its count (1 per voxel), and the average's
divide, max, subtract, exp, sum and arg-max over the classes (6 per class
and voxel).

Bytes: the volume read once (float32), the weights read once (bfloat16),
the blend buffer written and read once (float32, classes × padded
voxels), and the outputs written (uint8 label, float32 confidence). The
hand-worked numbers at 256×256×176 are in
``tests/test_chipbench_unest.py``.
"""
from __future__ import annotations

import math
from typing import Sequence

LN, IN, POOL = 7, 5, 26


def _roi_windows(shape: Sequence[int], st: dict) -> int:
    """Sliding windows over the volume, padded to the window where short:
    starts every floor(roi (1 - overlap)), the last clipped to the end."""
    R, n = st["roi"], 1
    for s in (max(int(v), R) for v in shape):
        step = R if s == R else max(int(R * (1 - st["overlap"])), 1)
        n *= next(i for i in range(s) if i * step + R >= s) + 1
    return n


def n_windows(shape: Sequence[int], st: dict) -> int:
    return _roi_windows(shape, st)


def param_count(st: dict) -> int:
    """Parameters of the model, biases and norm gains included."""
    P, F, C = st["patch"], st["feature_size"], st["classes"]
    D, depths, m = st["embed_dims"], st["depths"], st["mlp_ratio"]
    side = st["roi"] // P // 4
    n = P ** 3 * D[0] + D[0]
    for lvl, d in enumerate(D):
        if lvl:
            n += 27 * D[lvl - 1] * d + 3 * d
        n += 8 ** (2 - lvl) * side ** 3 * d
        n += depths[lvl] * (4 * d + 3 * d * d + d * d + d
                            + 2 * m * d * d + m * d + d)
    n += 2 * D[2]

    def res(cin, cout):
        return 27 * cin * cout + 27 * cout * cout + \
            (cin * cout if cin != cout else 0)

    n += res(1, F) + D[0] * 8 * 2 * F + res(2 * F, 2 * F)
    n += res(D[0], 2 * F) + res(D[1], 4 * F) + 27 * D[2] * 32 * F
    cin = 32 * F
    for cout, skip in ((16 * F, D[2]), (8 * F, 4 * F), (4 * F, 2 * F),
                       (2 * F, 2 * F), (F, F)):
        n += cin * 8 * cout + res(cout + skip, cout)
        cin = cout
    return n + F * C + C


def window_flops(st: dict) -> int:
    """The least FLOPs of one window's forward (logits included)."""
    P, F, C, R = st["patch"], st["feature_size"], st["classes"], st["roi"]
    D, depths, heads, m = st["embed_dims"], st["depths"], st["num_heads"], \
        st["mlp_ratio"]
    g = R // P
    flops = 2 * P ** 3 * D[0] * g ** 3 + D[0] * g ** 3       # embed + bias
    T = (g // 4) ** 3                                       # tokens a block
    for lvl, d in enumerate(D):
        n_in = (g >> (lvl - 1)) ** 3 if lvl else 0
        tokens = (g >> lvl) ** 3
        if lvl:                       # conv 3³ + bias, LN, max-pool
            flops += (2 * 27 * D[lvl - 1] + 1 + LN) * d * n_in
            flops += POOL * d * tokens
        flops += d * tokens                                 # positions
        blocks = tokens // T
        per_layer = tokens * (2 * d * 3 * d + 2 * d * d + 2 * 2 * m * d * d
                              + 2 * LN * d + 2 * d       # norms, residuals
                              + d + m * d + d           # biases
                              + m * d)                  # GELU
        per_layer += blocks * (2 * 2 * T * T * d + 3 * T * T * heads[lvl])
        flops += depths[lvl] * per_layer
    flops += LN * D[2] * (g >> 2) ** 3                      # final norm

    def conv(k, cin, cout, n):
        return 2 * k ** 3 * cin * cout * n

    def res(cin, cout, n):
        f = conv(3, cin, cout, n) + conv(3, cout, cout, n) + \
            2 * IN * cout * n + 3 * cout * n     # 2 LeakyReLU, 1 add
        if cin != cout:
            f += conv(1, cin, cout, n) + IN * cout * n
        return f

    v = lambda s: (R // s) ** 3                             # noqa: E731
    flops += res(1, F, v(1))
    flops += 2 * D[0] * 2 * F * v(2) + res(2 * F, 2 * F, v(2))
    flops += res(D[0], 2 * F, v(4)) + res(D[1], 4 * F, v(8))
    flops += conv(3, D[2], 32 * F, v(32))
    cin = 32 * F
    for s, cout, skip in ((16, 16 * F, D[2]), (8, 8 * F, 4 * F),
                          (4, 4 * F, 2 * F), (2, 2 * F, 2 * F),
                          (1, F, F)):
        flops += 2 * cin * cout * v(s) + res(cout + skip, cout, v(s))
        cin = cout
    return flops + (2 * F + 1) * C * v(1)                   # head + bias


def unit_work(shape: Sequence[int], st: dict):
    """(FLOPs, bytes) of one unit: every window, the blend and the
    outputs over a volume of ``shape``."""
    R, C = st["roi"], st["classes"]
    voxels = math.prod(int(s) for s in shape)
    padded = math.prod(max(int(s), R) for s in shape)
    n = n_windows(shape, st)
    flops = n * (window_flops(st) + (C + 1) * R ** 3) + 3 * voxels + \
        6 * C * voxels
    nbytes = 4 * voxels + 2 * param_count(st) + 2 * 4 * C * padded + \
        5 * voxels
    return flops, nbytes
