"""Plain numpy statement of the fused QA + checksum fold over one volume.

Over the volume's little-endian 32-bit words ``w_i``:
``s1 = sum w_i`` and ``s2 = sum (i mod 65521) * w_i``, both mod 2**32.
Over its values as float32, subnormals flushed to +0: the count of finite
values, their min and max, and their sum taken block by block (blocks of
1024 values, each summed by a power-of-two halving tree of float32 adds,
the block sums then added in order), every partial sum flushed to zero
when subnormal. This fixes the order of the float adds, so the result is
exact, not approximate.
"""
from __future__ import annotations

import numpy as np

M_POS = 65521
BLOCK = 1024
EXP_MASK = 0x7F800000
TINY = np.float32(2.0 ** -126)       # the smallest normal float32


def _ftz(v):
    v = np.asarray(v, np.float32)
    return np.where(v.view(np.uint32) & np.uint32(EXP_MASK), v,
                    np.float32(0.0))


def _block(n_vals: int, itemsize: int) -> int:
    blk, floor = BLOCK, max(8, 4 // itemsize)
    while blk // 2 >= max(n_vals, floor) and (blk // 2) * itemsize % 4 == 0:
        blk //= 2
    while blk * itemsize % 4:
        blk *= 2
    return max(blk, floor)


def _checksums(x: np.ndarray):
    raw = x.tobytes()
    raw += b"\0" * ((-len(raw)) % 4)
    w = np.frombuffer(raw, "<u4")
    s1 = int(np.sum(w, dtype=np.uint64)) & 0xFFFFFFFF
    # sum_i (i mod M) w_i: lay the words out in rows of M, so a word's
    # column is its position mod M, and weight each column's exact sum
    rows = -(-w.size // M_POS)
    grid = np.zeros(rows * M_POS, np.uint32)
    grid[:w.size] = w
    cols = grid.reshape(rows, M_POS).sum(axis=0, dtype=np.uint64)
    s2 = int(np.sum(np.arange(M_POS, dtype=np.uint64) * cols,
                    dtype=np.uint64)) & 0xFFFFFFFF
    return s1, s2


def qa_fold(x: np.ndarray) -> dict:
    x = np.ascontiguousarray(x)
    s1, s2 = _checksums(x)
    n = x.size
    blk = _block(n, x.dtype.itemsize)
    nb = max(-(-n // blk), 1)
    v = np.zeros(nb * blk, np.float32)
    with np.errstate(over="ignore", invalid="ignore"):
        v[:n] = x.reshape(-1).astype(np.float32)
    v = _ftz(v)
    finite = np.isfinite(v)
    finite[n:] = False
    part = _ftz(np.where(finite, v, np.float32(0.0)).reshape(nb, blk))
    width = blk
    while width > 1:
        width //= 2
        part = _ftz(part[:, :width] + part[:, width:2 * width])
    total = np.float32(0.0)
    zero = np.float32(0.0)
    for t in part[:, 0]:
        total = np.float32(total + t)
        if -TINY < total < TINY:          # subnormal or -0.0: flush to +0
            total = zero
    return {"s1": s1, "s2": s2,
            "vmin": float(np.min(np.where(finite, v, np.float32(np.inf)))),
            "vmax": float(np.max(np.where(finite, v, np.float32(-np.inf)))),
            "vsum": float(total), "finite_count": int(np.sum(finite))}
