"""Plain reference of ``bias_correct``: a least-squares fit of a low-order
polynomial to the centred log-intensities, divided out of the scan.

float64 on the host. The polynomial basis is separable, so the normal
equations come from 1-D sums and three contractions of the volume, and the
fitted field is a sum of outer products. ``lowp=True`` is the control: the
scan and both outputs rounded to bfloat16, the storage step below the
stage's float32.
"""
from __future__ import annotations

import itertools

import ml_dtypes
import numpy as np

OUTPUTS = ("T1w_biascorr", "bias_field")
# Largest |program - reference| / max |reference| per output; readings in
# PERF.md.
LIMITS = {"corrected": 5e-4, "field": 3e-4}


def _bf16(x: np.ndarray) -> np.ndarray:
    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


def _terms(order: int):
    return [(i, j, k) for i in range(order + 1)
            for j in range(order + 1 - i) for k in range(order + 1 - i - j)]


def reference(inputs: dict, stage: dict, lowp: bool = False) -> dict:
    v = np.asarray(inputs["T1w"], np.float64)
    if lowp:
        v = _bf16(v)
    logv = np.log(np.clip(v, 1e-3, None))
    c = logv - logv.mean()
    terms = _terms(int(stage["order"]))
    axes = [np.linspace(-1.0, 1.0, s) for s in v.shape]
    top = 2 * int(stage["order"])
    pw = [np.stack([g ** e for e in range(top + 1)]) for g in axes]
    gram = np.empty((len(terms), len(terms)))
    for (a, ta), (b, tb) in itertools.product(enumerate(terms), repeat=2):
        gram[a, b] = np.prod([pw[d][ta[d] + tb[d]].sum() for d in range(3)])
    cz = np.tensordot(c, pw[2].T, axes=([2], [0]))          # (X, Y, powers)
    rhs = np.array([pw[0][i] @ cz[:, :, k] @ pw[1][j] for i, j, k in terms])
    coef = np.linalg.solve(gram, rhs)
    field = np.zeros(v.shape)
    for w, (i, j, k) in zip(coef, terms):
        field += w * (pw[0][i][:, None, None] * pw[1][j][None, :, None]
                      * pw[2][k][None, None, :])
    out = {"T1w_biascorr": np.exp(logv - field), "bias_field": np.exp(field)}
    if lowp:
        out = {k: _bf16(x) for k, x in out.items()}
    return out


def _rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    if got.shape != want.shape:
        return float("inf")
    scale = float(np.max(np.abs(want))) or 1.0
    return float(np.max(np.abs(got - want))) / scale


def gaps(got: dict, want: dict, stage: dict) -> dict:
    return {"corrected": _rel(got["T1w_biascorr"], want["T1w_biascorr"]),
            "field": _rel(got["bias_field"], want["bias_field"])}
