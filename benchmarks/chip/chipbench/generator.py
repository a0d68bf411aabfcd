"""The one traffic generator: scanner dumps made from ``--seed``.

Every scan of a run has the shape its configuration states; the seed
changes the anatomy's proportions, the bias field and the noise, never a
size. A T1w is a head-like phantom (tissue shells, a smooth multiplicative
bias field, scanner noise) stored as int16 the way scanners write it; a
DWI is that kind of anatomy attenuated per gradient direction, with the
configuration's ``b0_volumes`` b=0 volumes spread evenly over the series.
Both follow ``chip_smoke.make_scans``. A maker takes the scan's entry in
the configuration (``shape`` and its own parameters).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def child_rngs(seed: int, n: int) -> List[np.random.Generator]:
    """``n`` independent generators drawn from one seed (any size of int)."""
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(int(seed)).spawn(n)]


def make_t1w(rng: np.random.Generator, spec: dict) -> np.ndarray:
    shape = tuple(int(s) for s in spec["shape"])
    radii = rng.uniform(0.95, 1.05, 3) * np.array([0.8, 0.9, 0.85])
    g = [np.linspace(-1, 1, s, dtype=np.float32) for s in shape]
    gx, gy, gz = np.meshgrid(*g, indexing="ij", sparse=True)
    r = np.sqrt((gx / radii[0]) ** 2 + (gy / radii[1]) ** 2
                + (gz / radii[2]) ** 2)
    anat = (20.0 + 380.0 * (r < 1.0) + 200.0 * (r < 0.75)
            + 150.0 * (r < 0.4)).astype(np.float32)
    b = rng.uniform(-1.0, 1.0, 3).astype(np.float32)
    bias = 1.0 + 0.15 * b[0] * gx + 0.1 * b[1] * gy * gy - 0.05 * b[2] * gz
    noise = rng.standard_normal(shape, dtype=np.float32) * 6.0
    return np.clip(anat * bias + noise, 0, 32767).astype(np.int16)


def make_dwi(rng: np.random.Generator, spec: dict) -> np.ndarray:
    X, Y, Z, V = (int(s) for s in spec["shape"])
    h = [np.linspace(-1, 1, s, dtype=np.float32) for s in (X, Y, Z)]
    hx, hy, hz = np.meshgrid(*h, indexing="ij", sparse=True)
    radius = np.float32(rng.uniform(0.75, 0.85))
    s0 = 30.0 + 500.0 * (np.sqrt(hx ** 2 + hy ** 2 + hz ** 2) < radius)
    b0 = np.linspace(0, V, int(spec["b0_volumes"]), endpoint=False)
    bval = np.ones(V, np.float32)
    bval[np.floor(b0).astype(int)] = 0.0
    atten = np.exp(-0.7 * bval * rng.uniform(0.5, 1.5, V).astype(np.float32))
    dwi = (s0[..., None] * atten).astype(np.float32)
    dwi += rng.standard_normal((X, Y, Z, V), dtype=np.float32) * 8.0
    return np.clip(dwi, 0, 32767).astype(np.int16)


MAKERS = {"T1w": make_t1w, "dwi": make_dwi}


def make_session(rng: np.random.Generator,
                 scans: Dict[str, dict]) -> Dict[str, np.ndarray]:
    """One session's scans, in the configuration's order of protocols."""
    return {proto: MAKERS[proto](rng, spec) for proto, spec in scans.items()}
