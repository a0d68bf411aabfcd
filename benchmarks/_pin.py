"""Shared throughput-mode scaffolding for the executor benchmarks.

The sweep must execute with XLA/BLAS intra-op parallelism pinned to one
thread — so each unit's compute occupies one core and worker/node scaling,
not operator-level thread contention, is what gets measured — and the pin
flags must apply *before* jax initializes. ``run_pinned`` therefore re-execs
the bench module in a subprocess carrying the pin env plus an in-proc flag,
and parses the child's ``name,value,derived`` CSV rows back out. One copy of
the flags and the parser, shared by every bench that needs pinning.
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path
from typing import Callable, List, Tuple

from repro.launch.env import _merge_xla_flags

PIN_XLA_FLAGS = ("--xla_cpu_multi_thread_eigen=false",
                 "intra_op_parallelism_threads=1")
PIN_ENV = {
    "OMP_NUM_THREADS": "1",
    "OPENBLAS_NUM_THREADS": "1",
    "MKL_NUM_THREADS": "1",
}

Row = Tuple[str, float, str]


def run_pinned(module: str, prefix: str, inproc_flag: str,
               inproc: Callable[[], List[Row]],
               timeout: float = 1200) -> List[Row]:
    """Run ``inproc()`` inside a thread-pinned re-exec of ``module``.

    In the child (``inproc_flag`` set) this calls ``inproc`` directly; in the
    parent it spawns ``python -m module`` with the pin env and collects the
    child's stdout rows whose name starts with ``prefix``.
    """
    if os.environ.get(inproc_flag):
        return inproc()
    env = dict(os.environ, **PIN_ENV, **{inproc_flag: "1"})
    env["XLA_FLAGS"] = _merge_xla_flags(os.environ.get("XLA_FLAGS", ""),
                                        PIN_XLA_FLAGS)
    proc = subprocess.run(
        [sys.executable, "-m", module],
        env=env, cwd=Path(__file__).resolve().parents[1],
        capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"pinned bench subprocess failed:\n{proc.stderr}")
    rows: List[Row] = []
    for line in proc.stdout.splitlines():
        if line.startswith(prefix):
            name, value, derived = line.split(",", 2)
            rows.append((name, float(value), derived))
    return rows
