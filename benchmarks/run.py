"""Run every benchmark module and print its ``name,value,derived`` CSV rows.

Each module runs in a child process of its own (``python -m benchmarks.run
--one NAME``), and this parent never imports JAX: a chip serves one process
at a time, so a parent that had touched it would starve every child.
"""
from __future__ import annotations

import argparse
import importlib
import subprocess
import sys
from pathlib import Path

MODULES = ("table1_cost", "pipeline_throughput", "executor_throughput",
           "cluster_throughput", "rpc_throughput", "locality_throughput",
           "peer_fabric", "ingest_stream", "campaign_plan", "recovery",
           "train_step_bench", "kernel_bench")


def _run_one(name: str) -> None:
    mod = importlib.import_module(f"benchmarks.{name}")
    for n, v, d in mod.run():
        print(f"{n},{v},{str(d).replace(',', ';')}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--one", choices=MODULES,
                    help="run one module in this process")
    args = ap.parse_args()
    if args.one:
        _run_one(args.one)
        return
    # artifacts live under benchmarks/out/ — gitignored, so a fresh checkout
    # doesn't have it; recreate rather than making every bench defensive
    root = Path(__file__).resolve().parents[1]
    (root / "benchmarks" / "out").mkdir(parents=True, exist_ok=True)
    print("name,value,derived", flush=True)
    failed = 0
    for name in MODULES:
        proc = subprocess.run([sys.executable, "-m", "benchmarks.run",
                               "--one", name], cwd=root)
        if proc.returncode != 0:
            failed += 1
            print(f"{name}_FAILED,,exit code {proc.returncode}", flush=True)
    if failed:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
