"""The measured path refuses what is not a chip, and a checkout without the
program: it exits non-zero and prints no result line."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]


def _run(cwd, env_extra=None, pythonpath=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "t1w.register", "--seed", "4294967311", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(out: str) -> bool:
    lines = [ln for ln in out.strip().splitlines() if ln.strip()]
    if not lines:
        return True
    try:
        return not isinstance(json.loads(lines[-1]), dict)
    except ValueError:
        return True


def test_cpu_is_refused():
    p = _run(ROOT, {"BENCH_RUN": "7"}, str(ROOT / "src"))
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "needs 1 TPU chip" in p.stderr


def test_a_checkout_of_only_the_benchmark_is_refused(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".work", ".cache",
                                                  "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
