"""A later change adds cells, a traffic kind and a per-layer metric by
adding files and ``BENCHMARK.json`` entries only: no file the benchmark
already has is edited, and each new cell runs, correct, with its metrics
in its result."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]


def _digests(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def _checkout(tmp_path):
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".work", ".cache", "__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    return bench, _digests(bench)


def _add(tmp_path, cell, per_layer=None, also_in=()):
    """Append ``cell`` to BENCHMARK.json, report ``scans_per_min`` in it,
    and add it to the per-layer metrics named in ``also_in``."""
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append(cell)
    if per_layer:
        spec["per_layer"].append(per_layer)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] == "scans_per_min" or m["name"] in also_in:
            m["workloads"].append(cell["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))


def _run(tmp_path, cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", cell,
         "--seed", "3", "--seconds", "0.01", "--trace", trace, "--tiny"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_new_cell_and_metric_by_adding_files(tmp_path):
    bench, before = _checkout(tmp_path)
    (bench / "traffic" / "closed_loop.bias_only.json").write_text(json.dumps(
        {"kind": "stage_closed_loop", "dag": ["bias_correct"],
         "warmup_seed": 0,
         "warmup_scans": 1, "cohort_scans": 3, "check_scans": 1}))
    (bench / "metrics" / "bias_units.py").write_text(
        "def read(run):\n"
        "    return sum(u['ok'] for u in run.units"
        " if u['stage'] == 'bias_correct')\n")
    _add(tmp_path, {"name": "t1w.bias", "config": "t1w-mprage-1mm",
                    "traffic": "closed_loop.bias_only", "chips": 1,
                    "why": "bias correction alone"},
         {"name": "bias_units", "unit": "units", "better": "higher",
          "source": "program_counter", "layer": "executor",
          "moves": "scans_per_min", "workloads": ["t1w.bias"]})
    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before

    outs = {trace: _run(tmp_path, "t1w.bias", trace) for trace in "01"}
    assert outs["0"]["correct"] and set(outs["0"]["metrics"]) == \
        {"scans_per_min", "setup_s"}
    assert outs["1"]["metrics"]["bias_units"]["value"] == 1.0


DRIVER = '''"""Stage units in calls of ``scans_per_call`` scans each."""
from chipbench.harness import BENCH_DIR, load_module

base = load_module(BENCH_DIR / "drivers" / "stage_closed_loop.py")


class Driver(base.Driver):
    def _scan(self):
        tr = self.run.cell.traffic
        k = 1 if self.next < int(tr["warmup_scans"]) \\
            else int(tr["scans_per_call"])
        idx = list(range(self.next, min(self.next + k, len(self.sessions))))
        self.next += len(idx)
        out = []
        for stage in self.dag:
            res = self.runners[stage].run(
                [self.units[stage][i] for i in idx])
            out += [{"stage": stage, "scan": i, "ok": r.status == "ok",
                     "seconds": r.seconds, "error": r.error}
                    for i, r in zip(idx, res)]
        return out
'''

REFERENCE = '''"""Plain reference of dwi_prequal: the DWI as voxels by
volumes, centred per volume, kept to its three largest singular
components, in float64."""
import numpy as np

OUTPUTS = ("dwi_denoised",)
LIMITS = {"denoised": 1e-3}


def reference(inputs, stage, lowp=False):
    dwi = np.asarray(inputs["dwi"], np.float64)
    flat = dwi.reshape(-1, dwi.shape[-1])
    mu = flat.mean(0)
    u, s, vt = np.linalg.svd(flat - mu, full_matrices=False)
    s[3:] = 0.0
    out = ((u * s) @ vt + mu).reshape(dwi.shape)
    return {"dwi_denoised": out.astype(np.float16) if lowp else out}


def gaps(got, want, stage):
    g = np.asarray(got["dwi_denoised"], np.float64)
    w = np.asarray(want["dwi_denoised"], np.float64)
    if g.shape != w.shape:
        return {"denoised": float("inf")}
    return {"denoised": float(np.max(np.abs(g - w)))
            / (float(np.max(np.abs(w))) or 1.0)}
'''


def test_new_traffic_kind_and_dwi_stage_by_adding_files(tmp_path):
    bench, before = _checkout(tmp_path)
    (bench / "drivers" / "stage_batched.py").write_text(DRIVER)
    (bench / "reference" / "dwi_prequal.py").write_text(REFERENCE)
    (bench / "traffic" / "batched.prequal.json").write_text(json.dumps(
        {"kind": "stage_batched", "dag": ["dwi_prequal"], "warmup_seed": 0,
         "warmup_scans": 1, "cohort_scans": 4, "scans_per_call": 2,
         "check_scans": 2}))
    _add(tmp_path, {"name": "session.prequal", "config": "t1w-dwi-session",
                    "traffic": "batched.prequal", "chips": 1,
                    "why": "DWI denoising, two sessions a call"},
         also_in=("unit_host_s",))
    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before

    outs = {trace: _run(tmp_path, "session.prequal", trace)
            for trace in "01"}
    assert outs["0"]["correct"], outs["0"]["compared"]
    # whole calls of two sessions each
    assert outs["0"]["attempted"] in (2, 4), outs["0"]
    assert set(outs["0"]["metrics"]) == {"scans_per_min", "setup_s"}
    assert "dwi_prequal.denoised" in outs["0"]["compared"]
    assert "unit_host_s" in outs["1"]["metrics"]
