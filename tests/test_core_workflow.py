"""End-to-end paper workflow: synthesize BIDS dataset -> manifest -> query ->
job generation -> execution -> provenance -> idempotent re-query. Plus fault
injection (retry), straggler duplication, and the exclusion CSV."""
import csv
import json
import re
from pathlib import Path

import numpy as np
import pytest

from repro.core import (DatasetManifest, IntegrityError, LocalRunner,
                        builtin_pipelines, generate_jobs, is_complete,
                        load_units, query_available_work, resource_status,
                        run_unit, synthesize_dataset)


@pytest.fixture()
def dataset(tmp_path):
    return synthesize_dataset(tmp_path, "testds", n_subjects=3,
                              sessions_per_subject=2, shape=(12, 12, 12))


def test_manifest_scan_and_validate(dataset):
    assert len(dataset.images) > 0
    assert dataset.validate() == []
    sessions = dataset.sessions()
    assert len(sessions) == 6
    # round-trip persistence
    p = Path(dataset.root) / "manifest.json"
    dataset.save(p)
    loaded = DatasetManifest.load(p)
    assert len(loaded.images) == len(dataset.images)
    assert loaded.images[0].sha256 == dataset.images[0].sha256


def test_query_and_exclusions(dataset, tmp_path):
    pipe = builtin_pipelines()["dwi_prequal"]      # needs T1w + dwi
    work, excluded = query_available_work(dataset, pipe)
    # odd-numbered subjects have no DWI (synthesized that way)
    assert len(work) > 0 and len(excluded) > 0
    assert all("missing input" in e.reason for e in excluded)


def test_full_processing_loop(dataset, tmp_path):
    pipe = builtin_pipelines()["bias_correct"]
    plan = generate_jobs(dataset, pipe, tmp_path / "jobs")
    assert plan.slurm_script and Path(plan.slurm_script).exists()
    slurm = Path(plan.slurm_script).read_text()
    assert "#SBATCH --array=0-" in slurm
    assert Path(plan.exclusion_csv).exists()
    assert len(plan.units) == 6

    runner = LocalRunner(pipe, dataset.root)
    results = runner.run(plan.units)
    assert all(r.status == "ok" for r in results)
    # outputs + provenance exist
    for u in plan.units:
        assert is_complete(Path(u.out_dir), pipe.digest())
        prov = json.loads((Path(u.out_dir) / "provenance.json").read_text())
        assert prov["status"] == "ok" and prov["inputs"]

    # idempotency: re-query finds nothing to do
    work2, excluded2 = query_available_work(dataset, pipe)
    assert work2 == []
    assert all("already processed" in e.reason for e in excluded2)


def test_digest_change_triggers_reprocessing(dataset, tmp_path):
    pipes = builtin_pipelines()
    pipe = pipes["bias_correct"]
    plan = generate_jobs(dataset, pipe, tmp_path / "jobs")
    LocalRunner(pipe, dataset.root).run(plan.units)
    # same pipeline, new version -> different digest -> everything re-queues
    import dataclasses
    pipe2 = type(pipe)(dataclasses.replace(pipe.spec, version="2.0"), pipe.fn)
    work, _ = query_available_work(dataset, pipe2)
    assert len(work) == 6


@pytest.mark.parametrize("name", ["bias_correct", "affine_register"])
def test_changed_stage_replans_derivatives_of_its_old_version(name, dataset,
                                                              tmp_path):
    """bias_correct's fit (lstsq rcond) and both stages' contractions
    (HIGHEST precision) changed what they compute, so their version moved
    past 1.0: a derivative committed under 1.0 must be planned again, not
    skipped as already processed."""
    import dataclasses
    pipe = builtin_pipelines()[name]
    old = type(pipe)(dataclasses.replace(pipe.spec, version="1.0"),
                     lambda inputs: {"stale": np.zeros(1, np.float32)})
    assert old.digest() != pipe.digest()
    plan = generate_jobs(dataset, old, tmp_path / "jobs")
    LocalRunner(old, dataset.root).run(plan.units)
    assert query_available_work(dataset, old)[0] == []
    work, _ = query_available_work(dataset, pipe)
    assert len(work) == len(plan.units) == 6


def test_generate_jobs_writes_manifest_and_every_referenced_path(dataset, tmp_path):
    """Regression: the SLURM template interpolated ``{out_dir}/manifest.json``
    (and a logs dir for ``#SBATCH --output``) that generate_jobs never
    created — an array submitted from the generated script referenced paths
    that did not exist. Every absolute path the script names must exist at
    submit time."""
    pipe = builtin_pipelines()["bias_correct"]
    plan = generate_jobs(dataset, pipe, tmp_path / "jobs")
    assert plan.manifest_file and Path(plan.manifest_file).exists()
    # the manifest next to the script reloads to the scanned dataset
    loaded = DatasetManifest.load(plan.manifest_file)
    assert len(loaded.images) == len(dataset.images)
    assert loaded.images[0].sha256 == dataset.images[0].sha256
    script = Path(plan.slurm_script).read_text()
    assert str(plan.manifest_file) in script
    referenced = re.findall(r"(/[^\s\\$]+)", script)
    assert referenced, "no paths found in the generated script?"
    for raw in referenced:
        # SLURM patterns (%x_%a.out) resolve at runtime; their dir must exist
        target = Path(raw.split("%")[0].rstrip("/"))
        assert target.exists(), f"script references missing {target}"


def test_units_json_roundtrip_reconstructs_identical_units(dataset, tmp_path):
    """The units JSON is the hand-off artifact to SLURM array tasks and
    ``repro.dist.rpc serve``: reloading it must reconstruct WorkUnits equal
    to the originals *including* the data-plane fields (input_digests /
    input_bytes) — silently dropping those would leave every downstream
    queue locality-blind."""
    pipe = builtin_pipelines()["bias_correct"]
    plan = generate_jobs(dataset, pipe, tmp_path / "jobs")
    reloaded = load_units(plan.units_file)
    assert reloaded == plan.units                # dataclass eq: every field
    for orig, back in zip(plan.units, reloaded):
        assert back.input_digests == orig.input_digests != {}
        assert back.input_bytes == orig.input_bytes != {}
        assert back.total_input_bytes == orig.total_input_bytes > 0
    # and a second round-trip is byte-stable
    from repro.core import dump_units
    again = tmp_path / "again.json"
    dump_units(reloaded, again)
    assert again.read_text() == Path(plan.units_file).read_text()


def test_retry_on_injected_failure(dataset):
    pipe = builtin_pipelines()["bias_correct"]
    work, _ = query_available_work(dataset, pipe)
    fails = {"n": 0}

    def flaky(unit, attempt):
        if attempt == 1:          # every unit fails once, succeeds on retry
            fails["n"] += 1
            raise RuntimeError("injected node failure")

    runner = LocalRunner(pipe, dataset.root, max_retries=2, fault_hook=flaky)
    results = runner.run(work)
    ok = [r for r in results if r.status == "ok"]
    assert len(ok) == len(work)
    assert fails["n"] == len(work)
    assert all(r.attempts == 2 for r in ok)


def test_failed_unit_records_failed_provenance(dataset):
    pipe = builtin_pipelines()["bias_correct"]
    work, _ = query_available_work(dataset, pipe)

    def always_fail(unit, attempt):
        raise RuntimeError("dead node")

    res = run_unit(work[0], pipe, dataset.root, fault_hook=always_fail)
    assert res.status == "failed"
    assert not is_complete(Path(work[0].out_dir), pipe.digest())
    # and the work unit is still queryable (not lost)
    work2, _ = query_available_work(dataset, pipe)
    assert any(u.job_id == work[0].job_id for u in work2)


def test_resource_status(tmp_path):
    st = resource_status(tmp_path)
    assert st["disk_free_gb"] > 0
    assert st["disk_total_gb"] >= st["disk_free_gb"]


def test_pipeline_outputs_sensible(dataset):
    pipes = builtin_pipelines()
    t1 = np.load(Path(dataset.root) / dataset.images[0].path)
    out = pipes["bias_correct"].run({"T1w": t1})
    assert out["T1w_biascorr"].shape == t1.shape
    assert np.all(np.isfinite(out["T1w_biascorr"]))
    # bias correction should reduce the coefficient of variation
    cv = lambda a: a.std() / a.mean()
    assert cv(out["T1w_biascorr"]) < cv(t1) * 1.05
    seg = pipes["segment_unest"].run({"T1w": t1})
    assert seg["segmentation"].shape == t1.shape
    assert set(np.unique(seg["segmentation"])) <= {0, 1, 2, 3}


def test_bias_correct_fits_beyond_two_million_voxels():
    """lstsq's default singular-value cut-off, eps * max(M, N), grows with
    the voxel count and passed the polynomial basis' smallest singular-value
    ratio (~0.22) at ~1.9M voxels: at acquisition size the fit returned an
    all-zero field and the stage changed nothing. A 128^3 volume (2.1M
    voxels) with a known smooth bias must come out flattened."""
    n = 128
    g = np.linspace(-1, 1, n, dtype=np.float32)
    gx, gy, gz = np.meshgrid(g, g, g, indexing="ij")
    bias = np.exp(0.3 * gx + 0.2 * gy * gy - 0.1 * gz)
    rng = np.random.default_rng(0)
    t1 = (100.0 * bias * rng.uniform(0.9, 1.1, bias.shape)).astype(np.float32)
    out = builtin_pipelines()["bias_correct"].run({"T1w": t1})
    assert out["T1w_biascorr"].dtype == out["bias_field"].dtype == np.float32
    # the recovered field tracks the imposed one (up to a constant factor)
    lf, lb = np.log(out["bias_field"]).ravel(), np.log(bias).ravel()
    assert np.corrcoef(lf, lb)[0, 1] > 0.99
    # what is left is the multiplicative noise, cv 0.2 / sqrt(12) = 0.058
    cv = lambda a: a.std() / a.mean()
    assert cv(t1) > 0.2 and cv(out["T1w_biascorr"]) < 0.07
