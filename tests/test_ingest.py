"""Paper §2.1 ingestion: convert + filter + QA + BIDS organize."""
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core.ingest import (IngestRule, ingest_directory, write_raw_dump)
from repro.core import builtin_pipelines, query_available_work


@pytest.fixture()
def raw_dir(tmp_path):
    rng = np.random.default_rng(0)
    d = tmp_path / "raw"
    good = rng.normal(100, 20, (16, 16, 16)).astype(np.float32)
    write_raw_dump(d / "a.npz", good, subject="001", session="01", protocol="T1w")
    write_raw_dump(d / "b.npz", good + 1, subject="001", session="02",
                   protocol="T1w")
    # filtered: wrong protocol
    write_raw_dump(d / "c.npz", good, subject="002", session="01", protocol="bold")
    # filtered: resolution out of bounds
    write_raw_dump(d / "d.npz", good, subject="002", session="02",
                   protocol="T1w", resolution_mm=5.0)
    # fails QA: NaNs
    bad = good.copy(); bad[0, 0, 0] = np.nan
    write_raw_dump(d / "e.npz", bad, subject="003", session="01", protocol="T1w")
    # corrupted file
    (d / "f.npz").write_bytes(b"not a dump")
    return d


def test_ingest_counts_and_bids(raw_dir, tmp_path):
    manifest, records = ingest_directory(raw_dir, tmp_path / "bids", "study")
    by = {r.source: r for r in records}
    assert by["a.npz"].status == "ok" and by["b.npz"].status == "ok"
    assert by["c.npz"].status == "filtered"
    assert by["d.npz"].status == "filtered"
    assert by["e.npz"].status == "failed_qa"
    assert by["f.npz"].status == "corrupted"
    # BIDS-valid and manifest sees exactly the 2 accepted scans
    assert manifest.validate() == []
    assert len(manifest.images) == 2
    report = json.loads((tmp_path / "bids" / "study" /
                         "ingestion_report.json").read_text())
    assert report["counts"] == {"ok": 2, "corrupted": 1, "filtered": 2,
                                "failed_qa": 1}
    # sidecars exist next to volumes (dcm2niix behaviour)
    vol = Path(by["a.npz"].dest)
    assert vol.with_suffix(".json").exists()


def test_ingested_dataset_flows_into_workflow(raw_dir, tmp_path):
    """The §2.1 output is directly queryable by the §2.3 engine."""
    manifest, _ = ingest_directory(raw_dir, tmp_path / "bids", "study")
    pipe = builtin_pipelines()["bias_correct"]
    work, excluded = query_available_work(manifest, pipe)
    assert len(work) == 2


# ---------------------------------------------------------------------------
# fused QA+checksum at ingest scale: mixed shape-buckets vs the numpy oracle
# ---------------------------------------------------------------------------

def test_qa_checksum_batched_mixed_shape_buckets_bit_exact():
    """Ingest-scale batching: volumes arrive in mixed shapes; each shape
    bucket goes through ONE ``qa_checksum_batched`` call. Every bucket must
    agree bit-exactly with the numpy oracle, and each row must equal the
    unbatched kernel on that volume (so bucketing never changes results)."""
    import jax.numpy as jnp
    from repro.kernels.checksum import (qa_checksum, qa_checksum_batched,
                                        qa_checksum_batched_ref)

    rng = np.random.default_rng(7)
    volumes = (
        [rng.normal(100, 20, (16, 16, 16)).astype(np.float32) for _ in range(3)]
        + [rng.normal(50, 9, (12, 12, 8)).astype(np.float32) for _ in range(4)]
        + [rng.normal(0, 1, (7, 5)).astype(np.float32) for _ in range(2)]
    )
    # NaN/Inf volumes exercise finite_count and the finite-only min/max/sum
    volumes[1] = volumes[1].copy()
    volumes[1][0, 0, 0] = np.nan
    volumes[4] = volumes[4].copy()
    volumes[4][3, 2, 1] = np.inf
    volumes[4][0, 1, 0] = -np.inf

    buckets = {}
    for v in volumes:
        buckets.setdefault(v.shape, []).append(v)
    assert len(buckets) == 3                         # genuinely mixed shapes

    for shape, vols in buckets.items():
        batch = np.stack(vols)
        got = qa_checksum_batched(jnp.asarray(batch), interpret=True)
        ref = qa_checksum_batched_ref(batch)
        for a, b in zip(got, ref):
            a = np.asarray(a)
            assert a.dtype == b.dtype
            assert np.array_equal(a, b, equal_nan=True), (shape, a, b)
        # row-wise: bucketed result == unbatched kernel per volume
        for i, v in enumerate(vols):
            s, q, c = qa_checksum(jnp.asarray(v), interpret=True)
            assert np.array_equal(np.asarray(s), np.asarray(got[0][i]))
            assert np.array_equal(np.asarray(q), np.asarray(got[1][i]),
                                  equal_nan=True)
            assert np.array_equal(np.asarray(c), np.asarray(got[2][i]))


def test_qa_checksum_batched_counts_nonfinite_voxels():
    """finite_count drives the ingest QA gate: it must count exactly the
    finite voxels of each volume in the bucket."""
    import jax.numpy as jnp
    from repro.kernels.checksum import qa_checksum_batched

    rng = np.random.default_rng(3)
    batch = rng.normal(0, 1, (4, 10, 10)).astype(np.float32)
    batch[1, 0, 0] = np.nan
    batch[2, 3, 3] = np.inf
    batch[2, 4, 4] = -np.inf
    batch[3] = np.nan                                # fully non-finite volume
    _, qa, cnt = qa_checksum_batched(jnp.asarray(batch), interpret=True)
    cnt = np.asarray(cnt)[:, 0]
    assert cnt.tolist() == [100, 99, 98, 0]
    qa = np.asarray(qa)
    assert qa[3, 0] == np.inf and qa[3, 1] == -np.inf   # empty-finite min/max
    assert qa[3, 2] == 0.0


def test_ingest_device_qa_uses_checksum_consistently(tmp_path):
    """device_qa ingest records carry the fused checksum; re-ingesting the
    same bytes reproduces it (content-derived, not run-derived)."""
    rng = np.random.default_rng(0)
    d = tmp_path / "raw"
    vol = rng.normal(100, 20, (16, 16, 16)).astype(np.float32)
    write_raw_dump(d / "a.npz", vol, subject="001", session="01",
                   protocol="T1w")
    _, rec1 = ingest_directory(d, tmp_path / "b1", "s", device_qa=True)
    _, rec2 = ingest_directory(d, tmp_path / "b2", "s", device_qa=True)
    assert rec1[0].checksum and rec1[0].checksum == rec2[0].checksum


# ---------------------------------------------------------------------------
# ingest-path correctness: host/device verdict parity, streamed ingest,
# atomic report commits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float16, np.float32, np.uint8,
                                   np.int16, np.uint16])
def test_host_and_device_qa_verdicts_agree_across_dtypes(tmp_path, dtype):
    """The host fast-QA reduces in float32 — the fused kernel's dtype — so
    both paths must reach the same accept/reject verdict for every input
    dtype. (Regression: native-dtype std/mean overflowed to inf on float16
    volumes at modest intensities, rejecting on the host path only.)"""
    rng = np.random.default_rng(5)
    d = tmp_path / "raw"
    # intensities chosen so a float16 sum overflows but a float32 one is fine
    if np.issubdtype(np.dtype(dtype), np.floating):
        vol = rng.normal(300, 40, (16, 16, 16)).astype(dtype)
    else:
        vol = rng.integers(50, 200, (16, 16, 16)).astype(dtype)
    write_raw_dump(d / "a.npz", vol, subject="001", session="01",
                   protocol="T1w")
    bad = vol.astype(np.float32)
    bad[0, 0, 0] = np.nan
    write_raw_dump(d / "b.npz", bad, subject="002", session="01",
                   protocol="T1w")
    _, rec_host = ingest_directory(d, tmp_path / "h", "s", device_qa=False)
    _, rec_dev = ingest_directory(d, tmp_path / "d", "s", device_qa=True)
    host = {r.source: r.status for r in rec_host}
    dev = {r.source: r.status for r in rec_dev}
    assert host == dev
    assert host["a.npz"] == "ok" and host["b.npz"] == "failed_qa"


def test_fast_qa_float16_not_rejected_by_overflow():
    """Direct regression for the native-dtype reduction: a bright float16
    volume whose f16 std/mean overflow must still pass host QA."""
    from repro.core.ingest import IngestRule, _fast_qa
    rng = np.random.default_rng(2)
    vol = rng.normal(400, 60, (24, 24, 24)).astype(np.float16)
    with np.errstate(over="ignore"):
        assert not np.isfinite(vol.astype(np.float16).std())   # the trap
    assert _fast_qa(vol, IngestRule()) == ""


def test_streamed_ingest_matches_one_shot_kernel_and_records_sha256(
        tmp_path, monkeypatch):
    """Streamed device QA (chunked fold + in-flight sha256) must be
    bit-identical to the one-shot ``qa_stats`` kernel over the committed
    volume, and the recorded sha256 must be the digest of the committed
    .npy bytes."""
    import dataclasses
    import hashlib
    from repro.core import stream as stream_mod
    from repro.kernels.checksum import qa_stats
    rng = np.random.default_rng(0)
    d = tmp_path / "raw"
    vol = rng.normal(100, 20, (48, 48, 48)).astype(np.float32)
    write_raw_dump(d / "a.npz", vol, subject="001", session="01",
                   protocol="T1w")
    # 64 KiB chunks over a ~432 KiB volume: several chunks, non-dividing tail
    monkeypatch.setenv(stream_mod.CHUNK_MB_ENV, "0.0625")
    _, rec_stream = ingest_directory(d, tmp_path / "s", "ds", device_qa=True)
    dest = Path(rec_stream[0].dest)
    one_shot = qa_stats(np.load(dest))
    assert rec_stream[0].checksum == f"{one_shot.checksum:016x}"
    assert rec_stream[0].qa == dataclasses.asdict(one_shot)
    assert rec_stream[0].sha256 == hashlib.sha256(
        dest.read_bytes()).hexdigest()
    report = json.loads((tmp_path / "s" / "ds" /
                         "ingestion_report.json").read_text())
    assert report["stream"]["chunks"] > 1
    assert report["stream"]["device_qa"] is True


def test_ingest_rule_default_not_shared_between_calls(raw_dir, tmp_path):
    """Regression: the rule default used to be one shared dataclass
    instance, so a caller mutating it changed every later call's filter."""
    import repro.core.ingest as ingest
    import inspect
    default = inspect.signature(ingest.ingest_directory) \
        .parameters["rule"].default
    assert default is None                     # construct-per-call
    _, rec1 = ingest_directory(raw_dir, tmp_path / "b1", "s")
    # simulate the old failure: mutate a rule the caller owns, re-ingest
    mine = IngestRule(allowed_protocols=("bold",))
    _, rec_bold = ingest_directory(raw_dir, tmp_path / "b2", "s", rule=mine)
    _, rec2 = ingest_directory(raw_dir, tmp_path / "b3", "s")
    assert [r.status for r in rec1] == [r.status for r in rec2]


def test_ingestion_report_commit_is_atomic(raw_dir, tmp_path, monkeypatch):
    """A crash mid-report-write must leave the previous report intact, not
    a torn file (tmp+fsync+rename discipline)."""
    from repro.core import ingest as ingest_mod
    manifest, _ = ingest_directory(raw_dir, tmp_path / "bids", "study")
    rp = tmp_path / "bids" / "study" / "ingestion_report.json"
    before = rp.read_bytes()
    json.loads(before)                              # valid committed report

    def torn_write(path, data, *, fsync=True):
        path = Path(path)
        if path.name == "ingestion_report.json":
            # crash after the tmp file is partially written, before rename
            tmp = path.with_name(".torn-tmp")
            tmp.write_bytes(data[: len(data) // 2])
            raise OSError("simulated crash mid-write")
        return real_write(path, data, fsync=fsync)

    real_write = ingest_mod.atomic_write_bytes
    monkeypatch.setattr(ingest_mod, "atomic_write_bytes", torn_write)
    with pytest.raises(OSError, match="simulated crash"):
        ingest_directory(raw_dir, tmp_path / "bids", "study")
    assert rp.read_bytes() == before                # old report untouched
    json.loads(rp.read_text())


def test_ingest_leaves_no_tmp_litter(raw_dir, tmp_path):
    ingest_directory(raw_dir, tmp_path / "bids", "study", device_qa=True)
    litter = [p for p in (tmp_path / "bids").rglob("*")
              if p.name.startswith(".") and "tmp" in p.name]
    assert litter == []
