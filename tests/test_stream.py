"""Streaming chunked ingest (repro.core.stream): bit-exact chunked QA fold,
in-flight digests, pipeline fallbacks, and the data-plane wiring
(InputCache.fetch_array, load_unit_inputs, provenance, ingest)."""
import hashlib
import io
import os

import numpy as np
import pytest

from repro.core import stream as stream_mod
from repro.core.stream import (StreamReport, bytes_chunks, stream_chunks,
                               stream_chunk_bytes,
                               stream_file, stream_load_npy,
                               stream_verify_bytes, _Prefetcher)
from repro.core.integrity import sha256_load_array
from repro.kernels.checksum import (ACCUMULATOR_DTYPES, QAChecksumAccumulator,
                                    qa_stats)

RNG = np.random.default_rng(7)


def _volume(shape, dtype):
    if np.issubdtype(np.dtype(dtype), np.integer):
        info = np.iinfo(dtype)
        return RNG.integers(info.min, min(info.max, 1 << 30), shape,
                            dtype=dtype, endpoint=False)
    return (RNG.normal(50, 30, shape)).astype(dtype)


# ---------------------------------------------------------------------------
# chunked fold == one-shot kernel, bit-exact
# ---------------------------------------------------------------------------
# Deterministic sweep of the invariant (the hypothesis property in
# test_property.py draws random shapes/chunkings where hypothesis is
# installed; this sweep pins the hard cases everywhere).

@pytest.mark.parametrize("shape", [(1,), (33,), (16, 16, 16), (37, 41),
                                   (5, 3, 7), (1023,), (1025,), (0,)])
@pytest.mark.parametrize("chunk", [1, 7, 64, 1000, 4096, 1 << 30])
def test_chunked_fold_bit_exact_f32(shape, chunk):
    vol = _volume(shape, np.float32)
    if vol.size > 4:
        vol.flat[1] = np.nan
        vol.flat[3] = np.inf
        vol.flat[vol.size - 1] = -np.inf
    acc = QAChecksumAccumulator(vol.size, vol.dtype, interpret=True)
    data = vol.tobytes()
    for off in range(0, len(data), chunk):
        acc.update(data[off:off + chunk])
    assert acc.finalize() == qa_stats(vol, interpret=True)


@pytest.mark.parametrize("dtype", ACCUMULATOR_DTYPES)
def test_chunked_fold_bit_exact_dtype_sweep(dtype):
    vol = _volume((11, 13), np.dtype(dtype))
    if np.issubdtype(vol.dtype, np.floating):
        vol.flat[5] = np.nan
    # a chunk size that never aligns with block or item boundaries
    for chunk in (3, 997):
        acc = QAChecksumAccumulator(vol.size, vol.dtype, interpret=True)
        data = vol.tobytes()
        for off in range(0, len(data), chunk):
            acc.update(data[off:off + chunk])
        assert acc.finalize() == qa_stats(vol, interpret=True)


def test_chunked_fold_host_backend_bit_exact():
    vol = _volume((29, 31), np.float32)
    vol.flat[17] = np.inf
    ref = qa_stats(vol, interpret=True)
    for chunk in (1, 123, 1 << 20):
        acc = QAChecksumAccumulator(vol.size, vol.dtype, backend="host")
        data = vol.tobytes()
        for off in range(0, len(data), chunk):
            acc.update(data[off:off + chunk])
        assert acc.finalize() == ref


def test_accumulator_rejects_overrun_and_truncation():
    vol = _volume((8,), np.float32)
    acc = QAChecksumAccumulator(vol.size, vol.dtype, backend="host")
    with pytest.raises(ValueError):
        acc.update(vol.tobytes() + b"x")
    acc = QAChecksumAccumulator(vol.size, vol.dtype, backend="host")
    acc.update(vol.tobytes()[:-1])
    with pytest.raises(ValueError, match="truncated"):
        acc.finalize()
    # finalized accumulators refuse further updates
    acc = QAChecksumAccumulator(vol.size, vol.dtype, backend="host")
    acc.update(vol.tobytes())
    acc.finalize()
    with pytest.raises(RuntimeError):
        acc.update(b"")


# ---------------------------------------------------------------------------
# the stream pipeline: digests, QA, fallbacks
# ---------------------------------------------------------------------------

def test_stream_load_npy_digest_matches_resident_path(tmp_path):
    vol = _volume((16, 16, 16), np.float32)
    p = tmp_path / "v.npy"
    np.save(p, vol)
    arr, digest, qa, rep = stream_load_npy(p, chunk_bytes=4096,
                                           device_qa=True)
    ref_arr, ref_digest = sha256_load_array(p)
    assert digest == ref_digest
    assert np.array_equal(arr, ref_arr)
    assert qa == qa_stats(vol, interpret=True)
    assert rep.nbytes == p.stat().st_size
    assert rep.chunks == -(-rep.nbytes // 4096)


def test_stream_non_npy_bytes_degrade_to_hash_only():
    blob = b"definitely not an npy file" * 100
    digest, qa, rep = stream_verify_bytes(blob, chunk_bytes=64)
    assert qa is None
    assert digest == hashlib.sha256(blob).hexdigest()
    assert rep.nbytes == len(blob)


def test_stream_fortran_and_truncated_degrade_to_hash_only(tmp_path):
    f = np.asfortranarray(_volume((6, 7), np.float32))
    p = tmp_path / "f.npy"
    np.save(p, f)
    arr, digest, qa, _ = stream_load_npy(p, device_qa=True)
    assert qa is None and np.array_equal(arr, f)
    assert digest == hashlib.sha256(p.read_bytes()).hexdigest()
    # truncated payload: digest of the bytes that arrived, QA refused
    raw = p.read_bytes()[:-5]
    digest, qa, _ = stream_verify_bytes(raw, chunk_bytes=16)
    assert qa is None and digest == hashlib.sha256(raw).hexdigest()


def test_stream_header_split_across_tiny_chunks(tmp_path):
    vol = _volume((33,), np.float32)
    p = tmp_path / "v.npy"
    np.save(p, vol)
    raw = p.read_bytes()
    data, digest, qa, rep = stream_chunks(bytes_chunks(raw, 7), npy_qa=True,
                                          chunk_bytes=7)
    assert data == raw
    assert digest == hashlib.sha256(raw).hexdigest()
    assert qa == qa_stats(vol, interpret=True)


def test_prefetcher_propagates_source_errors():
    def boom():
        yield b"ok"
        raise OSError("link died")
    pf = _Prefetcher(boom())
    with pytest.raises(OSError, match="link died"):
        list(pf)


def test_stream_report_merge_and_overlap():
    a = StreamReport(nbytes=10, chunks=2, chunk_bytes=5, read_s=1.0,
                     hash_s=0.5, device_s=0.25, wall_s=1.2)
    b = StreamReport(nbytes=6, chunks=1, chunk_bytes=6, read_s=0.5,
                     hash_s=0.25, device_s=0.0, wall_s=0.5)
    assert a.overlap_s == pytest.approx(0.55)
    a.merge(b)
    assert a.nbytes == 16 and a.chunks == 3 and a.files == 2
    assert a.chunk_bytes == 6
    d = a.to_dict()
    assert d["overlap_s"] == pytest.approx(a.overlap_s)
    assert StreamReport.from_dict(d).nbytes == 16


def test_stream_knobs(monkeypatch):
    monkeypatch.delenv(stream_mod.CHUNK_MB_ENV, raising=False)
    assert stream_chunk_bytes() == stream_mod.DEFAULT_CHUNK_BYTES
    monkeypatch.setenv(stream_mod.CHUNK_MB_ENV, "2")
    assert stream_chunk_bytes() == 2 << 20
    monkeypatch.setenv(stream_mod.CHUNK_MB_ENV, "0.001")   # floored
    assert stream_chunk_bytes() == stream_mod.MIN_CHUNK_BYTES
    monkeypatch.setenv(stream_mod.CHUNK_MB_ENV, "junk")
    assert stream_chunk_bytes() == stream_mod.DEFAULT_CHUNK_BYTES


# ---------------------------------------------------------------------------
# data-plane wiring: InputCache, load_unit_inputs, provenance
# ---------------------------------------------------------------------------

from repro.dist.cache import InputCache  # noqa: E402


def test_fetch_array_streams_storage_misses(tmp_path):
    vol = _volume((16, 16, 16), np.float32)
    src = tmp_path / "v.npy"
    np.save(src, vol)
    cache = InputCache(tmp_path / "c")
    arr, digest, origin, nbytes, info = cache.fetch_array(src)
    assert origin == "storage"
    assert digest == hashlib.sha256(src.read_bytes()).hexdigest()
    assert info is not None and info["nbytes"] == nbytes
    st = cache.stats()
    assert st["stream_fetches"] == 1 and st["stream_bytes"] == nbytes
    assert st["stream_chunks"] >= 1
    # the hit path serves resident bytes: no stream report
    _, d2, origin2, _, info2 = cache.fetch_array(src)
    assert (origin2, info2) == ("cache", None) and d2 == digest


def test_fetch_array_stream_matches_sha256_load_array(tmp_path, monkeypatch):
    """A streamed storage miss returns the same digest and array as the
    one-read reference loader, across several chunks and a short tail."""
    vol = _volume((48, 48, 48), np.float32)
    src = tmp_path / "v.npy"
    np.save(src, vol)
    monkeypatch.setenv(stream_mod.CHUNK_MB_ENV, "0.0625")
    ref_arr, ref_digest = sha256_load_array(src)
    arr, digest, origin, nbytes, info = InputCache(
        tmp_path / "c").fetch_array(src)
    assert origin == "storage" and info["chunks"] > 1
    assert digest == ref_digest and np.array_equal(arr, ref_arr)


def test_load_unit_inputs_aggregates_stream_reports(tmp_path):
    from repro.core.workflow import load_unit_inputs
    from repro.core.query import WorkUnit
    roots = tmp_path / "data"
    rels = {}
    for name in ("T1w", "T2w"):
        rel = f"sub-001/{name}.npy"
        (roots / "sub-001").mkdir(parents=True, exist_ok=True)
        np.save(roots / rel, _volume((8, 8, 8), np.float32))
        rels[name] = rel
    unit = WorkUnit(dataset="ds", subject="001", session="01",
                    pipeline="p", pipeline_digest="d",
                    inputs=rels, out_dir=str(tmp_path / "out"))
    # cache-less path streams straight off storage
    _, sums, _, _, _, stream = load_unit_inputs(unit, roots)
    assert stream is not None and stream["files"] == 2
    for rel, digest in sums.items():
        assert digest == hashlib.sha256((roots / rel).read_bytes()).hexdigest()
    # cache path: misses stream, aggregate report matches per-file bytes
    cache = InputCache(tmp_path / "c")
    _, sums2, _, _, _, stream2 = load_unit_inputs(unit, roots, cache=cache)
    assert sums2 == sums
    assert stream2 is not None and stream2["files"] == 2
    assert stream2["nbytes"] == sum(
        (roots / r).stat().st_size for r in rels.values())
    # all-resident second pass: nothing streamed
    _, _, hit, _, _, stream3 = load_unit_inputs(unit, roots, cache=cache)
    assert hit is True and stream3 is None


def test_run_unit_stamps_stream_provenance(tmp_path):
    from repro.core.pipelines import builtin_pipelines
    from repro.core.provenance import Provenance
    from repro.core.query import WorkUnit
    from repro.core.workflow import run_unit
    pipe = builtin_pipelines()["bias_correct"]
    root = tmp_path / "data"
    rel = "sub-001/T1w.npy"
    (root / "sub-001").mkdir(parents=True)
    np.save(root / rel, np.abs(_volume((8, 8, 8), np.float32)))
    unit = WorkUnit(dataset="ds", subject="001", session="01",
                    pipeline="bias_correct", pipeline_digest=pipe.digest(),
                    inputs={"T1w": rel}, out_dir=str(tmp_path / "out"))
    res = run_unit(unit, pipe, root)
    assert res.status == "ok"
    prov = Provenance.load(tmp_path / "out")
    assert prov is not None and prov.stream is not None
    assert prov.stream["nbytes"] == (root / rel).stat().st_size
    assert prov.stream["overlap_s"] >= 0.0


# ---------------------------------------------------------------------------
# subnormals, fold failures, and what the report says ran
# ---------------------------------------------------------------------------

def test_subnormal_is_flushed_by_every_fold():
    """The hypothesis property's counterexample, pinned: a lone float32
    subnormal. The host fold used to keep it (min = max = sum = 1.8e-39)
    while the device folds flushed some of it; every path now flushes it to
    zero before min/max/sum, and the oracle agrees."""
    from repro.kernels.checksum import qa_checksum_ref
    arr = np.asarray([1.805228351860303e-39], np.float32)
    want = qa_stats(arr, interpret=True)
    assert (want.vmin, want.vmax, want.vsum, want.finite_count) == \
        (0.0, 0.0, 0.0, 1)
    sums, qa, cnt = qa_checksum_ref(arr)
    assert (int(sums.view(np.uint32)[0]), int(sums.view(np.uint32)[1]),
            *map(float, qa), int(cnt[0])) == \
        (want.s1, want.s2, want.vmin, want.vmax, want.vsum,
         want.finite_count)
    for backend in ("host", "device"):
        acc = QAChecksumAccumulator(arr.size, arr.dtype, backend=backend,
                                    interpret=True)
        data = arr.tobytes()
        for off in range(0, len(data), 1):          # chunk = 1 byte
            acc.update(data[off:off + 1])
        assert acc.finalize() == want, backend


def test_subnormal_partial_sums_are_flushed():
    """Two normal values whose sum is subnormal: the block tree flushes the
    partial sum, on the kernel, the host fold and the oracle alike."""
    from repro.kernels.checksum import qa_checksum_ref
    arr = np.asarray([1.5e-38, -1.4e-38], np.float32)
    want = qa_stats(arr, interpret=True)
    assert want.vsum == 0.0
    assert float(qa_checksum_ref(arr)[1][2]) == 0.0
    acc = QAChecksumAccumulator(arr.size, arr.dtype, backend="host")
    acc.update(arr.tobytes())
    assert acc.finalize() == want


def _fold_fails(monkeypatch):
    def boom(self, chunk, nblocks):
        raise RuntimeError("QA kernel failed")
    monkeypatch.setattr(QAChecksumAccumulator, "_process", boom)


def test_failed_fold_raises_instead_of_hash_only(tmp_path, monkeypatch):
    vol = _volume((16, 16, 16), np.float32)
    p = tmp_path / "v.npy"
    np.save(p, vol)
    _fold_fails(monkeypatch)
    with pytest.raises(RuntimeError, match="QA kernel failed"):
        stream_chunks(bytes_chunks(p.read_bytes(), 4096), npy_qa=True,
                      chunk_bytes=4096)


def test_failed_device_kernel_propagates(monkeypatch):
    """A kernel error on the device backend (a compile refusal raises
    ValueError) is not read as an overrun or truncation."""
    from repro.kernels.checksum import checksum as ck

    def refuse(*a, **k):
        raise ValueError("Cannot store scalars to VMEM")
    monkeypatch.setattr(ck, "_qa_call", refuse)
    buf = io.BytesIO()
    np.save(buf, _volume((16, 16, 16), np.float32))
    with pytest.raises(ValueError, match="VMEM"):
        stream_verify_bytes(buf.getvalue(), chunk_bytes=4096,
                            qa_backend="device", interpret=True)


def test_ingest_raises_when_the_fold_fails(tmp_path, monkeypatch):
    from repro.core.ingest import ingest_directory, write_raw_dump
    raw = tmp_path / "raw"
    write_raw_dump(raw / "a.raw.npz", np.abs(_volume((16, 16, 16),
                                                     np.float32)) + 1,
                   subject="01", session="01", protocol="T1w")
    _fold_fails(monkeypatch)
    with pytest.raises(RuntimeError, match="QA kernel failed"):
        ingest_directory(raw, tmp_path / "bids", "ds", device_qa=True)


@pytest.mark.parametrize("backend,interpret", [("host", None),
                                               ("device", True)])
def test_stream_report_records_the_fold_that_ran(backend, interpret):
    buf = io.BytesIO()
    np.save(buf, _volume((16, 16, 16), np.float32))
    _, qa, rep = stream_verify_bytes(buf.getvalue(), chunk_bytes=4096,
                                     qa_backend=backend, interpret=True)
    assert qa is not None
    assert (rep.qa_backend, rep.qa_interpret) == (backend, interpret)
    d = rep.to_dict()
    assert (d["qa_backend"], d["qa_interpret"]) == (backend, interpret)
    merged = StreamReport.from_dict(d)
    merged.merge(StreamReport(qa_backend="host"))
    assert merged.qa_backend == ",".join(sorted({backend, "host"}))


def test_ingest_records_carry_per_scan_qa_and_stream(tmp_path):
    from repro.core.ingest import ingest_directory, write_raw_dump
    raw = tmp_path / "raw"
    vols = {}
    for sub in ("01", "02"):
        vols[sub] = np.abs(_volume((16, 16, 16), np.float32)) + 1
        write_raw_dump(raw / f"{sub}.raw.npz", vols[sub], subject=sub,
                       session="01", protocol="T1w")
    _, recs = ingest_directory(raw, tmp_path / "bids", "ds", device_qa=True)
    for rec, sub in zip(recs, ("01", "02")):
        assert rec.status == "ok"
        assert rec.stream["device_qa"] is True
        assert rec.stream["qa_backend"] == "host"      # auto, off TPU
        st = qa_stats(vols[sub], interpret=True)
        assert rec.qa["finite_count"] == st.finite_count
        assert rec.qa["vsum"] == st.vsum
        assert rec.checksum == f"{st.checksum:016x}"
