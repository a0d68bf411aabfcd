"""The program's spans (``repro.core.trace``) as a JAX profiler trace holds
them: a unit's phases nest inside ``repro.unit`` in order, ingest's phases
inside ``repro.ingest``, compile phases open and close with JAX's own
events, each ``bytes`` stat is what lies on disk, the counters are the sums
of the spans, and the benchmark's reading of the trace is unchanged."""
import contextlib
import glob
import sys
from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import (builtin_pipelines, generate_jobs, run_unit,
                        synthesize_dataset, trace)
from repro.core.ingest import ingest_directory, write_raw_dump

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip"))
from chipbench import trace as bench_trace  # noqa: E402


class Span(NamedTuple):
    name: str
    start: int
    end: int
    stats: dict


def record(out_dir: Path, fn):
    """Run ``fn`` under the profiler as the benchmark starts it; return the
    trace and its ``repro.*`` host spans, in order of start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True)
    profile = ProfileData.from_file(path)
    spans = [Span(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for p in profile.planes if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events
             if e.name.startswith("repro.")]
    return profile, sorted(spans, key=lambda s: s.start)


def inside(spans, outer):
    return [s for s in spans if s is not outer
            and outer.start <= s.start and s.end <= outer.end]


def named(spans, name):
    return [s for s in spans if s.name == name]


@pytest.fixture()
def dataset(tmp_path):
    return synthesize_dataset(tmp_path, "spands", n_subjects=2,
                              sessions_per_subject=1, shape=(12, 12, 12),
                              with_dwi=False)


@pytest.fixture()
def units(dataset, tmp_path):
    pipe = builtin_pipelines()["bias_correct"]
    return pipe, generate_jobs(dataset, pipe, tmp_path / "jobs").units


def test_unit_phases_nest_in_order_with_one_unit_stat(units, dataset,
                                                      tmp_path):
    pipe, (unit, _) = units
    _, spans = record(tmp_path / "prof",
                      lambda: run_unit(unit, pipe, dataset.root))
    [outer] = named(spans, "repro.unit")
    tag = f"bias_correct:{unit.subject}:{unit.session}"
    assert outer.stats == {"unit": tag, "attempt": 1}
    phases = [s for s in inside(spans, outer) if s.name != "repro.compile"]
    assert [s.name for s in phases] == [
        "repro.unit.load", "repro.stage.call", "repro.stage.to_host",
        "repro.unit.save", "repro.unit.save", "repro.unit.commit"]
    assert all(a.end <= b.start for a, b in zip(phases, phases[1:]))
    assert {s.stats["unit"] for s in phases if "unit" in s.stats} == {tag}
    assert {s.stats["stage"] for s in phases if "stage" in s.stats} == \
        {"bias_correct"}


def test_bytes_stats_are_the_bytes_on_disk(units, dataset, tmp_path):
    pipe, (unit, _) = units
    _, spans = record(tmp_path / "prof",
                      lambda: run_unit(unit, pipe, dataset.root))
    root = Path(dataset.root)
    [load] = named(spans, "repro.unit.load")
    assert load.stats["bytes"] == sum((root / rel).stat().st_size
                                      for rel in unit.inputs.values())
    saved = sorted(Path(unit.out_dir).glob("*.npy"))
    payload = sorted(np.load(p).nbytes for p in saved)
    assert sorted(s.stats["bytes"] for s in named(spans, "repro.unit.save")) \
        == payload
    [to_host] = named(spans, "repro.stage.to_host")
    assert to_host.stats["bytes"] == sum(payload)


def test_compile_spans_open_and_close_with_jax(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    x = jnp.arange(7.0)
    before = trace.counters()
    _, first = record(tmp_path / "a", lambda: f(x).block_until_ready())
    compiles = named(first, "repro.compile")
    assert {s.stats["event"] for s in compiles} == {"trace", "lower",
                                                    "backend"}
    assert all(s.stats["fun"] and s.end >= s.start for s in compiles)
    backend = [s for s in compiles if s.stats["event"] == "backend"]
    assert trace.counters()["compile.backend.n"] \
        - before.get("compile.backend.n", 0) == len(backend) >= 1
    y = (x + 1).block_until_ready()
    mid = trace.counters()
    _, second = record(tmp_path / "b", lambda: f(y).block_until_ready())
    assert not [s for s in named(second, "repro.compile")
                if s.stats["event"] == "backend"]
    assert trace.counters()["compile.backend.n"] == mid["compile.backend.n"]


def test_nested_compile_seconds_count_once():
    before = trace.counters()
    ev = "/jax/core/compile/jaxpr_trace_duration"
    jax.monitoring.record_scalar(ev, 100.0, fun_name="outer")
    jax.monitoring.record_scalar(ev, 101.0, fun_name="inner")
    jax.monitoring.record_event_time_span(ev, 101.0, 103.0,
                                          fun_name="inner")
    jax.monitoring.record_event_time_span(ev, 100.0, 105.0,
                                          fun_name="outer")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    after = trace.counters()

    def grew(key):
        return after.get(key, 0) - before.get(key, 0)
    assert grew("compile.trace.n") == 2
    assert grew("compile.trace.s") == pytest.approx(5.0)
    assert grew("compile.cache_hits") == 1


def _write_batch(raw: Path, names, rng):
    for sub in names:
        write_raw_dump(raw / f"sub-{sub}_ses-01_T1w.raw.npz",
                       (rng.normal(300, 40, (16, 16, 16))).astype(np.int16),
                       subject=sub, session="01", protocol="T1w")


def _npy_bytes(dataset_dir: Path) -> int:
    return sum(p.stat().st_size for p in dataset_dir.rglob("*.npy"))


def test_ingest_phases_and_manifest_rehash(tmp_path):
    rng = np.random.default_rng(3)
    _write_batch(tmp_path / "raw1", ["a1", "a2"], rng)
    _write_batch(tmp_path / "raw2", ["b1", "b2"], rng)
    bids = tmp_path / "bids"
    on_disk = []

    def batches():
        for raw in ("raw1", "raw2"):
            ingest_directory(tmp_path / raw, bids, "ds", device_qa=True)
            on_disk.append(_npy_bytes(bids / "ds"))
    _, spans = record(tmp_path / "prof", batches)
    ingests = named(spans, "repro.ingest")
    assert [s.stats for s in ingests] == [{"dataset": "ds"}] * 2
    for outer, raw, want in zip(ingests, ("raw1", "raw2"), on_disk):
        body = [s for s in inside(spans, outer) if s.name != "repro.compile"]
        counts = {n: len(named(body, n)) for n in {s.name for s in body}}
        assert counts == {"repro.ingest.convert": 2,
                          "repro.ingest.serialize": 2,
                          "repro.ingest.verify": 2, "repro.ingest.write": 4,
                          "repro.ingest.manifest": 1,
                          "repro.manifest.hash": 2 if raw == "raw1" else 4,
                          "repro.ingest.report": 1}
        [scan] = named(body, "repro.ingest.manifest")
        hashed = inside(body, scan)
        assert {s.name for s in hashed} == {"repro.manifest.hash"}
        assert sum(s.stats["bytes"] for s in hashed) == want
        assert sorted(s.stats["bytes"]
                      for s in named(body, "repro.ingest.convert")) == \
            sorted(p.stat().st_size
                   for p in (tmp_path / raw).glob("*.npz"))
    writes = named(spans, "repro.ingest.write")
    files = sorted(p for p in (bids / "ds").rglob("sub-*")
                   if p.is_file())
    assert sorted((s.stats["suffix"], s.stats["bytes"]) for s in writes) == \
        sorted((p.suffix, p.stat().st_size) for p in files)
    npy = sorted(s.stats["bytes"] for s in writes
                 if s.stats["suffix"] == ".npy")
    assert sorted(s.stats["bytes"]
                  for s in named(spans, "repro.ingest.verify")) == npy


def test_counters_are_the_sums_of_the_spans(units, dataset, tmp_path):
    pipe, (_, unit) = units
    _write_batch(tmp_path / "raw", ["c1"], np.random.default_rng(4))
    trace.reset()

    def work():
        run_unit(unit, pipe, dataset.root)
        ingest_directory(tmp_path / "raw", tmp_path / "bids", "ds",
                         device_qa=True)
        jax.jit(lambda x: x * 7.0 - 2.0)(jnp.ones(5)).block_until_ready()
    _, spans = record(tmp_path / "prof", work)
    got = {k: v for k, v in trace.counters().items()
           if not k.endswith(".s") and "cache" not in k}
    want = {}
    for s in spans:
        name = s.name[len("repro."):]
        if name == "compile":
            name = f"compile.{s.stats['event']}"
        want[name + ".n"] = want.get(name + ".n", 0) + 1
        if "bytes" in s.stats:
            want[name + ".bytes"] = want.get(name + ".bytes", 0) \
                + s.stats["bytes"]
    assert got == want
    assert want["compile.backend.n"] >= 1


def test_benchmark_reads_the_same_harness_spans(units, dataset, tmp_path,
                                                monkeypatch):
    pipe, (first, second) = units
    fn = pipe.fn

    def staged(inputs):
        with TraceAnnotation("stage.bias_correct"):
            return fn(inputs)
    pipe.fn = staged

    def harness_view(unit, out):
        def go():
            with TraceAnnotation("window"), \
                    TraceAnnotation("unit.bias_correct"):
                run_unit(unit, pipe, dataset.root)
        profile, spans = record(out, go)
        return [s[0] for s in bench_trace.extract(profile).spans], spans

    with_program, spans = harness_view(first, tmp_path / "on")
    assert spans and not [s for s in spans
                          if s.name.startswith(bench_trace.HOST_PREFIXES)]
    monkeypatch.setattr(trace, "span",
                        lambda name, **stats: contextlib.nullcontext())
    without, none = harness_view(second, tmp_path / "off")
    assert not [s for s in none if s.name != "repro.compile"]
    assert with_program == without == ["window", "unit.bias_correct",
                                       "stage.bias_correct"]
