"""The readers of the program's own spans (``chipbench.program_spans``): on
hand-made span lists against numbers worked by hand, on a trace without
program spans, where they read nothing, and on traced rehearsal runs of
the ingest and segmentation cells on the CPU."""
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness, program_spans, trace  # noqa: E402
from chipbench.program_spans import Span  # noqa: E402

S = 1e9                                            # ns per second
UNIT_METRICS = ["unit_load_s", "unit_to_host_s", "unit_save_s",
                "unit_backend_compiles"]
INGEST_METRICS = ["ingest_convert_s_per_gb", "ingest_write_s_per_gb",
                  "ingest_manifest_s_per_gb", "manifest_rehash_ratio"]


def read(name, run):
    return harness.metric_reader(BENCH, name).read(run)


def span(name, a, b, **stats):
    return Span(name, a * S, b * S, stats)


def fake(spans, **kw):
    t = trace.Trace(ops=[("fusion.1", "jit_scan", 2.5 * S, 3.5 * S, 0)],
                    spans=[("window", 0.0, 10 * S)], devices=[0])
    base = dict(trace=t, program_spans=spans, bytes_accepted=2e9,
                units=[{"stage": "affine_register", "ok": True}] * 2)
    base.update(kw)
    return types.SimpleNamespace(**base)


def unit_spans():
    out = []
    for u, t in (("affine_register:001:01", 1.0), ("affine_register:002:01",
                                                   5.0)):
        out += [span("repro.unit", t, t + 3.9, unit=u, attempt=1),
                span("repro.unit.load", t, t + 0.5, unit=u, bytes=10),
                span("repro.stage.call", t + 0.5, t + 3.0,
                     stage="affine_register")]
    out += [span("repro.compile", 1.6, 1.8, event="trace", fun="f"),
            span("repro.compile", 2.0, 3.0, event="backend", fun="f"),
            span("repro.stage.to_host", 4.0, 4.2, stage="affine_register"),
            span("repro.unit.save", 4.2, 4.6, unit="a", bytes=5),
            span("repro.unit.commit", 4.6, 4.7, unit="a"),
            # the second unit's copy ends past the window: 0.2 s counts
            span("repro.stage.to_host", 9.8, 10.4, stage="affine_register"),
            span("repro.unit.save", 8.0, 8.3, unit="b", bytes=5),
            span("repro.unit.save", 8.3, 8.6, unit="b", bytes=5)]
    return sorted(out, key=lambda s: s.start)


def ingest_spans():
    return [span("repro.ingest", 0.5, 9.5, dataset="cohort"),
            span("repro.ingest.convert", 1.0, 2.0, bytes=7),
            span("repro.ingest.write", 2.0, 2.5, bytes=100, suffix=".npy"),
            span("repro.ingest.write", 2.5, 2.6, bytes=3, suffix=".json"),
            span("repro.ingest.convert", 3.0, 3.5, bytes=7),
            span("repro.ingest.write", 3.5, 4.0, bytes=100, suffix=".npy"),
            span("repro.ingest.write", 4.0, 4.1, bytes=3, suffix=".json"),
            span("repro.ingest.manifest", 5.0, 9.0),
            span("repro.manifest.hash", 5.0, 6.0, bytes=100),
            span("repro.manifest.hash", 6.0, 7.0, bytes=100),
            span("repro.manifest.hash", 7.0, 8.0, bytes=100)]


@pytest.mark.parametrize("name,want", [
    ("unit_load_s", 0.5), ("unit_load_s.register", 0.5),
    ("unit_to_host_s", 0.2), ("unit_to_host_s.register", 0.2),
    ("unit_save_s", 0.5), ("unit_save_s.register", 0.5),
    ("unit_backend_compiles", 0.5), ("unit_backend_compiles.register", 0.5)])
def test_unit_readers(name, want):
    assert read(name, fake(unit_spans())) == pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    ("ingest_convert_s_per_gb", 1.5 / 2), ("ingest_write_s_per_gb", 1.2 / 2),
    ("ingest_manifest_s_per_gb", 4.0 / 2), ("manifest_rehash_ratio", 1.5)])
def test_ingest_readers(name, want):
    assert read(name, fake(ingest_spans(), units=[])) == pytest.approx(want)


def test_no_compile_in_units_reads_zero():
    spans = [s for s in unit_spans() if s.name != "repro.compile"]
    assert read("unit_backend_compiles", fake(spans)) == 0.0


@pytest.mark.parametrize("name", UNIT_METRICS + INGEST_METRICS)
def test_a_program_without_spans_reads_nothing(name):
    assert read(name, fake([])) is None
    assert read(name, fake(None, trace=None)) is None


def test_idle_by_span():
    # device busy 2.5-3.5 s of a 10 s window; gaps [0, 2.5) and [3.5, 10)
    run = fake(unit_spans())
    assert program_spans.idle_by_span(run) == pytest.approx(
        {"repro.unit.load": 2.5, "repro.stage.call": 6.5})


def traced_tiny(tmp_path, workload, seconds):
    kept = {}
    res = harness.run_cell(workload, 2 ** 32 + 101, seconds, True, tiny=True,
                           workdir=tmp_path / "work",
                           patch=lambda run, driver: kept.update(run=run))
    return res, kept["run"]


def test_tiny_ingest_reads_every_metric_and_the_closed_form(tmp_path):
    # a window longer than the rehearsal's dumps last runs every batch
    res, run = traced_tiny(tmp_path, "session.ingest", 60.0)
    assert res["correct"], res["compared"]
    batches = run.cell.traffic["batches"]
    per = run.cell.traffic["sessions_per_batch"] * len(run.scans)
    assert run.attempted == batches * per
    values = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(INGEST_METRICS) <= set(values)
    assert all(values[k] > 0 for k in INGEST_METRICS)
    assert values["manifest_rehash_ratio"] == pytest.approx(
        (batches + 1) / 2)


def test_tiny_segment_reads_every_metric(tmp_path):
    res, _ = traced_tiny(tmp_path, "t1w.segment", 0.01)
    assert res["correct"], res["compared"]
    values = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(UNIT_METRICS) <= set(values)
    assert all(values[k] > 0 for k in UNIT_METRICS[:3])
    assert values["unit_backend_compiles"] >= 0
