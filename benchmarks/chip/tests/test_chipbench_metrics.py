"""Each metric reader on a hand-made run, against numbers worked by hand;
a reader with nothing to read returns nothing."""
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import harness, trace, work  # noqa: E402

T1W = [256, 256, 176]
SEG = {"patch": 4, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
       "d_head": 32, "d_ff": 256, "n_layers": 2, "n_classes": 4}
S = 1e9                                            # ns per second


def read(name, run):
    return harness.metric_reader(BENCH, name).read(run)


def fake(**kw):
    t = trace.Trace(
        ops=[("fusion.1", "jit_scan", 1 * S, 5 * S, 0),
             ("_qa_call.1", "jit__qa_call", 6 * S, 6.5 * S, 0)],
        spans=[("window", 0.0, 10 * S), ("stage.affine_register", 0.5 * S,
                                          5.5 * S)],
        devices=[0])
    stages = {"affine_register": {"steps": 10}, "segment_unest": SEG}
    base = dict(trace=t, device_kind="TPU v5 lite", shapes={"T1w": T1W},
                stage=stages.get, window_s=10.0, setup_s=42.0, attempted=2,
                scans_committed=2, bytes_accepted=2e9, stream_wall_s=9.0,
                payload_bytes_folded=int(4.095e9), compile_s=3.0,
                units=[{"stage": "affine_register", "ok": True,
                        "seconds": 6.0, "stage_s": 5.0},
                       {"stage": "affine_register", "ok": True,
                        "seconds": 4.0, "stage_s": 3.0}])
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_end_to_end_readers():
    run = fake()
    assert read("scans_per_min", run) == pytest.approx(12.0)
    assert read("scans_per_min.register", run) == pytest.approx(12.0)
    assert read("ingest_gbps", run) == pytest.approx(1.6)
    assert read("setup_s", run) == 42.0


def test_host_and_counter_readers():
    run = fake()
    for cell in ("", ".register"):
        assert read("unit_host_s" + cell, run) == pytest.approx(1.0)
        assert read("window_compile_s" + cell, run) == pytest.approx(1.5)
    assert read("ingest_stream_s_per_gb", run) == pytest.approx(4.5)


def test_trace_readers():
    run = fake()
    flops, nbytes = work.register_work(T1W, 10)
    least = 2 * nbytes / 819e9                       # bytes bound the unit
    # the stage's ops: fusion.1, 4 s of device time
    assert read("register_roofline_pct", run) == pytest.approx(
        100 * least / 4.0)
    assert read("step_mfu.register", run) == pytest.approx(
        100 * least / 8.0)
    # folded 4.095 GB in 0.5 s of _qa_call: 8.19 GB/s, 1% of 819 GB/s
    assert read("qa_fold_roofline_pct", run) == pytest.approx(1.0)
    assert read("step_mfu.ingest", run) == pytest.approx(0.05)
    # busy 4.5 s of a 10 s window
    assert read("device_idle_pct.stage", run) == pytest.approx(55.0)
    assert read("device_idle_pct.ingest", run) == pytest.approx(55.0)
    assert read("device_idle_pct.register", run) == pytest.approx(55.0)


def test_segment_mfu():
    run = fake(units=[{"stage": "segment_unest", "ok": True, "seconds": 5.0,
                       "stage_s": 4.7}])
    assert read("segment_mfu_pct", run) == pytest.approx(
        100 * 16_716_021_104_640 / (4.7 * 197e12))


@pytest.mark.parametrize("name", [
    "register_roofline_pct", "step_mfu.register", "segment_mfu_pct",
    "qa_fold_roofline_pct", "step_mfu.ingest", "device_idle_pct.stage"])
def test_no_chip_trace_reads_nothing(name):
    run = fake(trace=trace.Trace([], [("window", 0.0, 1.0)], []))
    assert read(name, run) is None
