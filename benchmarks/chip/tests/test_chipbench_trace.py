"""The reduction from a profiler trace to busy time, kernel time, idle gaps
and the breakdown, on hand-made traces and on a small recorded one."""
import gzip
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def made():
    ops = [("fusion.1", "jit_step", 0.0, 10.0, 0),
           ("fusion.2", "jit_step", 5.0, 20.0, 0),
           ("custom-call.3", "jit__qa_call", 30.0, 40.0, 0)]
    spans = [("window", 0.0, 50.0), ("unit.affine_register", 0.0, 45.0),
             ("stage.affine_register", 0.0, 25.0)]
    return trace.Trace(ops, spans, [0])


def test_union_and_busy():
    t = made()
    assert trace.union([(0, 10), (5, 20), (30, 40)], 0, 50) == \
        [(0, 20), (30, 40)]
    assert trace.union([(0, 10)], 2, 5) == [(2, 5)]
    assert trace.busy_s(t, 0, 50) == pytest.approx(30e-9)
    assert trace.busy_s(t, 10, 35) == pytest.approx(15e-9)


def test_busy_is_averaged_over_devices():
    t = made()
    t.ops.append(("fusion.9", "jit_step", 0.0, 50.0, 1))
    t.devices = [0, 1]
    assert trace.busy_s(t, 0, 50) == pytest.approx((30e-9 + 50e-9) / 2)


def test_ops_in_spans_and_top_ops():
    t = made()
    inside = trace.ops_in_spans(t, "stage.")
    assert [o[0] for o in inside] == ["fusion.1", "fusion.2"]
    assert trace.top_ops(t.ops, 2) == [["jit_step/fusion.2", 15e-9],
                                       ["jit_step/fusion.1", 10e-9]]


def test_idle_gaps_are_named_by_the_host_span():
    t = made()
    gaps = trace.idle_gaps(t, 0, 50)
    # [20, 30): midpoint 25 is past the stage's end, inside the unit
    # [40, 50): midpoint 45 is past the unit's end
    assert [(g[0], g[1]) for g in gaps] == [
        ("unit.outside_stage", pytest.approx(10e-9)),
        ("harness", pytest.approx(10e-9))]
    gaps = trace.idle_gaps(t, 0, 50, extra=[("compile", 22.0, 28.0)])
    assert gaps[0][0] == "compile"


def test_breakdown_lists_ops_and_gaps():
    b = trace.breakdown(made(), 0, 50)
    assert b["device_ops"][0][0] == "jit_step/fusion.2"
    assert len(b["idle_gaps"]) == 2


def test_window_is_required():
    t = trace.Trace([], [], [])
    with pytest.raises(ValueError):
        t.window()


def test_names_are_shortened():
    assert trace.op_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a)") == \
        "fusion.3"
    assert trace.module_name("jit__qa_call(1234)") == "jit__qa_call"


@pytest.fixture(scope="module")
def recorded():
    """A v5e trace of three 1024x1024 matmul+tanh launches under
    ``window``/``unit.demo``/``stage.demo`` and one QA fold under
    ``ingest.batch``."""
    from jax.profiler import ProfileData
    raw = gzip.decompress((DATA / "v5e_small.xplane.pb.gz").read_bytes())
    return trace.extract(ProfileData.from_serialized_xspace(raw))


def test_recorded_trace_reduces(recorded):
    t = recorded
    assert t.devices == [0]
    assert [s[0] for s in t.spans] == ["window", "unit.demo", "stage.demo",
                                       "ingest.batch"]
    lo, hi = t.window()
    assert hi - lo == pytest.approx(59_952_334)
    mods = {o[1] for o in t.ops}
    assert {"jit__lambda", "jit__qa_checksum_2d"} <= mods
    tanh = [o for o in t.ops if o[0] == "convolution_tanh_fusion"]
    assert len(tanh) == 3
    # the device clock runs 1.3-1.9 ms behind the host's: each launch lies
    # that far before its host dispatch
    assert 42_000_000 < tanh[0][2] < 43_280_485 < tanh[2][2]
    busy = trace.busy_s(t, lo, hi)
    assert 0 < busy < (hi - lo) / 1e9
    b = trace.breakdown(t, lo, hi)
    assert b["device_ops"][0][0].startswith("jit_")
    assert {g[0] for g in b["idle_gaps"]} <= {
        "stage.demo", "unit.outside_stage", "ingest.batch", "harness"}
