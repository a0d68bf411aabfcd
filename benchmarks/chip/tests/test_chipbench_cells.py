"""Each cell's generator, window and correctness path at the rehearsal
shapes on the CPU (``tiny``: no look for a chip), the same run with the
timed path broken underneath (``correct`` must come out false), and each
cell's control: its reference computed a precision step lower must fail
the limits, alone and put in the program's place."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import generator, harness  # noqa: E402

CONFIG = json.loads((BENCH / "configs" / "t1w-mprage-1mm.json").read_text())
TINY = CONFIG["tiny"]["scans"]["T1w"]["shape"]


def tiny_run(tmp_path, workload, trace=False, patch=None, seed=12345,
             control=False):
    return harness.run_cell(workload, seed, 0.01, trace, tiny=True,
                            workdir=tmp_path / "work", patch=patch,
                            control=control)


@pytest.mark.parametrize("workload,metric", [
    ("t1w.register", "scans_per_min.register"),
    ("t1w.segment", "scans_per_min"),
    ("session.ingest", "ingest_gbps")])
def test_tiny_rehearsal_is_correct(tmp_path, workload, metric):
    res = tiny_run(tmp_path, workload, seed=2 ** 31 + 17)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {metric, "setup_s"}
    assert list(res)[-1] == "compared"
    assert not (tmp_path / "work").exists()


def test_tiny_traced_run_reports_trace_fields(tmp_path):
    res = tiny_run(tmp_path, "t1w.register", trace=True)
    assert res["correct"]
    # on the CPU no TPU plane exists: device-trace readers return nothing
    assert "register_roofline_pct" not in res["metrics"]
    assert {"unit_host_s.register", "window_compile_s.register"} <= \
        set(res["metrics"])
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _break_stage(stage, mutate):
    def patch(run, driver):
        pipe = driver.pipes[stage]
        fn = pipe.fn

        def broken(inputs):
            return mutate(dict(fn(inputs)))
        pipe.fn = broken
    return patch


def _unchanged_state(run, driver):
    """A registration whose gradient steps leave the affine where it
    started: the stage is called with a zero learning rate."""
    from repro.core import builtin_pipelines
    base = builtin_pipelines()["affine_register"].fn
    steps = run.stage("affine_register")["steps"]
    driver.pipes["affine_register"].fn = \
        lambda inputs: base(inputs, steps=steps, lr=0.0)


def _alter(name, index=0, delta=1.0):
    def mutate(out):
        a = np.array(out[name])
        a.reshape(-1)[index] += delta
        out[name] = a
        return out
    return mutate


@pytest.mark.parametrize("workload,patch", [
    ("t1w.register", _unchanged_state),
    ("t1w.register", _break_stage("affine_register", _alter("T1w_reg"))),
    ("t1w.register", _break_stage("affine_register", _alter("affine", 0,
                                                            1e-3))),
    ("t1w.segment", _break_stage("segment_unest", _alter("class_logits"))),
    ("t1w.segment", _break_stage("segment_unest",
                                 _alter("segmentation", 77, 1))),
    ("t1w.segment", _break_stage("bias_correct", _alter("bias_field", 5,
                                                        0.01))),
], ids=["register-unchanged-state", "register-altered-voxel",
        "register-altered-affine", "segment-altered-logit",
        "segment-altered-label", "bias-altered-field"])
def test_broken_stage_is_not_correct(tmp_path, workload, patch):
    res = tiny_run(tmp_path, workload, patch=patch)
    assert res["correct"] is False, res["compared"]


def _half_batch(run, driver):
    """Ingest only half of each batch's dumps."""
    import repro.core as core
    real = core.ingest_directory

    def half(raw_dir, *a, **k):
        dumps = sorted(Path(raw_dir).glob("*.npz"))
        for p in dumps[len(dumps) // 2:]:
            p.rename(p.with_suffix(".held"))
        try:
            return real(raw_dir, *a, **k)
        finally:
            for p in Path(raw_dir).glob("*.held"):
                p.rename(p.with_suffix(".npz"))
    core.ingest_directory = half
    run._restore = lambda: setattr(core, "ingest_directory", real)


def _altered_verdict(run, driver):
    """The streamed QA verdict altered where the fold produces it."""
    import dataclasses
    import repro.core.ingest as ingest
    real = ingest._fast_qa_streamed

    def altered(vol, rule):
        reason, st, digest, data, rep = real(vol, rule)
        return (reason, dataclasses.replace(st, vsum=st.vsum + 1.0), digest,
                data, rep)
    ingest._fast_qa_streamed = altered
    run._restore = lambda: setattr(ingest, "_fast_qa_streamed", real)


def _altered_bytes(run, driver):
    """One byte of every committed volume flipped as ingest writes it."""
    import repro.core.ingest as ingest
    real = ingest.atomic_write_bytes

    def flipped(path, data, **k):
        if str(path).endswith(".npy"):
            data = data[:-1] + bytes([data[-1] ^ 1])
        return real(path, data, **k)
    ingest.atomic_write_bytes = flipped
    run._restore = lambda: setattr(ingest, "atomic_write_bytes", real)


@pytest.mark.parametrize("patch", [_half_batch, _altered_verdict,
                                   _altered_bytes],
                         ids=["half-batch", "altered-verdict",
                              "altered-bytes"])
def test_broken_ingest_is_not_correct(tmp_path, patch):
    box = {}

    def apply(run, driver):
        patch(run, driver)
        box["restore"] = run._restore
    try:
        res = tiny_run(tmp_path, "session.ingest", patch=apply)
    finally:
        box.get("restore", lambda: None)()
    assert res["correct"] is False, res["compared"]


def _stage_scan(seed=7):
    return generator.make_t1w(generator.child_rngs(seed, 1)[0],
                              {"shape": TINY}).astype(np.float32)


@pytest.mark.parametrize("stage", ["affine_register", "bias_correct",
                                   "segment_unest"])
def test_control_fails_the_limits(stage):
    ref = harness.load_module(BENCH / "reference" / f"{stage}.py")
    cfg = CONFIG["stages"][stage]
    inputs = {"T1w": _stage_scan()}
    want = ref.reference(inputs, cfg)
    got = ref.reference(inputs, cfg, lowp=True)
    readings = ref.gaps(got, want, cfg)
    assert any(readings[k] > lim for k, lim in ref.LIMITS.items()), readings


def test_ingest_control_fails():
    ref = harness.load_module(BENCH / "reference" / "ingest.py")
    vol = _stage_scan().astype(np.int16)
    assert ref.expected(vol, lowp=True)["qa"] != ref.expected(vol)["qa"]


@pytest.mark.parametrize("workload", ["t1w.register", "t1w.segment",
                                      "session.ingest"])
def test_control_in_the_programs_place_is_not_correct(tmp_path, workload):
    res = tiny_run(tmp_path, workload, control=True, seed=2 ** 33 + 5)
    assert res["attempted"] >= 1
    assert res["correct"] is False, res["compared"]
    assert all(c["value"] != float("inf")
               for c in res["compared"].values()), res["compared"]


def test_window_cache_entries_are_dropped(tmp_path):
    cache = tmp_path / "cache"
    cache.mkdir()
    (cache / "setup-cache").write_bytes(b"kept")
    before = {p.name for p in cache.iterdir()}
    (cache / "window-cache").write_bytes(b"dropped")
    (cache / "window-atime").write_bytes(b"dropped")
    assert harness._drop_new_entries(cache, before) == 2
    assert [p.name for p in cache.iterdir()] == ["setup-cache"]
    assert harness._drop_new_entries(None, set()) == 0
