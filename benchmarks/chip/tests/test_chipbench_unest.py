"""The ``t1w.unest`` cell on the CPU at its rehearsal shape (a 32x32x24
T1w, padded to one full-width 96³ window of the published model): the
run's readings lie within the rehearsal's bounds; one label or one
confidence altered, or the control in the program's place, reads not
correct; the control fails the limits alone; and the unit's work is
counted as worked by hand at 256x256x176."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import generator, harness, unest_work  # noqa: E402

CONFIG = json.loads((BENCH / "configs" / "t1w-unest-wholebrain.json")
                    .read_text())
STAGE = CONFIG["stages"]["unest_wholebrain"]
TINY = CONFIG["tiny"]["scans"]["T1w"]["shape"]


def tiny_run(tmp_path, patch=None, control=False, seed=2 ** 31 + 61):
    return harness.run_cell("t1w.unest", seed, 0.01, False, tiny=True,
                            workdir=tmp_path / "work", patch=patch,
                            control=control)


# The rehearsal's own bounds, since its one 96^3 window is 97% zero
# padding, where the bfloat16 program departs further from the float32
# reference than on the cell's full windows. At the cell's limits, over 5
# seeds (max p_ref 1, 75-77% of voxels decided), the program reads
# confidence 0.107-0.158 and labels 0 to 5.4e-5 (1 voxel in 18,531), the
# control 0.574-0.671 and 0.063-0.067; on the chip at full size the program
# reads 0.023. Each bound lies near the geometric mean of the two.
TINY_BOUNDS = {"confidence": 0.3, "labels": 1e-3}


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    return tiny_run(tmp_path_factory.mktemp("rehearsal"))


def test_tiny_rehearsal_is_correct(rehearsal):
    res = rehearsal
    for name, bound in TINY_BOUNDS.items():
        assert res["compared"][f"unest_wholebrain.{name}"]["value"] <= bound, \
            res["compared"]
    assert res["compared"]["unest_wholebrain.provenance"]["value"] == 0
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"scans_per_min", "setup_s"}
    assert set(res["compared"]) == {"unest_wholebrain.confidence",
                                    "unest_wholebrain.labels",
                                    "unest_wholebrain.provenance"}


def _altered(name):
    """The stage's output altered at its most confident voxel, which every
    reading of the limits counts as decided: the label moved to the next
    class, or the confidence raised by 1."""
    def patch(run, driver):
        pipe = driver.pipes["unest_wholebrain"]
        fn = pipe.fn

        def broken(inputs):
            out = {k: np.array(v) for k, v in fn(inputs).items()}
            at = np.unravel_index(np.argmax(out["label_confidence"]),
                                  out["label_confidence"].shape)
            if name == "segmentation":
                out[name][at] = (int(out[name][at]) + 1) % STAGE["classes"]
            else:
                out[name][at] += 1.0
            return out
        pipe.fn = broken
    return patch


@pytest.mark.parametrize("name,reading", [("segmentation", "labels"),
                                          ("label_confidence", "confidence")])
def test_an_altered_output_is_not_correct(tmp_path, rehearsal, name,
                                         reading):
    res = tiny_run(tmp_path, patch=_altered(name))
    assert res["correct"] is False, res["compared"]
    key = f"unest_wholebrain.{reading}"
    # the same seed unaltered: the one altered voxel alone moves the reading
    assert res["compared"][key]["value"] > \
        rehearsal["compared"][key]["value"], res["compared"]


def test_control_fails_the_limits():
    ref = harness.load_module(BENCH / "reference" / "unest_wholebrain.py")
    vol = generator.make_t1w(generator.child_rngs(7, 1)[0],
                             {"shape": TINY}).astype(np.float32)
    want = ref.reference({"T1w": vol}, STAGE)
    got = ref.reference({"T1w": vol}, STAGE, lowp=True)
    readings = ref.gaps(got, want, STAGE)
    assert any(readings[k] > lim for k, lim in ref.LIMITS.items()), readings


def test_control_in_the_programs_place_is_not_correct(tmp_path):
    res = tiny_run(tmp_path, control=True, seed=2 ** 33 + 9)
    assert res["attempted"] >= 1
    assert res["correct"] is False, res["compared"]
    assert all(c["value"] != float("inf")
               for c in res["compared"].values()), res["compared"]


def test_unit_work_at_the_cell_shape():
    # per 96^3 window (tokens g^3 = 24^3, blocks of T = 216, F = 32):
    # embed     2*64*128*24^3 + bias                          228,261,888
    # level 0   positions + 2 layers of 13,824 tokens x
    #           (2*128*384 + 2*128^2 + 16*128^2 + 14*128 + 2*128 + 768
    #           + 512) + 64 blocks x (4*216^2*128 + 3*216^2*4)
    #                                                      14,094,729,216
    # agg 1     (2*27*128 + 8)*256*24^3 + 26*256*12^3      24,500,994,048
    # level 1   the same at 1,728 tokens, 8 blocks, d 256   6,241,591,296
    # agg 2     (2*27*256 + 8)*512*12^3 + 26*512*6^3       12,240,543,744
    # level 2   8 layers at 216 tokens, 1 block, d 512     11,677,077,504
    # final LN  7*512*216                                         774,144
    # enc0..3   Res(1->32) at 96^3, up + Res(64) at 48^3,
    #           Res(128->64) at 24^3, Res(256->128) at 12^3
    #           51,017,416,704 + 50,826,313,728 + 9,415,360,512
    #           + 4,703,698,944
    # dec4      2*27*512*1024*27                              764,411,904
    # up5..up1  9,627,918,336 + 16,088,924,160 + 32,193,773,568
    #           + 77,134,823,424 + 154,524,450,816
    # head      (2*32 + 1)*133*96^3                     7,648,542,720
    assert unest_work.window_flops(STAGE) == 482_929_606_656
    assert unest_work.param_count(STAGE) == 83_535_909
    assert unest_work.n_windows((256, 256, 176), STAGE) == 196   # 7 x 7 x 4
    assert unest_work.n_windows(TINY, STAGE) == 1
    flops, nbytes = unest_work.unit_work((256, 256, 176), STAGE)
    # 196 windows x (window + 134 * 96^3 blend adds) + 3 V normalise
    # + 6 * 133 * V for the average's reductions, V = 11,534,336
    assert flops == 94_686_678_614_016
    # 4 V volume + 2 B * 83,535,909 weights + 2 * 4 * 133 * V buffer
    # written and read + 5 V outputs
    assert nbytes == 12_543_414_346
    # bound by compute: 94.69 TFLOP / 197 TFLOP/s = 0.4806 s a scan
    assert flops / 197e12 > nbytes / 819e9
