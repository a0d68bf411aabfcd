"""The harness's work counts against numbers worked by hand at the cells'
shapes (T1w 256x256x176: N = 11,534,336 voxels; 64x64x44 = 180,224
segmentation tokens)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import peaks, work  # noqa: E402

T1W = (256, 256, 176)
SEG = {"patch": 4, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
       "d_head": 32, "d_ff": 256, "n_layers": 2, "n_classes": 4}


def test_register_work_at_the_cell_shape():
    flops, nbytes = work.register_work(T1W, 10)
    # N * (10 steps * 84 + 39) = 11,534,336 * 879
    assert flops == 10_138_681_344
    # N * 4 B * (10 steps + normalise + warp read + warp write)
    assert nbytes == 599_785_472


def test_segment_flops_at_the_cell_shape():
    assert work.segment_tokens(T1W, 4) == 180_224
    # patch projection   2*T*64*128               =      2,952,790,016
    # q/k/v/o per layer  T*(2*128*256 + 2*128*128) =     17,716,740,096
    # attention per layer 4*4*32 * T*(T+1)/2       =  8,315,102,822,400
    # MLP per layer      4*T*128*256               =     23,622,320,128
    # head               2*T*128*4                 =        184,549,376
    assert work.segment_flops(T1W, SEG) == 16_716_021_104_640


def test_fold_bytes_are_the_float32_payloads():
    assert work.fold_bytes(256 * 256 * 176 * 4) == 46_137_344
    assert work.fold_bytes(96 * 96 * 60 * 100 * 4) == 221_184_000


def test_peaks_and_least_time():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    # register: bound by bytes, 599,785,472 / 819e9 s
    flops, nbytes = work.register_work(T1W, 10)
    assert peaks.least_seconds(flops, nbytes, "TPU v5 lite") == \
        pytest.approx(599_785_472 / 819e9)
    # segment at 4.7 s a scan: 1.67e13 / (4.7 s * 197e12) = 1.805%
    share = work.segment_flops(T1W, SEG) / (4.7 * p["bf16_flops_per_s"])
    assert share == pytest.approx(0.018054, rel=1e-4)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
