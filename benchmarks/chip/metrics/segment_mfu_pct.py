"""The segmentation backbone's FLOPs per scan (``work.segment_flops``)
times committed scans, over the summed wall seconds of the segment_unest
stage calls times the chip's bfloat16 peak."""
from chipbench import peaks, work



def read(run):
    units = [u for u in run.units if u["ok"] and u["stage"] == "segment_unest"]
    wall = sum(u["stage_s"] for u in units)
    if run.trace is None or not run.trace.devices or wall <= 0:
        return None
    flops = work.segment_flops(run.shapes["T1w"], run.stage("segment_unest"))
    peak = peaks.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops * len(units) / (wall * peak)
