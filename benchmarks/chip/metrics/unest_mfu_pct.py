"""The committed unest_wholebrain units' FLOPs (``unest_work.unit_work``)
over the summed wall seconds of the stage calls times the chip's bfloat16
peak."""
from chipbench import peaks, unest_work


def read(run):
    units = [u for u in run.units
             if u["ok"] and u["stage"] == "unest_wholebrain"]
    wall = sum(u["stage_s"] for u in units)
    if run.trace is None or not run.trace.devices or wall <= 0:
        return None
    flops, _ = unest_work.unit_work(run.shapes["T1w"],
                                    run.stage("unest_wholebrain"))
    peak = peaks.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops * len(units) / (wall * peak)
