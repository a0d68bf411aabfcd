"""The whole affine_register stage call's share of the chip's peak: the
least time for its work (``work.py``) over the stage's wall seconds, idle
time included."""
from chipbench import peaks, work



def read(run):
    units = [u for u in run.units
             if u["ok"] and u["stage"] == "affine_register"]
    wall = sum(u["stage_s"] for u in units)
    if run.trace is None or not run.trace.devices or wall <= 0:
        return None
    steps = run.stage("affine_register")["steps"]
    flops, nbytes = work.register_work(run.shapes["T1w"], steps)
    least = peaks.least_seconds(flops * len(units), nbytes * len(units),
                              run.device_kind)
    return 100.0 * least / wall
