"""The least time the chip needs for the committed affine_register units'
steps and final warp (FLOPs and bytes from their shapes, ``work.py``),
over the device time of the ops that ran inside the stage's spans."""
from chipbench import peaks, trace, work


def read(run):
    units = [u for u in run.units
             if u["ok"] and u["stage"] == "affine_register"]
    if run.trace is None or not run.trace.devices or not units:
        return None
    ops = trace.ops_in_spans(run.trace, "stage.affine_register")
    dev_s = trace.covered_ns([(o[2], o[3]) for o in ops], float("-inf"),
                             float("inf")) / 1e9
    if dev_s <= 0:
        return None
    steps = run.stage("affine_register")["steps"]
    flops, nbytes = work.register_work(run.shapes["T1w"], steps)
    least = peaks.least_seconds(flops * len(units), nbytes * len(units),
                              run.device_kind)
    return 100.0 * least / dev_s
