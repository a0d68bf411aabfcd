"""The least time the chip needs for the committed unest_wholebrain units'
work (``unest_work.unit_work``: every window's convs, matmuls and block
attention, the blend and the outputs), over the device time of the ops that
ran inside the stage's spans."""
from chipbench import peaks, trace, unest_work


def read(run):
    units = [u for u in run.units
             if u["ok"] and u["stage"] == "unest_wholebrain"]
    if run.trace is None or not run.trace.devices or not units:
        return None
    ops = trace.ops_in_spans(run.trace, "stage.unest_wholebrain")
    dev_s = trace.covered_ns([(o[2], o[3]) for o in ops], float("-inf"),
                             float("inf")) / 1e9
    if dev_s <= 0:
        return None
    flops, nbytes = unest_work.unit_work(run.shapes["T1w"],
                                         run.stage("unest_wholebrain"))
    least = peaks.least_seconds(flops * len(units), nbytes * len(units),
                                run.device_kind)
    return 100.0 * least / dev_s
