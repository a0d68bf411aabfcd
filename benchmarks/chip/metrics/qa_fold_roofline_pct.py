"""Bytes folded by the QA+checksum kernel on the chip, over the kernel's
summed device time in the trace (the ops of its ``_qa_call`` program), as a
share of the HBM peak."""
from chipbench import peaks, trace, work


def read(run):
    if run.trace is None or not run.trace.devices \
            or not run.payload_bytes_folded:
        return None
    lo, hi = run.trace.window()
    ops = [(o[2], o[3]) for o in run.trace.ops if "_qa_call" in o[1]]
    dev_s = trace.covered_ns(ops, lo, hi) / 1e9
    if dev_s <= 0:
        return None
    least = peaks.least_seconds(0, work.fold_bytes(run.payload_bytes_folded),
                              run.device_kind)
    return 100.0 * least / dev_s
