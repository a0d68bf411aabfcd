"""The whole ingest window's share of the chip's peak: the least time for
the fold's bytes over the window's seconds, everything the host does
included."""
from chipbench import peaks, work



def read(run):
    if run.trace is None or not run.trace.devices \
            or not run.payload_bytes_folded:
        return None
    lo, hi = run.trace.window()
    least = peaks.least_seconds(0, work.fold_bytes(run.payload_bytes_folded),
                              run.device_kind)
    return 100.0 * least / ((hi - lo) / 1e9)
