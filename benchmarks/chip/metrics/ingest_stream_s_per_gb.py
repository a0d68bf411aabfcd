"""Summed StreamReport.wall_s of the accepted scans (sha256, the QA fold and
its staging, per scan) per GB of raw dump accepted: the program's own
counters, no trace needed."""


def read(run):
    if not run.bytes_accepted:
        return None
    return run.stream_wall_s / (run.bytes_accepted / 1e9)
