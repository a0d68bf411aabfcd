"""Seconds of the sliding-window program (``repro.unest.infer`` spans in
the window, volume upload to ``block_until_ready``) per window it ran, the
windows read from each span's ``windows`` stat."""
from chipbench import program_spans


def read(run):
    found = program_spans.named(run, "repro.unest.infer")
    windows = program_spans.stat_sum(found, "windows")
    if not windows:
        return None
    return sum(s.end - s.start for s in found) / 1e9 / windows
