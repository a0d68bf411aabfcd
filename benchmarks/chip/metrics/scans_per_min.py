"""Scans whose every stage committed ok with provenance, per minute of the
window (host clock, from the window's start to the last commit)."""


def read(run):
    if not run.units or run.window_s <= 0:
        return None
    return run.scans_committed * 60.0 / run.window_s
