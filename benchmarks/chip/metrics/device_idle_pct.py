"""Share of the window in which no op ran on the chip (trace)."""
from chipbench import trace


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.trace.window()
    return 100.0 * (1.0 - trace.busy_s(run.trace, lo, hi) / ((hi - lo) / 1e9))
