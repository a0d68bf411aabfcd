"""Raw scanner-dump bytes accepted into the BIDS tree, in Gb/s of the
window (host clock, from the window's start to the end of the last batch)."""


def read(run):
    if run.window_s <= 0 or not run.attempted:
        return None
    return run.bytes_accepted * 8 / 1e9 / run.window_s
