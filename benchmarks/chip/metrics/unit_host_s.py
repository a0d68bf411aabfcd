"""Mean seconds a committed unit spends outside its stage call: verifying
and loading inputs, moving outputs to the host, hashing and saving them,
committing provenance (UnitResult.seconds less the harness's span around
the stage, which ends at block_until_ready)."""


def read(run):
    ok = [u for u in run.units if u["ok"]]
    if not ok:
        return None
    return sum(u["seconds"] - u["stage_s"] for u in ok) / len(ok)
