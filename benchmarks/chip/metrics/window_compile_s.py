"""Seconds of JAX compile events inside the window (tracing, lowering,
backend compile or persistent-cache read) per committed unit."""


def read(run):
    ok = sum(u["ok"] for u in run.units)
    return run.compile_s / ok if ok else None
