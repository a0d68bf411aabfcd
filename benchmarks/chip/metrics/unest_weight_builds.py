"""Weight builds (``repro.unest.weights`` spans) in the window per
committed unit; 0 where the stage ran (``repro.unest.infer`` spans) on
weights built before the window."""
from chipbench import program_spans


def read(run):
    ok = program_spans.committed(run)
    if not ok or not program_spans.named(run, "repro.unest.infer"):
        return None
    return len(program_spans.named(run, "repro.unest.weights")) / ok
