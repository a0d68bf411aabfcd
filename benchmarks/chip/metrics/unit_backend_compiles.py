"""Backend compiles (``repro.compile`` spans with ``event`` ``backend``: an
XLA compile or a persistent-cache read) in the window per committed unit;
0 where units ran and nothing compiled."""
from chipbench import program_spans


def read(run):
    ok = program_spans.committed(run)
    if not ok or not program_spans.named(run, "repro.unit"):
        return None
    return sum(s.stats.get("event") == "backend"
               for s in program_spans.named(run, "repro.compile")) / ok
