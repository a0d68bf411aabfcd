"""Mean seconds per committed unit of verifying and loading its inputs
(``repro.unit.load`` spans in the window, on whichever thread loaded)."""
from chipbench import program_spans


def read(run):
    return program_spans.per_unit(run, "repro.unit.load")
