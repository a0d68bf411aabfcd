"""Mean seconds per committed unit of hashing and saving its outputs, fsync
included (``repro.unit.save`` spans in the window)."""
from chipbench import program_spans


def read(run):
    return program_spans.per_unit(run, "repro.unit.save")
