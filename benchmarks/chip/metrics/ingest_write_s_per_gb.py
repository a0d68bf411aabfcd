"""Seconds the program spent writing volumes and sidecars into the BIDS
tree, fsync included (``repro.ingest.write`` spans in the window), per GB
of raw dump accepted."""
from chipbench import program_spans


def read(run):
    return program_spans.per_gb(run, "repro.ingest.write")
