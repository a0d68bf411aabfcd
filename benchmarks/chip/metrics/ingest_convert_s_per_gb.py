"""Seconds the program spent reading raw dumps and taking them apart
(``repro.ingest.convert`` spans in the window) per GB of raw dump
accepted."""
from chipbench import program_spans


def read(run):
    return program_spans.per_gb(run, "repro.ingest.convert")
