"""Seconds the program spent re-scanning the dataset's manifest after each
batch (``repro.ingest.manifest`` spans in the window) per GB of raw dump
accepted."""
from chipbench import program_spans


def read(run):
    return program_spans.per_gb(run, "repro.ingest.manifest")
