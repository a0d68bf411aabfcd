"""Bytes the manifest re-scans hashed (``repro.manifest.hash``) over the
volume bytes written (``repro.ingest.write`` of ``.npy`` payloads), in the
window: 1 where each batch hashed only what it wrote; (B + 1) / 2 after B
equal batches into one dataset that each re-hash it whole."""
from chipbench import program_spans


def read(run):
    written = program_spans.stat_sum(
        [s for s in program_spans.named(run, "repro.ingest.write")
         if s.stats.get("suffix") == ".npy"], "bytes")
    if not written:
        return None
    return program_spans.stat_sum(
        program_spans.named(run, "repro.manifest.hash"), "bytes") / written
