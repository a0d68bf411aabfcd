"""Mean seconds per committed unit of copying the stage's outputs to the
host (``repro.stage.to_host`` spans in the window; the harness's stage call
has already waited for the device)."""
from chipbench import program_spans


def read(run):
    return program_spans.per_unit(run, "repro.stage.to_host")
