#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload t1w.register --seed 7 \\
        --seconds 10 --trace 0

Reads ``BENCHMARK.json`` at the repository root. Without a TPU with as
many chips as the cell asks for it exits non-zero and prints no result.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``compared``: every number that
decided ``correct`` beside its limit. The same numbers are the last lines
of standard error. ``--tiny`` is for the CPU tests only: it skips the look
for a chip and runs the configuration's rehearsal shapes.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy shapes (tests only)")
    args = ap.parse_args(argv)
    import repro.core  # noqa: F401 — no program, no run
    from chipbench.harness import SetupError, log, run_cell
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), tiny=args.tiny)
    except SetupError as e:
        log(f"not measured: {e}")
        return 2
    log(f"correct: {result['correct']}")
    for name, c in result["compared"].items():
        log(f"compared {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
