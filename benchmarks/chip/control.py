#!/usr/bin/env python3
"""Run a cell's control on the chip: for each seed, one run of the cell at
its own size (a short window) with the control in the program's place
(each stage's reference a precision step lower; for ingest, the volumes
rounded to bfloat16 where ingest converts them), checked as a run is.
``correct`` has to come out false; the readings are the limits' upper
ends. All seeds run in one process, so set-up compiles once.

    python3 benchmarks/chip/control.py --workload t1w.segment \\
        --seeds 11,12,13 --seconds 1

Prints one JSON line per seed: ``correct`` and ``compared`` (the
control's readings, each beside its limit). The benchmark's own runs never
run the control.
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
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    from chipbench.harness import run_cell
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run_cell(args.workload, seed, args.seconds, False,
                       tiny=args.tiny, control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "compared": res["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
