"""End-to-end driver: train a ~100M-param llama3.2-family model for a few
hundred steps on the sharded data pipeline, with async checkpointing and a
mid-run simulated crash + resume.

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import tempfile
from pathlib import Path

import numpy as np

from repro.launch.env import enable_compile_cache
from repro.launch.train import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="llama3.2-1b")
    args = ap.parse_args()

    with tempfile.TemporaryDirectory() as td:
        data = str(Path(td) / "data")
        ckpt = str(Path(td) / "ckpt")
        half = args.steps // 2
        print(f"=== phase 1: {half} steps, then 'crash' ===")
        _, losses1 = train(args.arch, steps=half, batch=8, seq=128,
                           data_dir=data, ckpt_dir=ckpt, ckpt_every=25)
        print(f"=== phase 2: resume from checkpoint, to {args.steps} ===")
        _, losses2 = train(args.arch, steps=args.steps, batch=8, seq=128,
                           data_dir=data, ckpt_dir=ckpt, ckpt_every=50,
                           resume=True)
        print(f"loss: start {np.mean(losses1[:10]):.3f} -> "
              f"end {np.mean(losses2[-10:]):.3f}")
        assert np.mean(losses2[-10:]) < np.mean(losses1[:10]), \
            "training should reduce loss"
        print("OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
