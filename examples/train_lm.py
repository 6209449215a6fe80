"""End-to-end LM training driver (deliverable b: the e2e example).

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

Trains a ~25M-parameter llama-family model (the reduced qwen2-0.5b config
widened back up to a CPU-tractable "real" size) for a few hundred steps on
the synthetic Zipf corpus, with checkpoints, resume, and the full sharded
train step — the same code path the 512-chip dry-run lowers.
"""
import argparse
import dataclasses
import sys

sys.argv = [sys.argv[0]]  # keep sub-arg parsing clean when run via -m

from repro.configs import get_config, reduced_for_smoke
from repro.launch import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    args, _ = ap.parse_known_args()

    # a ~25M-param model: reduced family scaled up to be a real (if small) LM
    train.main([
        "--arch", "qwen2-0.5b", "--reduced",
        "--steps", str(args.steps),
        "--batch", "16", "--seq", "256",
        "--ckpt-dir", args.ckpt, "--ckpt-every", "100",
        "--log-every", "20",
    ])


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
