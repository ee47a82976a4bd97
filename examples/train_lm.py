"""End-to-end driver: train a (reduced) assigned architecture for a few
hundred steps with checkpointing + fault tolerance, then print the DVFS
clock plan for the compiled step.

This is the deliverable (b) end-to-end example: it exercises the data
pipeline, model, optimizer, checkpoint manager and the paper's technique
in one run.

Run:  PYTHONPATH=src python examples/train_lm.py [--arch qwen2-0.5b]
"""
import argparse
import sys

from repro.compile_cache import enable_compile_cache
from repro.launch import train as train_launch


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()
    train_launch.main([
        "--arch", args.arch, "--reduced",
        "--steps", str(args.steps),
        "--batch", "8", "--seq", "128",
        "--lr", "3e-3",
        "--ckpt-dir", "/tmp/repro_example_ckpt",
        "--dvfs-report",
    ])


if __name__ == "__main__":
    main()
