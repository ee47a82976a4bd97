"""Serve a (reduced) assigned architecture with batched requests:
prefill + greedy decode, plus the per-phase DVFS clock plan showing the
paper's headline — decode is memory-bound, so the clock drops ~40% nearly
for free while prefill stays near boost.

Run:  PYTHONPATH=src python examples/serve_lm.py [--arch qwen2-0.5b]
"""
import argparse

from repro.compile_cache import enable_compile_cache
from repro.launch import serve as serve_launch


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    args = ap.parse_args()
    serve_launch.main([
        "--arch", args.arch, "--reduced",
        "--batch", "4", "--prompt-len", "32", "--gen", "16",
        "--dvfs-report",
    ])


if __name__ == "__main__":
    main()
