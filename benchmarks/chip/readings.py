#!/usr/bin/env python3
"""The readings a cell's limits are set from: the program's numbers and
the control's, on many seeds, in one process.

    python3 benchmarks/chip/readings.py --workload <cell> \\
        --seeds 11,12,13 --seconds <s> [--rehearse-cpu]

For each seed it makes one run of the cell as ``run.py`` does (set-up,
a window of ``--seconds``, the check) and then computes the control on
the same samples: the plain reference in the program's place at the
nearest precision below the configuration's (``reference.fft_high``).
One JSON line per seed: the seed, the checks with their limits, the
control's readings, ``correct``, ``setup_s`` and the programs built
inside the window.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    import importlib.util
    spec = importlib.util.spec_from_file_location("chipbench_run",
                                                  HERE / "run.py")
    run = importlib.util.module_from_spec(spec)
    sys.modules["chipbench_run"] = run
    spec.loader.exec_module(run)
    cell = run.load_cell(args.workload, rehearse=args.rehearse_cpu)
    for seed in (int(s) for s in args.seeds.split(",")):
        run.T_PROCESS = run.time.perf_counter()
        try:
            res = run.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace=False, rehearse=args.rehearse_cpu,
                               control=True)
        except run.NoChip as e:
            print(f"readings.py: {e}", file=sys.stderr)
            return 2
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": res["correct"], "checks": res["checks"],
            "control": res["control"], "setup_s": res["setup_s"],
            "window_builds": res["window"]["builds"],
            "metrics": res["metrics"],
            "memory_peak_bytes": res["device"]["memory_peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    if sys.path and Path(sys.path[0]).resolve() == HERE:
        sys.path.pop(0)
    sys.exit(main())
