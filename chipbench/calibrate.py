#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: the compared
numbers of the program and of its controls, for one cell on many seeds.

  python3 chipbench/calibrate.py --workload <cell> --seeds 101-112 --seconds 4

Each seed runs in a process of its own (the chip belongs to one process at
a time, and this parent never imports JAX).  One JSON line per seed on
stdout: the program's numbers under "checks", and under "control" those of
the reference with int8 weights put in the program's place and of the
served tokens shifted by one id ("altered"), each with the ``correct`` the
run's own checks give it.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def one(workload: str, seed: int, seconds: float) -> None:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(REPO), str(REPO / "src")]
    from chipbench import harness

    out = harness.run_cell(workload, seed, seconds, False,
                           t_process=time.perf_counter(), control=True)
    print(json.dumps({"seed": seed, "correct": out["correct"],
                      "attempted": out["attempted"],
                      "checks": out["checks"], "control": out["control"]}),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last, e.g. 101-112")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--one", type=int, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.one is not None:
        one(args.workload, args.one, args.seconds)
        return 0
    lo, _, hi = args.seeds.partition("-")
    for seed in range(int(lo), int(hi or lo) + 1):
        p = subprocess.run([sys.executable, __file__, "--workload", args.workload,
                            "--seeds", args.seeds, "--seconds", str(args.seconds),
                            "--one", str(seed)], stdout=subprocess.PIPE, text=True)
        lines = p.stdout.strip().splitlines()
        print(lines[-1] if p.returncode == 0 and lines else
              json.dumps({"seed": seed, "rc": p.returncode}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
