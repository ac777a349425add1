#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  JAX's persistent compilation cache lives in the checkout's
``.jax_cache``, so only the first run of a cell there compiles.  With no
TPU, or fewer chips than the cell asks for, it exits 3 and prints no
result.  The compared numbers and their limits close standard error and
the result line.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the compilation cache has one fixed place, inside the checkout; set
    # before JAX is imported, and taken by the program's own cache setup
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(REPO), str(REPO / "src")]
    try:
        from chipbench import harness

        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_process=T_PROCESS)
    except Exception as exc:  # noqa: BLE001 — no chip or a failed run: no result
        if type(exc).__name__ == "NoChip":
            print(f"chipbench: {exc}", file=sys.stderr)
            return 3
        traceback.print_exc()
        return 1
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
