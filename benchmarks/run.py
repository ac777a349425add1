"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Module map:
  e2e_latency       -> Fig 1 / Fig 9   (cold start vs systems vs warm)
  metadata_restore  -> Fig 2 / Fig 10  (metadata restore + replay ops)
  prefetch          -> Fig 4           (sync / advisory-async / guaranteed)
  working_set       -> Fig 5 / Table 1 (shared/private/zero composition)
  ablation          -> Fig 11          (restore optimizations, incremental)
  concurrency       -> Fig 12 (+Fig 3 interference) (burst max latency)
  cluster           -> N-node placement policies (locality vs baselines)
  dedup             -> content-addressed chunk store: 1 base + K deltas
                       over 3 nodes, CAS on vs off; merged into
                       BENCH_coldstart.json under "dedup"
  qos               -> Invocation API v2: LATENCY vs BATCH open-loop mix
  rollout           -> train->serve continuous-delta pipeline: mid-flight
                       versioned publishes, canary/promote/rollback,
                       serve/train colocation; merged into
                       BENCH_coldstart.json under "rollout"
  restore_bandwidth -> device-restore fast path (upload stream + overlay
                       patch) vs the storage roofline; merged into
                       BENCH_coldstart.json under "device_restore"
  roofline          -> EXPERIMENTS.md §Roofline (from dry-run artifacts)

``e2e_latency`` additionally drops ``BENCH_coldstart.json`` at the repo
root (per-mode TTFT / working-set time / total restore time, the
delta-chain economics, and the ``memory_pressure`` scenario — budget <
sum of images, N concurrent cold starts completing via the reclaim
ladder, with the ledger's per-kind memory high-water marks) so CI can
track the cold-start trajectory.  Set ``BENCH_SMOKE=1`` for the CI-sized
run (one function, one repetition).
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

from repro.launch.compile_cache import enable_compile_cache

REPO_ROOT = Path(__file__).resolve().parents[1]

MODULES = [
    "e2e_latency",
    "metadata_restore",
    "prefetch",
    "working_set",
    "ablation",
    "concurrency",
    "cluster",
    "dedup",
    "qos",
    "prewarm",
    "scale",
    "rollout",
    "restore_bandwidth",
    "roofline",
]


def _write_summary(name: str, mod, summary: dict) -> Path:
    """One BENCH_<target>.json per module by default; a module that sets
    ``BENCH_TARGET``/``SUMMARY_KEY`` merges under a key of a shared file
    (the cluster scenario rides in BENCH_coldstart.json)."""
    target = getattr(mod, "BENCH_TARGET", name.replace("e2e_latency", "coldstart"))
    out = REPO_ROOT / f"BENCH_{target}.json"
    key = getattr(mod, "SUMMARY_KEY", None)
    try:
        data = json.loads(out.read_text()) if out.exists() else {}
    except json.JSONDecodeError:
        data = {}
    if key:
        data[key] = summary
    else:
        # keyless modules own the top level but must not clobber sibling
        # modules' merged keys (e.g. --only e2e_latency after --only cluster)
        data.update(summary)
    out.write_text(json.dumps(data, indent=2, sort_keys=True))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated module list")
    args = ap.parse_args()
    mods = args.only.split(",") if args.only else MODULES
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name in mods:
        t0 = time.time()
        mod = error = None
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            for row in mod.run():
                n, us, derived = row
                print(f"{n},{us:.1f},{derived}")
        except Exception as e:
            failures += 1
            error = f"{type(e).__name__}: {e}"
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
        # a failed scenario must be VISIBLY failed, not silently absent:
        # whatever partial SUMMARY it accumulated is written, stamped with
        # the error, and the harness exits non-zero below
        summary = getattr(mod, "SUMMARY", None) if mod is not None else None
        if error is not None:
            summary = dict(summary or {})
            summary["error"] = error
        if summary:
            out = _write_summary(name, mod, summary)
            print(f"# wrote {out}", flush=True)
        # merge regression guard: a module that declares a SUMMARY_KEY
        # must actually land it (or its error stamp) in the shared file —
        # an empty SUMMARY silently skips _write_summary, and that is
        # exactly the failure mode that left qos absent from
        # BENCH_coldstart.json for two releases
        if mod is not None and getattr(mod, "SUMMARY_KEY", None):
            target = getattr(mod, "BENCH_TARGET", name)
            out = REPO_ROOT / f"BENCH_{target}.json"
            landed = False
            try:
                landed = mod.SUMMARY_KEY in json.loads(out.read_text())
            except (OSError, json.JSONDecodeError):
                pass
            if not landed:
                failures += 1
                print(
                    f"{name},nan,ERROR:summary key "
                    f"{mod.SUMMARY_KEY!r} never landed in {out.name}",
                    flush=True,
                )
        print(f"# {name} finished in {time.time()-t0:.1f}s", flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
