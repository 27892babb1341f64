"""Benchmark orchestrator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (us_per_call empty for pure
accuracy/cost numbers; derived empty for pure timings) and writes a JSON
dump to experiments/bench_results.json.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

# allow `python benchmarks/run.py` as well as `python -m benchmarks.run`,
# with or without PYTHONPATH=src
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

# a fixed 4-device host mesh for the sharded assoc_scale section, matching
# scripts/tier1.sh (must land in the environment before jax first imports;
# a user-provided count wins)
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               + os.environ.get("XLA_FLAGS", "")).strip()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: cost,convergence,training,"
                         "local_iters,kernels,roofline,assoc_scale,"
                         "live_hfel,admission")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: shrink the assoc_scale stress points "
                         "(skips the multi-minute N>=1000 runs) and swap "
                         "live_hfel's full three-policy run for a 2-round "
                         "verify-on smoke, so each section finishes in "
                         "under a minute; quick results are printed but NOT "
                         "persisted, so bench_guard baselines are never "
                         "disturbed")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    results = {}
    lines = []

    def report(name, us_per_call, derived):
        us = f"{us_per_call:.1f}" if us_per_call is not None else ""
        d = derived if derived is not None else ""
        line = f"{name},{us},{d}"
        lines.append(line)
        print(line, flush=True)

    print("name,us_per_call,derived", flush=True)

    sections = {
        "cost": lambda: __import__("benchmarks.paper_cost",
                                   fromlist=["run"]).run(report),
        "convergence": lambda: __import__("benchmarks.paper_convergence",
                                          fromlist=["run"]).run(report),
        "training": lambda: __import__(
            "benchmarks.paper_training",
            fromlist=["run"]).run(report, rounds=args.rounds),
        "local_iters": lambda: __import__(
            "benchmarks.paper_local_iters", fromlist=["run"]).run(report),
        "kernels": lambda: __import__("benchmarks.kernel_micro",
                                      fromlist=["run"]).run(report),
        "roofline": lambda: __import__("benchmarks.roofline_table",
                                       fromlist=["run"]).run(report),
        "assoc_scale": lambda: __import__(
            "benchmarks.assoc_scale",
            fromlist=["run"]).run(report, quick=args.quick),
        "live_hfel": lambda: __import__(
            "benchmarks.live_hfel",
            fromlist=["run"]).run(report, quick=args.quick),
        "admission": lambda: __import__(
            "benchmarks.admission",
            fromlist=["run"]).run(report, quick=args.quick),
    }
    chosen = (args.only.split(",") if args.only else list(sections))
    ok = True
    for name in chosen:
        try:
            results[name] = sections[name]()
        except Exception:
            ok = False
            traceback.print_exc()
            report(f"{name}/FAILED", None, "see stderr")

    if args.quick:
        print("quick mode: results not persisted", flush=True)
        if not ok:
            sys.exit(1)
        return

    def load_json(path):
        if not os.path.exists(path):
            return {}
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    os.makedirs("experiments", exist_ok=True)
    out_path = "experiments/bench_results.json"
    prev_path = "experiments/bench_results.prev.json"
    fresh = {k: v for k, v in results.items() if not callable(v)}
    merged = load_json(out_path)
    # rotate baselines for scripts/bench_guard.py PER SECTION: only sections
    # this run actually refreshed move their previous results into the
    # baseline file. A `--only` run therefore cannot rotate away unrelated
    # sections' baselines, and a crashed section keeps its comparison point.
    rotated = {name: merged[name] for name in fresh if name in merged}
    if rotated:
        prev = load_json(prev_path)
        prev.update(rotated)
        with open(prev_path, "w") as f:
            json.dump(prev, f, indent=1, default=str)
    # accumulate sections across --only runs, but drop stale data for any
    # section that was chosen this run and FAILED — absence signals failure
    for name in chosen:
        if name not in fresh:
            merged.pop(name, None)
    merged.update(fresh)
    with open(out_path, "w") as f:
        json.dump(merged, f, indent=1, default=str)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
