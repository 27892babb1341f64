"""One run of one cell:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (everything before the first timed request, compiles included),
then a closed loop of requests for ``--seconds``, then the check of a
seeded sample of the answers against the plain reference. The last line of
standard output is the result object; the numbers compared, each beside
its limit, are the last lines of standard error and the last key of the
result. A traced run (``--trace 1``) records the window with the JAX
profiler and reports the per-layer metrics. Every metric, end-to-end or
per-layer, is read by the reader file of its name (``spec.metric_reader``).
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

from benchlib import spec as spec_mod
from benchlib.window import p50, run_window, span

T_START = time.perf_counter()
# A traced run records a window of one request. The device trace holds an
# event for every step of the group solver's nested loops: one request at
# N=12/K=3 wrote 148 MB on a TPU v5e, and a 10-s window of six requests at
# the paper's size took the run to 280 s.
TRACE_WINDOW_S = 1.0


class Log:
    """Every line names the device it ran on."""

    def __init__(self):
        self.tag = "bench"

    def set_device(self, dev: dict) -> None:
        self.tag = f"bench[{dev['platform']} {dev['kind']} x{dev['count']}]"

    def __call__(self, msg: str) -> None:
        print(f"{self.tag} {msg}", file=sys.stderr, flush=True)


def _profile_options():
    """Device ops and the benchmark's own spans; no Python function trace
    and no HLO protos, which the reduction does not read."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def judge(nums: dict, limits: dict) -> dict:
    """Each number compared beside its limit; a number passes when it is
    no greater than its limit."""
    return {k: {"value": nums[k], "limit": limits[k],
                "ok": bool(nums[k] <= limits[k])}
            for k in limits}


def main(argv=None, *, cell=None, device=None) -> int:
    """``cell`` and ``device`` let a test steer a run on the CPU at a small
    size; the benchmark's own runs pass neither."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    log = Log()
    cell = cell or spec_mod.load_cell(args.workload)
    from benchlib import device as dev_mod

    device = device or dev_mod.require_chips(cell.chips)
    log.set_device(device)
    peaks = dev_mod.PEAKS.get(device["kind"])

    from repro.analysis.recompile import CompileLog
    from repro.utils.compile_cache import enable_compile_cache

    import jax

    log(f"compile cache {enable_compile_cache()}")
    # keep every program, the small ones too, so a run's set-up finds all
    # of them in the cache that an earlier run of the checkout filled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    traced = bool(args.trace)
    loop = spec_mod.loop_class(cell.traffic["loop"])(cell, args.seed,
                                                     traced=traced)
    loop.setup()
    setup_s = time.perf_counter() - T_START
    log(f"setup {setup_s:.6f} s")

    trace_dir = None
    with CompileLog() as compiles:
        if traced:
            trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=_profile_options())
        seconds = min(args.seconds, TRACE_WINDOW_S) if traced else args.seconds
        with span("bench.window", traced):
            win = run_window(loop, seconds, traced=traced)
        in_window = list(compiles.events)
        if traced:
            jax.profiler.stop_trace()
    n = len(win["records"])
    gen = win["gen_s"]
    log(f"window {win['window_s']:.6f} s, {n} requests; generator "
        f"{sum(gen):.6f} s in all, median {p50(gen):.6f} s, max "
        f"{max(gen):.6f} s (outside each request's time)")
    log(f"compiles in the window: {len(in_window)} {in_window}")
    mem = (dev_mod.memory_peak_bytes(cell.chips)
           if device["platform"] == "tpu" else 0)
    device = dict(device, memory_peak_bytes=mem)

    # everything a reader may need, for the readers that later cells add
    # as files: the window's records, the set-up, the trace, the config
    # and the chip's published peaks
    ctx = {"records": win["records"], "gen_s": gen,
           "window_s": win["window_s"], "setup_s": setup_s, "peaks": peaks,
           "config": cell.config, "log": log}
    breakdown = None
    if traced:
        from benchlib import trace as trace_mod

        red = trace_mod.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        ctx["trace"] = red
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = spec_mod.metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for name, v in metrics.items():
        log(f"metric {name} {v['value']!r} {v['unit']}")

    loop.release()
    nums, _ = loop.check()
    log(loop.check_info)
    checks = judge(nums, cell.limits["limits"])
    correct = all(c["ok"] for c in checks.values())
    result = {"correct": correct, "attempted": n, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0
