"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The trace (``*.xplane.pb``) holds a ``/host:CPU`` plane, whose threads carry
the benchmark's own ``bench.*`` spans, and one ``/device:TPU:<i>`` plane per
chip, whose ``XLA Ops`` line holds one event per device operation. Both are
on one clock. Over the window span ``bench.window``:

* busy: the union of the device op intervals, averaged over the chips;
* device ops: total time per operation name (the HLO instruction name);
* idle gaps: the stretches between busy intervals, each put down to the
  innermost ``bench.*`` span that covers its middle (what the host was
  doing), totalled per span name.
"""

from __future__ import annotations

import glob
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10


def _events(trace_path: str):
    """(host spans, {device plane: [(start, end, name)]})."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace_path)
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name))
        elif DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                op_name(ev.name)))
            devices[plane.name] = ops
    return spans, devices


_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12 fusion``."""
    head, _, rest = hlo.partition(" = ")
    head = head.lstrip("%").strip()
    m = _OPCODE.search(rest)
    return f"{head} {m.group(1)}" if m else head


def union_length(intervals) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def host_activity(spans, t: float) -> str:
    """Innermost bench span covering time ``t`` (the window itself last)."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "outside"


def reduce_events(spans, devices) -> dict:
    win = [(s, e) for s, e, n in spans if n == "bench.window"]
    if not win or not devices:
        raise ValueError("trace holds no window span or no device plane")
    w0, w1 = win[0]
    busy, op_time, gaps = [], {}, {}
    for ops in devices.values():
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in ops
                  if e > w0 and s < w1]
        busy.append(union_length((s, e) for s, e, _ in inside))
        for s, e, n in inside:
            op_time[n] = op_time.get(n, 0.0) + (e - s)
        edges = [w0] + [x for iv in merged((s, e) for s, e, _ in inside)
                        for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                what = host_activity(spans, 0.5 * (a + b))
                gaps[what] = gaps.get(what, 0.0) + (b - a)
    n_dev = len(devices)

    def top(d):
        return [[k, v / n_dev * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": sum(busy) / n_dev * 1e-9,
            "window_s": (w1 - w0) * 1e-9,
            "device_ops": top(op_time), "idle_gaps": top(gaps)}


def find_trace(trace_dir) -> str:
    found = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[0]


def reduce_dir(trace_dir) -> dict:
    spans, devices = _events(find_trace(trace_dir))
    return reduce_events(spans, devices)
