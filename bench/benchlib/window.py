"""The measured window: a closed loop of requests for a fixed time, and the
arithmetic of the end-to-end metrics over it."""

from __future__ import annotations

import statistics
import time
from contextlib import nullcontext


def span(name: str, traced: bool):
    """A host span in the profiler's trace (only in a traced run)."""
    if not traced:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_window(loop, seconds: float, *, traced: bool = False) -> dict:
    """Drive ``loop`` for ``seconds``: the benchmark generates each request
    (outside the request's time), then hands it over and times it until
    the answer is a host array. Requests start until the window closes;
    the one in flight at the close finishes and counts.

    Returns ``{"records": [...], "gen_s": [...], "window_s": float}``;
    each record has ``t`` (seconds of the request) and whatever the loop's
    ``serve`` reported."""
    records, gen_s = [], []
    clock = time.perf_counter
    t_open = clock()
    close = t_open + seconds
    i = 0
    while clock() < close:
        g0 = clock()
        with span("bench.generate", traced):
            req = loop.next_request(i)
        t0 = clock()
        with span("bench.request", traced):
            answer, rec = loop.serve(req)
        t1 = clock()
        rec["t"] = t1 - t0
        gen_s.append(t0 - g0)
        loop.keep(i, req, answer, rec)
        records.append(rec)
        i += 1
    return {"records": records, "gen_s": gen_s, "window_s": clock() - t_open}


def p50(values) -> float:
    return float(statistics.median(values))


def p90(values) -> float:
    """90th percentile over all values (``statistics.quantiles``, exclusive
    method); with fewer than two values, the largest."""
    values = list(values)
    if len(values) < 2:
        return float(max(values))
    return float(statistics.quantiles(values, n=10)[8])
