"""Median host time of the warm re-solve's reach patch and host repair
(``hfel.rerun.patch`` plus ``hfel.rerun.repair``) per request, in ms;
``None`` where the program keeps no span durations."""

import statistics

SPANS = ("hfel.rerun.patch", "hfel.rerun.repair")


def read(ctx):
    vals = [sum(sum(r["spans"][s]) for s in SPANS) for r in ctx["records"]
            if all(s in r.get("spans", {}) for s in SPANS)]
    return 1e3 * statistics.median(vals) if vals else None
