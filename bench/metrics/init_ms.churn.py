"""Median host time of the stale rows' toggle-cache init
(``hfel.sweep.init``) per warm re-solve, in ms; ``None`` where the program
keeps no span durations."""

import statistics


def read(ctx):
    vals = [sum(r["spans"]["hfel.sweep.init"]) for r in ctx["records"]
            if "hfel.sweep.init" in r.get("spans", {})]
    return 1e3 * statistics.median(vals) if vals else None
