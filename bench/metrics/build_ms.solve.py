"""Median host time of ``FastAssociationEngine(...)`` per solve request, in
ms."""

import statistics


def read(ctx):
    vals = [r["build_s"] for r in ctx["records"] if "build_s" in r]
    return 1e3 * statistics.median(vals) if vals else None
