"""Median time of all association requests completed in the window, each
from hand-over until the answer is a host array, in s."""

from benchlib.window import p50


def read(ctx):
    return p50([r["t"] for r in ctx["records"]])
