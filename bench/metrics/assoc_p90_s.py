"""90th percentile of the same requests as ``assoc_p50_s``, in s."""

from benchlib.window import p90


def read(ctx):
    return p90([r["t"] for r in ctx["records"]])
