"""Mean moves the device loop applied per warm re-solve
(``engine.last_moves``)."""


def read(ctx):
    vals = [r["moves"] for r in ctx["records"] if r.get("moves") is not None]
    return sum(vals) / len(vals) if vals else None
