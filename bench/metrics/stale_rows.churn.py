"""Mean toggle-cache rows re-priced at the warm init per request (the
engine's ``stale_rows`` counter)."""


def read(ctx):
    vals = [r["counts"]["stale_rows"] for r in ctx["records"]
            if "stale_rows" in (r.get("counts") or {})]
    return sum(vals) / len(vals) if vals else None
