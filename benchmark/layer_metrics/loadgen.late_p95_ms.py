"""Load generator (the benchmark's own): how late requests left, actual
send minus due time, 95th percentile.  A starved generator must not be read
as a fast server."""

UNIT = "ms"


def read(ctx, raw):
    return raw.get("late_p95_ms") if raw["kind"] == "serve" else None
