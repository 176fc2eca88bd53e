"""Device: 1 - (union of device-operation intervals / traced window), serve
cells (moves serve_tokens_per_s)."""

from benchmark.trace_reduce import idle_pct

UNIT = "%"


def read(ctx, raw):
    return idle_pct(raw.get("trace")) if raw["kind"] == "serve" else None
