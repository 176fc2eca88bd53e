"""Device: 1 - (union of device-operation intervals / traced window), train
cells (moves train_tokens_per_s_chip)."""

from benchmark.trace_reduce import idle_pct

UNIT = "%"


def read(ctx, raw):
    return idle_pct(raw.get("trace")) if raw["kind"] == "train" else None
