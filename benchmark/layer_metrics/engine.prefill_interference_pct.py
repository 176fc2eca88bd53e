"""Serve engine: share of decode-tick wall time the engine's own tick meter
bills to co-scheduled prefills, as the difference of its counters over the
window (``perf_stats()`` before and after)."""

UNIT = "%"


def read(ctx, raw):
    if raw["kind"] != "serve":
        return None
    a, b = raw["engine_before"], raw["engine_after"]
    if "interference_s" not in b:
        return None
    wall = lambda s: s["tick_s"]["decode_only"] + s["tick_s"]["interleaved"]
    decode_wall = wall(b) - wall(a)
    if decode_wall <= 0:
        return None
    return 100.0 * (b["interference_s"] - a["interference_s"]) / decode_wall
