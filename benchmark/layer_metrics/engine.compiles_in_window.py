"""Device ownership: executables the replica's process built or loaded from
the persistent cache between the two ``perf_stats()`` calls around the load.
Every shape is warmed before, so anything but 0 is a recompile in the
measured window."""

UNIT = "count"


def read(ctx, raw):
    if raw.get("kind") != "serve":
        return None
    before = (raw.get("engine_before") or {}).get("compiles")
    after = (raw.get("engine_after") or {}).get("compiles")
    if before is None or after is None:
        return None
    return after["count"] - before["count"]
