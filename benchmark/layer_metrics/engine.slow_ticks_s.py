"""Serve engine: the seconds of the load's ticks whose host time lay two
power-of-two buckets or more above the bucket of the load's median tick (4 x
its lower edge: the rule by which the engine keeps a slow tick's record,
``perf_stats()["slow_ticks"]``), from ``host_hist`` between the driver's two
reads.  0 for a load without such a tick."""

UNIT = "s"


def read(ctx, raw):
    from benchmark import host_window

    found = host_window.hist(raw)
    if found is None:
        return None
    ticks, seconds = found
    return sum(seconds[host_window.median_bucket(ticks) + 2:])
