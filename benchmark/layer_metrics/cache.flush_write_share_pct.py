"""Models and kernels: the cache tiles the decode chunks' flushes wrote (a
full layer a tensor: one a dispatched row, two where its columns cross into
the next tile) over the tiles of the padded slab, from the engine's
``perf_stats()["cache_tiles"]`` counter over the load.  What a flush that
rewrote every slot's columns would read is 100 or more."""

UNIT = "%"


def read(ctx, raw):
    from benchmark import engine_window

    share = engine_window.ratio(raw, ("cache_tiles", "flushed"),
                                ("cache_tiles", "padded"))
    return None if share is None else 100.0 * share
