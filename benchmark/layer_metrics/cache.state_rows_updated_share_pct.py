"""Models and kernels, a family whose layers carry a per-request state: the
rows of state the decode steps' update TOUCHED over the rows that were LIVE
(had to take the step), from the engine's ``perf_stats()["state"]`` counter
over the load (``rows_updated`` over ``rows_live``, both summed over the steps
the drained chunks really ran).  100: only the live rows' state moves (the
kernel that walks the chunk's active slots); an update over every row of the
cache reads ``rows of the cache / live rows`` x 100 (400 with 49 rows for 12
live).  None where the program has no such counter."""

UNIT = "%"


def read(ctx, raw):
    from benchmark import engine_window

    share = engine_window.ratio(raw, ("state", "rows_updated"),
                                ("state", "rows_live"))
    return None if share is None else 100.0 * share
