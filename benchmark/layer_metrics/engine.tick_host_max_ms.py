"""Serve engine: the worst tick of the load on the engine thread's clock: the
upper edge of the highest power-of-two bucket of ``host_hist`` (a tick's
admission, dispatch and drain bookkeeping summed, the drain's blocked reads
left out) that gained a tick between the driver's two reads; for the last
bucket, which has no upper edge (1,024 ms and more), the mean of the ticks in
it.  Beside ``engine.tick_host_ms``, the mean: a single stall of 2 s moves this
and hardly the mean."""

UNIT = "ms"


def read(ctx, raw):
    from benchmark import host_window

    found = host_window.hist(raw)
    if found is None:
        return None
    ticks, seconds = found
    top = max(i for i, n in enumerate(ticks) if n)
    if top == len(ticks) - 1:
        return 1e3 * seconds[top] / ticks[top]
    return float(2 ** top)
