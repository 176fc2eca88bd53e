"""Serve engine: what a tick costs the engine thread apart from waiting for
the device: admission (the prefill calls' dispatch), the chunk's dispatch and
the drain's bookkeeping, a tick that dispatched or drained anything
(``host_s`` and ``ticks_live`` of the tick meter over the load).  The floor
under any shorter chunk."""

UNIT = "ms"


def read(ctx, raw):
    from benchmark import engine_window

    ticks = engine_window.delta(raw, "ticks_live")
    parts = [engine_window.delta(raw, "host_s", k)
             for k in ("admit", "dispatch", "drain_book")]
    if not ticks or None in parts:
        return None
    return 1e3 * sum(parts) / ticks
