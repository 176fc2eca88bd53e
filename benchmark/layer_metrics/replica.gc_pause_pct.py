"""Serve proxy and router (the replica's process): the share of the load's
wall time in which a garbage collection held every thread of the replica:
``100 x d process.gc.pause_s / d t`` between the driver's two reads (one
``gc.callbacks`` entry a process sums the pauses)."""

UNIT = "%"


def read(ctx, raw):
    from benchmark import host_window

    paused, wall = host_window.process(raw, "gc", "pause_s"), host_window.process(raw)
    if paused is None or not wall:
        return None
    return 100.0 * paused / wall
