"""Serve engine: how often the scheduler took the engine thread's core inside
its host phases: involuntary context switches (``host_switches.involuntary``,
``getrusage(RUSAGE_THREAD)`` at the instants ``host_s`` is read) a host second,
over the load."""

UNIT = "1/s"


def read(ctx, raw):
    from benchmark import host_window

    wall = host_window.summed(raw, "host_s")
    taken = host_window.summed(raw, "host_switches", "involuntary")
    if not wall or taken is None:
        return None
    return taken / wall
