"""Serve proxy and router (the replica's process): the CPU the replica's
OTHER threads burned over the load, in percent of one core: ``100 x (d
process.cpu_s - d process.engine_thread_cpu_s) / d t`` between the driver's
two reads.  Python among them holds the GIL against the engine thread; all of
them share the host's cores with it."""

UNIT = "%"


def read(ctx, raw):
    from benchmark import host_window

    every = host_window.process(raw, "cpu_s")
    own = host_window.process(raw, "engine_thread_cpu_s")
    wall = host_window.process(raw)
    if every is None or own is None or not wall:
        return None
    return 100.0 * (every - own) / wall
