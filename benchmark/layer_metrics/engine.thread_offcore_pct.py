"""Serve engine: of the engine thread's host seconds over the load (``host_s``:
admission, dispatch, drain bookkeeping; not the drain's blocked reads), the
share it was NOT on a core: ``100 x (d host_s - d host_cpu_s) / d host_s``, the
thread's own CPU clock beside the wall clock at the same instants.  Off the
core with involuntary switches is the scheduler
(``engine.thread_preempted_per_s``), with voluntary ones a wait: the GIL under
another thread of the replica, or a lock."""

UNIT = "%"


def read(ctx, raw):
    from benchmark import host_window

    wall = host_window.summed(raw, "host_s")
    cpu = host_window.summed(raw, "host_cpu_s")
    if not wall or cpu is None:
        return None
    return 100.0 * (wall - cpu) / wall
