"""Profiling helpers: XLA device traces.

The reference's on-demand profiling endpoints
(``dashboard/modules/reporter/profile_manager.py``), with the device-side
half it never had: ``profile_trace`` captures an XLA/jax device trace
viewable in TensorBoard or Perfetto.  Spans of a request are
``ray_tpu.util.tracing``'s.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Iterator

# one device trace at a time per process: jax.profiler.start_trace raises
# out of XLA on a second concurrent start, and a nested profile scope
# (e.g. profile_step firing inside a user's own profile_trace block)
# must degrade to a no-op instead of killing the train loop
_trace_lock = threading.Lock()
_trace_active = False


@contextlib.contextmanager
def profile_trace(logdir: str, *, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a jax/XLA profiler trace for the enclosed block.

    Run inside a Train worker loop (or any TPU-holding task)::

        with profiling.profile_trace("/tmp/trace"):
            train_step(...)

    Open with TensorBoard's profile plugin or ui.perfetto.dev.
    Re-entrant by degrading: when a trace is already running in this
    process the inner scope is a no-op (the outer trace still covers it)
    rather than an XLA "profiler already started" crash.

    The profiler's python tracer is OFF: it hooks every Python call of the
    process and slows a host-bound thread severalfold (a serve engine read
    48-52 % device idle under it and 0 % without).  ``host_tracer_level``
    is the profiler's own (2 keeps ``TraceAnnotation`` spans and JAX's
    launches, 0 leaves the device planes alone).
    """
    global _trace_active
    import jax

    with _trace_lock:
        if _trace_active:
            started = False
        else:
            _trace_active = started = True
    if not started:
        yield
        return
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = host_tracer_level
        jax.profiler.start_trace(logdir, profiler_options=options)
    except Exception:
        # a start failure (e.g. a foreign profiler session already owns
        # the backend) must not take the step down with it
        with _trace_lock:
            _trace_active = False
        yield
        return
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
        finally:
            with _trace_lock:
                _trace_active = False


def profile_step(logdir: str) -> bool:
    """Arm a ONE-STEP device trace on the process's active
    :class:`~ray_tpu.util.perf.StepProfiler`: the next ``prof.step()``
    scope runs inside :func:`profile_trace` and the trace lands under
    ``logdir``.  This is the on-demand hook a doctor perf rule (or an
    operator staring at ``ray_tpu perf``) triggers to capture device
    detail for exactly one step without paying trace overhead steadily.
    Returns whether a profiler was armed (False: no active profiler in
    this process)."""
    from ray_tpu.util import perf as _perf

    prof = _perf.active_profiler()
    if prof is None:
        return False
    prof.arm_trace(logdir)
    return True
