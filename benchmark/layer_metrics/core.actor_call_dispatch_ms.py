"""Core runtime: mean of the ``task.dispatch`` spans the replica's process
closed over the window: from ``.remote()`` in the caller (the spec context's
stamp) to the worker reaching the call, so serialisation, the head's
dispatch, the socket and the actor's executor.  Only calls that carry a
trace context have one: here the ``handle_request`` calls, one a request."""

UNIT = "ms"


def read(ctx, raw):
    from benchmark import stages

    return stages.window_mean_ms(raw, "task.dispatch")
