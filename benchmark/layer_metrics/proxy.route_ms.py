"""Serve proxy and router: mean of the ``serve.route`` stage over the window,
from the proxy's ``_execute`` opening the request's root span to the router
having submitted the replica call (route match, admission, the wait for a
free replica).  Both stamps are the proxy's; the replica folds the stage
when the call arrives."""

UNIT = "ms"


def read(ctx, raw):
    from benchmark import stages

    return stages.window_mean_ms(raw, "serve.route")
