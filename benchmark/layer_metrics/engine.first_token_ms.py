"""Serve engine: mean of the ``engine.first_token`` stage over the window,
from the prefill's dispatch to its sampled token being on the host: the rest
of the chunk the device is running, then the padded prefill itself."""

UNIT = "ms"


def read(ctx, raw):
    from benchmark import stages

    return stages.window_mean_ms(raw, "engine.first_token")
