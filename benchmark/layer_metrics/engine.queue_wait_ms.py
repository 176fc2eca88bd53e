"""Serve engine: mean of the ``engine.queue`` stage over the window, from the
engine's request object existing to its prefill being dispatched: the wait
for a free slot and for the engine thread to come round (it sits in the
drain of the running chunk)."""

UNIT = "ms"


def read(ctx, raw):
    from benchmark import stages

    return stages.window_mean_ms(raw, "engine.queue")
