"""Serve engine: decode steps the finished requests' chunks ran (chunks that
gave a request a token x the chunk's length) over the gaps between their
tokens (tokens - 1), from the tick meter's ``decode`` counters over the load.
1.0 is no granularity loss; a request of 18 tokens in chunks of 16 pays 32
steps for 17 gaps."""

UNIT = "ratio"


def read(ctx, raw):
    from benchmark import engine_window

    return engine_window.ratio(raw, ("decode", "chunk_steps_paid"),
                               ("decode", "gaps"))
