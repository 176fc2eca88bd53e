"""Serve proxy and router: what polling adds between the first token being on
the host and the proxy holding it: mean ``engine.stream_yield`` (the engine's
``stream`` loop noticing it) plus mean ``serve.pickup`` (the chunk lying in
the replica's stream queue until the proxy's next ``next_chunks`` call
returns it)."""

UNIT = "ms"


def read(ctx, raw):
    from benchmark import stages

    parts = [stages.window_mean_ms(raw, p)
             for p in ("engine.stream_yield", "serve.pickup")]
    return None if None in parts else sum(parts)
