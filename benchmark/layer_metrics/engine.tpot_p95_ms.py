"""Serve engine: the judged statistic taken inside the engine.  Per request
(last token on the host - first token on the host) / (tokens - 1), the
``engine.decode_per_token`` fold; 95th percentile over the requests that
ended between the driver's two reads (pre-roll and window, where the
client's ``tpot_p95_ms`` is over the window alone).  It holds the decode
step, the chunk steps a request pays for its gaps, and the prefill calls in
between; what the client's ``tpot_p95_ms`` has over it is delivery."""

UNIT = "ms"


def read(ctx, raw):
    from benchmark import engine_window

    return engine_window.p95_ms(raw, "engine.decode_per_token")
