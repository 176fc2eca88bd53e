"""Serve proxy and router: the judged statistic taken where the replica hands
a stream's chunks to the proxy.  Per request (last ``next_chunks`` reply that
carried data - first) / (chunks - 1), the ``serve.stream_per_chunk`` fold;
95th percentile over the streams that ended between the driver's two reads
(pre-roll and window, where the client's ``tpot_p95_ms`` is over the window
alone).  Against the client's ``tpot_p95_ms``: what the proxy and HTTP add;
against ``engine.tpot_p95_ms``: what the two 20 ms polls add."""

UNIT = "ms"


def read(ctx, raw):
    from benchmark import engine_window

    return engine_window.p95_ms(raw, "serve.stream_per_chunk")
