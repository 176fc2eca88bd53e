"""Serve proxy and router: what lies between the engine and the client.
Client TTFT p50 (from the moment the request was SENT) minus the engine's
own TTFT p50 (``perf_stats()``: submit to first token on the host), so it
holds proxy, router, replica call path, the stream's polling and HTTP."""

UNIT = "ms"


def read(ctx, raw):
    if raw["kind"] != "serve" or raw.get("client_ttft_from_send_p50_s") is None:
        return None
    engine_p50 = (raw["engine_after"].get("ttft") or {}).get("p50_s")
    if engine_p50 is None:
        return None
    return 1e3 * (raw["client_ttft_from_send_p50_s"] - engine_p50)
