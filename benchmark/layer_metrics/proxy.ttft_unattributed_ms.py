"""Serve proxy and router: the part of the client's time to first token that
no span covers yet.  Mean, over the generator's completed requests, of first
token minus SENT (so the generator's lateness is not in it), less the mean
of ``serve.first_reply`` (root span opened to the first ``next_chunks`` reply
that carries data, over the same requests): accept and parse before
``_execute``, the reply's way back to the proxy, its write, the client's
read."""

UNIT = "ms"


def read(ctx, raw):
    from benchmark import stages

    covered = stages.window_mean_ms(raw, "serve.first_reply")
    if covered is None:
        return None
    seen = [r["times"][0] - r["sent"] for r, _ in raw.get("records", [])
            if r.get("done") and r["times"]]
    if not seen:
        return None
    return 1e3 * sum(seen) / len(seen) - covered
