"""Serve engine: 95th percentile of the ``engine.queue`` stage as the replica
reports it after the window.  A percentile cannot be differenced: it is over
the phase's reservoir of the last 4,096 spans, which then holds the warm-up
posts (one a prefill bucket), the pre-roll and the window."""

UNIT = "ms"


def read(ctx, raw):
    from benchmark import stages

    after = stages.of(raw, "engine_after")
    if after is None or "engine.queue" not in after:
        return None
    return 1e3 * after["engine.queue"]["p95_s"]
