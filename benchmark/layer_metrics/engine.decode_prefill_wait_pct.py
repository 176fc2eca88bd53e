"""Serve engine: of the time the finished requests spent between their first
and their last token on the host (``engine.decode``), the part the device
spent on OTHER requests' prefill calls (the prefill parts of the periods
between landings that fell inside each span), from the tick meter's
``decode`` counters over the load."""

UNIT = "%"


def read(ctx, raw):
    from benchmark import engine_window

    share = engine_window.ratio(raw, ("decode", "prefill_s"),
                                ("decode", "span_s"))
    return None if share is None else 100.0 * share
