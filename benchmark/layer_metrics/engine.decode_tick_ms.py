"""Serve engine: the mean period between two chunk landings of the ticks that
held no prefill call (``tick_s`` over ``ticks`` of the tick meter's
``decode_only`` class), over the load: pre-roll and window.  While the
look-ahead keeps the device's queue non-empty it is one decode chunk's device
time on the host's clock, in every run and over the whole load; over the
chunk's steps it stands beside ``model.decode_step_ms`` (a traced few
seconds).  None from a program whose meter is fed at the dispatch (it has no
``ticks_live``): its ``decode_only`` wall is another tick's."""

UNIT = "ms"


def read(ctx, raw):
    from benchmark import engine_window

    if engine_window.delta(raw, "ticks_live") is None:
        return None
    mean_s = engine_window.ratio(raw, ("tick_s", "decode_only"),
                                 ("ticks", "decode_only"))
    return None if mean_s is None else 1e3 * mean_s
