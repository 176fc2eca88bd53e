"""Serve engine, a family with a vision tower in front of its text path: of
the periods in which a request was decoding (the tick meter's decode-only and
interleaved periods: ``engine.prefill_interference_pct``'s denominator), the
part that went to TOWER calls of other requests' videos (the meter's
``vision_ticks.tower_interference_s``: a tower call's landing-to-landing
seconds in an interleaved period; the tower's time is booked as prefill, so
this is a part of the interference, not beside it).  The difference of the
driver's two ``perf_stats()`` reads.  None where the program keeps no such
count."""

UNIT = "%"


def read(ctx, raw):
    before, after = raw.get("engine_before"), raw.get("engine_after")
    if raw.get("kind") != "serve" or not after or "vision_ticks" not in after:
        return None
    was = (before or {}).get("vision_ticks") or {}
    tower = after["vision_ticks"]["tower_interference_s"] - was.get(
        "tower_interference_s", 0.0)
    wall = sum(after["tick_s"][k] - ((before or {}).get("tick_s") or {}).get(k, 0.0)
               for k in ("decode_only", "interleaved"))
    return 100.0 * tower / wall if wall > 0 else None
