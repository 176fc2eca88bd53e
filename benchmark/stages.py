"""The served request's stage spans as the per-layer readers see them.

The replica's ``perf_stats()["stages"]`` (``ray_tpu.util.tracing.span_stats``
of the stage phases: cumulative ``count`` and ``sum_s`` per phase, plus
percentiles over the phase's last 4,096 spans) is taken by the serve driver
before the load generator starts and after it has ended, so a difference
covers every request the generator sent, pre-roll and window alike, and
nothing of the warm-up.  A program that has no such key (the commit before
the spans, a train cell) gives every reader None.
"""

from __future__ import annotations


def of(raw: dict, which: str):
    """``raw[which]["stages"]``, or None where the program offers none."""
    if raw.get("kind") != "serve":
        return None
    return (raw.get(which) or {}).get("stages") or None


def window_mean_ms(raw: dict, phase: str):
    """Mean duration, in ms, of the spans of ``phase`` closed between the
    two ``perf_stats()`` calls; None without the key, the phase or a span."""
    before, after = of(raw, "engine_before"), of(raw, "engine_after")
    if after is None or phase not in after:
        return None
    b = (before or {}).get(phase) or {"count": 0, "sum_s": 0.0}
    n = after[phase]["count"] - b["count"]
    if n <= 0:
        return None
    return 1e3 * (after[phase]["sum_s"] - b["sum_s"]) / n
