"""What the replica's engine says of the load between the serve driver's two
``perf_stats()`` reads (``raw["engine_before"]``, ``raw["engine_after"]``:
before the load generator starts and after it has ended, so pre-roll and
window, and nothing of the warm-up), for the readers of the tick meter's
counters and of the decode stages.

A cumulative counter is differenced.  A percentile cannot be: a stage's row
(``ray_tpu.util.tracing.span_stats``) therefore carries ``recent``, its newest
durations in the order they closed, and the window's own are the last
``after.count - before.count`` of them (the warm-up posts, alone on the chip
and two chunks for 17 gaps each, would else sit in the tail of a p95).  A
program without the key (the commit before it), a train cell and a run with
the observability layer off give every reader None.
"""

from __future__ import annotations

from benchmark import stages
from benchmark.traffic_gen import percentile


def delta(raw: dict, *path: str):
    """``after - before`` of the cumulative number at ``path`` in
    ``perf_stats()``; None where the cell is no serve cell or either read
    lacks the path."""
    if raw.get("kind") != "serve":
        return None
    ends = []
    for which in ("engine_before", "engine_after"):
        node = raw.get(which)
        for key in path:
            node = node.get(key) if isinstance(node, dict) else None
        if not isinstance(node, (int, float)):
            return None
        ends.append(node)
    return ends[1] - ends[0]


def ratio(raw: dict, over: tuple, under: tuple):
    """``delta(over) / delta(under)``; None without either or over nothing."""
    a, b = delta(raw, *over), delta(raw, *under)
    return None if a is None or not b else a / b


def durations(raw: dict, phase: str):
    """The durations, in seconds and in closing order, of the spans of
    ``phase`` closed between the two reads; None without the row, its
    ``recent`` or a span, and where the load closed more than a row hands
    out (a percentile over part of the load would carry no sign of it)."""
    before, after = stages.of(raw, "engine_before"), stages.of(raw, "engine_after")
    row = (after or {}).get(phase) or {}
    n = row.get("count", 0) - ((before or {}).get(phase) or {}).get("count", 0)
    recent = row.get("recent") or ()
    if not 0 < n <= len(recent):
        return None
    return recent[-n:]


def p95_ms(raw: dict, phase: str):
    found = durations(raw, phase)
    return None if found is None else 1e3 * percentile(found, 95)
