"""What the load cost the replica's engine THREAD by kind of time, and what the
rest of its process did meanwhile, for the six readers of the host-stall keys
``perf_stats()`` has since PR 55 (``host_cpu_s``, ``host_switches``,
``host_hist`` beside ``host_s``; ``t``; ``process``): differences between the
serve driver's two reads as ``engine_window`` takes them, pre-roll and window.
A program without the keys, a train cell and a run with the observability
layer off (the keys are there, no tick was metered) give every reader None.
"""

from __future__ import annotations

from benchmark import engine_window

PHASES = ("admit", "dispatch", "drain_book")


def metered(raw: dict) -> bool:
    """The engine's meter counted a tick between the two reads."""
    return bool(engine_window.delta(raw, "ticks_live"))


def summed(raw: dict, *path: str):
    """``engine_window.delta`` of a by-phase counter, the three phases
    summed; None without any of them or without a metered tick."""
    parts = [engine_window.delta(raw, *path, phase) for phase in PHASES]
    return None if None in parts or not metered(raw) else sum(parts)


def process(raw: dict, *path: str):
    """``delta`` of a number under ``perf_stats()["process"]`` (or of ``t``,
    the reads' wall clock, for an empty path); None without a metered tick."""
    got = engine_window.delta(raw, *(("process",) + path if path else ("t",)))
    return got if metered(raw) else None


def hist(raw: dict):
    """``(ticks, seconds)`` each power-of-two bucket of a tick's host
    milliseconds (``<1, 1-2, ... >=1024``) gained between the two reads; None
    without the key or a tick."""
    if raw.get("kind") != "serve":
        return None
    ends = [(raw.get(which) or {}).get("host_hist")
            for which in ("engine_before", "engine_after")]
    if not all(isinstance(h, dict) and "ticks" in h and "seconds" in h
               for h in ends):
        return None
    ticks = [a - b for a, b in zip(ends[1]["ticks"], ends[0]["ticks"])]
    seconds = [a - b for a, b in zip(ends[1]["seconds"], ends[0]["seconds"])]
    return (ticks, seconds) if sum(ticks) else None


def median_bucket(ticks: list) -> int:
    seen = 0
    for bucket, n in enumerate(ticks):
        seen += n
        if 2 * seen >= sum(ticks):
            return bucket
    return len(ticks) - 1
