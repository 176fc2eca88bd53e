"""The readers of the decode stages and of the tick meter's counters, each on
a hand-made ``raw``: the value it computes, and None where the program offers
no such key (the commit before them), where the observability layer is off
(the keys are there and nothing was added) and in a train cell."""

import copy
import importlib.util
import os

import pytest

FOLDER = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "layer_metrics")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), os.path.join(FOLDER, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def stage(durations):
    return {"count": len(durations), "sum_s": sum(durations), "p50_s": 0.0,
            "p95_s": 0.0, "recent": list(durations)}


WARM = [0.050, 0.051, 0.052, 0.053]  # four warm-up posts: slow, alone
# twenty requests of the load: 5.0 .. 24.0 ms a gap, so p95 = 23.05
LOAD = [0.005 + 0.001 * i for i in range(20)]


def meter(ticks_live, n_decode, decode_s, gaps, paid, span_s, prefill_s,
          host, flushed, padded):
    return {
        "ticks_live": ticks_live,
        "ticks": {"decode_only": n_decode, "interleaved": 0,
                  "prefill_only": 0},
        "tick_s": {"decode_only": decode_s, "interleaved": 0.0,
                   "prefill_only": 0.0},
        "decode": {"requests": 0, "gaps": gaps, "chunk_steps_paid": paid,
                   "span_s": span_s, "prefill_s": prefill_s},
        "host_s": dict(zip(("admit", "dispatch", "drain_book"), host)),
        "cache_tiles": {"flushed": flushed, "padded": padded},
    }


def raw_with_decode():
    """Four warm-up requests before the driver's first read, twenty between
    its two; the counters' warm-up values are odd so that they must cancel."""
    before = meter(7, 3, 0.9, 68, 128, 9.0, 0.0, (0.1, 0.1, 0.1), 13, 400)
    after = meter(7 + 100, 3 + 80, 0.9 + 80 * 0.0975, 68 + 1000, 128 + 1600,
                  9.0 + 10.0, 1.5, (0.1 + 0.02, 0.1 + 0.05, 0.1 + 0.03),
                  13 + 300, 400 + 11900)
    before["stages"] = {"engine.decode_per_token": stage(WARM),
                        "serve.stream_per_chunk": stage(WARM),
                        "clock_skew": 0}
    after["stages"] = {
        "engine.decode_per_token": stage(WARM + LOAD),
        "serve.stream_per_chunk": stage(WARM + [d + 0.001 for d in LOAD]),
        "clock_skew": 0}
    return {"kind": "serve", "engine_before": before, "engine_after": after}


VALUES = {
    "engine.tpot_p95_ms": ("ms", 23.05),
    "replica.tpot_p95_ms": ("ms", 24.05),
    "engine.chunk_steps_per_gap": ("ratio", 1.6),
    "engine.decode_prefill_wait_pct": ("%", 15.0),
    "engine.decode_tick_ms": ("ms", 97.5),
    "engine.tick_host_ms": ("ms", 1.0),
    "cache.flush_write_share_pct": ("%", 100.0 * 300 / 11900),
}
# the counter a parent of this PR already keeps, events on or off
KEPT_WITHOUT_EVENTS = {"cache.flush_write_share_pct"}


@pytest.mark.parametrize("name", sorted(VALUES))
def test_reader_value_and_none_without_the_key(name):
    mod = reader(name)
    unit, value = VALUES[name]
    assert mod.UNIT == unit
    assert mod.read(None, raw_with_decode()) == pytest.approx(value)
    # a commit before the keys: perf_stats() has neither stages' ``recent``
    # nor the meter's new counters; its ``ticks`` and ``tick_s`` are there,
    # fed at the dispatch (another tick's wall), and are not to be read
    old = raw_with_decode()
    for n, which in enumerate(("engine_before", "engine_after")):
        old[which] = {"ttft": {}, "ticks": {"decode_only": 3 + 80 * n},
                      "tick_s": {"decode_only": 0.9 + 7.8 * n},
                      "stages": {"engine.queue": {"count": 9, "sum_s": 1.0,
                                                  "p50_s": 0.1, "p95_s": 0.2}}}
    assert mod.read(None, old) is None
    # RAY_TPU_EVENTS=0: the keys are there, nothing was added to them
    off = raw_with_decode()
    off["engine_after"] = copy.deepcopy(off["engine_before"])
    off["engine_before"]["stages"] = off["engine_after"]["stages"] = {}
    assert mod.read(None, off) is None
    if name in KEPT_WITHOUT_EVENTS:
        off["engine_after"]["cache_tiles"] = {"flushed": 313, "padded": 12300}
        assert mod.read(None, off) == pytest.approx(value)
    assert mod.read(None, {"kind": "train"}) is None
    assert mod.read(None, {"kind": "train", **raw_with_decode(),
                           "kind": "train"}) is None


def test_a_windows_p95_ignores_what_closed_before_it():
    """Over the reservoir the four warm-up entries ARE the tail (p95 of the
    24 is 50.85 ms); over the window they are not read."""
    from benchmark import engine_window
    from benchmark.traffic_gen import percentile

    raw = raw_with_decode()
    assert 1e3 * percentile(WARM + LOAD, 95) == pytest.approx(51.85)
    assert engine_window.durations(raw, "engine.decode_per_token") == LOAD
    assert engine_window.p95_ms(raw, "engine.decode_per_token") == (
        pytest.approx(23.05))
    # exactly what the row hands out closed in the window: all of it ...
    row = raw["engine_after"]["stages"]["engine.decode_per_token"]
    row["count"] += len(WARM)
    assert engine_window.durations(raw, "engine.decode_per_token") == (
        WARM + LOAD)
    # ... one more: a percentile over part of the load is not given
    row["count"] += 1
    assert engine_window.p95_ms(raw, "engine.decode_per_token") is None
    # no span closed: nothing to read
    raw["engine_before"]["stages"]["engine.decode_per_token"]["count"] = (
        row["count"])
    assert engine_window.p95_ms(raw, "engine.decode_per_token") is None
