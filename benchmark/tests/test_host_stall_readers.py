"""The six readers of the engine thread's time by kind (``perf_stats()``'s
``host_cpu_s``, ``host_switches``, ``host_hist``, ``t``, ``process``), each on
a hand-made ``raw``: the value it computes, and None where the program offers
no such key (the commit before them), where the observability layer is off
(the keys are there and no tick was metered) and in a train cell."""

import copy
import importlib.util
import os

import pytest

FOLDER = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "layer_metrics")
PHASES = ("admit", "dispatch", "drain_book")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), os.path.join(FOLDER, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def stats(t, ticks_live, wall, cpu, involuntary, hist_ticks, hist_s, gc_pause,
          process_cpu, thread_cpu):
    by_phase = lambda total: dict(zip(PHASES, (total / 2, total / 4, total / 4)))  # noqa: E731
    return {
        "t": t, "ticks_live": ticks_live,
        "host_s": by_phase(wall), "host_cpu_s": by_phase(cpu),
        "host_switches": {"voluntary": dict.fromkeys(PHASES, 7),
                          "involuntary": by_phase(involuntary)},
        "host_gc_s": dict.fromkeys(PHASES, 0.0),
        "host_hist": {"ticks": list(hist_ticks), "seconds": list(hist_s)},
        "slow_ticks": [],
        "process": {"cpu_s": process_cpu, "engine_thread_cpu_s": thread_cpu,
                    "gc": {"collections": [9, 1, 0], "pause_s": gc_pause,
                           "recent": []},
                    "threads": {"generation-engine": thread_cpu}},
    }


def raw_with_a_stall():
    """The warm-up left odd values in every counter (a compile's 3 s tick
    among them: it must cancel).  The load: 60 s, 400 ticks of ~5 ms in the
    4-8 bucket, 20 of ~10 ms, 6 of ~45 ms (32-64) and one of 2 s (>= 1,024);
    4.0 host seconds of which the thread was on its core for 1.0, 12
    involuntary switches; 0.3 s of GC pauses; the process burned 9 s of CPU,
    3 of them the engine thread's."""
    before = stats(1000.0, 9, 3.4, 3.1, 4,
                   [2, 1, 0, 5, 0, 0, 0, 0, 0, 0, 0, 1],
                   [0.001, 0.002, 0, 0.03, 0, 0, 0, 0, 0, 0, 0, 3.0],
                   0.11, 40.0, 5.0)
    after = stats(1060.0, 9 + 427, 3.4 + 4.0, 3.1 + 1.0, 4 + 12,
                  [2, 1, 0, 5 + 400, 20, 0, 6, 0, 0, 0, 0, 1 + 1],
                  [0.001, 0.002, 0, 0.03 + 1.6, 0.2, 0, 0.27, 0, 0, 0, 0,
                   3.0 + 2.0],
                  0.11 + 0.3, 40.0 + 9.0, 5.0 + 3.0)
    return {"kind": "serve", "engine_before": before, "engine_after": after}


VALUES = {
    # the one 2 s tick: the last bucket has no upper edge, so its mean
    "engine.tick_host_max_ms": ("ms", 2000.0),
    # the median tick is in 4-8: 16 ms and more are the 6 + 1 slow ones
    "engine.slow_ticks_s": ("s", 0.27 + 2.0),
    "engine.thread_offcore_pct": ("%", 75.0),
    "engine.thread_preempted_per_s": ("1/s", 3.0),
    "replica.gc_pause_pct": ("%", 0.5),
    "replica.other_threads_cpu_pct": ("%", 10.0),
}


@pytest.mark.parametrize("name", sorted(VALUES))
def test_reader_value_and_none_without_the_key(name):
    mod = reader(name)
    unit, value = VALUES[name]
    assert mod.UNIT == unit
    assert mod.read(None, raw_with_a_stall()) == pytest.approx(value)
    # the commit before the keys: the meter's old counters and nothing else
    old = raw_with_a_stall()
    for which in ("engine_before", "engine_after"):
        old[which] = {k: old[which][k] for k in ("ticks_live", "host_s")}
    assert mod.read(None, old) is None
    # RAY_TPU_EVENTS=0: the keys are there, no tick was metered (the clock
    # and the process's CPU still moved)
    off = raw_with_a_stall()
    moved = off["engine_after"]
    off["engine_after"] = copy.deepcopy(off["engine_before"])
    off["engine_after"]["t"] = moved["t"]
    off["engine_after"]["process"]["cpu_s"] = moved["process"]["cpu_s"]
    assert mod.read(None, off) is None
    assert mod.read(None, {"kind": "train"}) is None
    assert mod.read(None, {**raw_with_a_stall(), "kind": "train"}) is None


def test_worst_tick_below_the_last_bucket_reads_its_upper_edge():
    raw = raw_with_a_stall()
    raw["engine_after"]["host_hist"]["ticks"][-1] -= 1  # no 2 s tick
    raw["engine_after"]["host_hist"]["seconds"][-1] -= 2.0
    assert reader("engine.tick_host_max_ms").read(None, raw) == 64.0
    assert reader("engine.slow_ticks_s").read(None, raw) == pytest.approx(0.27)


def test_a_never_started_engine_has_no_thread_to_read():
    raw = raw_with_a_stall()
    for which in ("engine_before", "engine_after"):
        raw[which]["process"]["engine_thread_cpu_s"] = None
    assert reader("replica.other_threads_cpu_pct").read(None, raw) is None
    assert reader("replica.gc_pause_pct").read(None, raw) == pytest.approx(0.5)
