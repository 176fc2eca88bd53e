"""A stalled tick names its cause: what the tick meter keeps of the engine
thread's time by KIND (wall, CPU, context switches, GC pauses), the record a
slow tick leaves behind, and four forced stalls, each classified from its
record alone.  Every wait in this file carries its own time limit."""

import gc
import sys
import threading
import time

import jax.numpy as jnp
import pytest

from ray_tpu._private import events as events_mod
from ray_tpu._private import sampling_profiler
from ray_tpu.serve.llm import GenerationEngine, _TickMeter, make_config
from ray_tpu.util import doctor, tracing

LIMIT_S = 60.0  # no single wait of a case is longer


def tiny_engine(**kw):
    cfg = make_config("gpt2", "tiny", dtype=jnp.float32)
    kw = {"n_slots": 2, "max_new_tokens": 6, "decode_chunk_steps": 3,
          "prefill_buckets": (8,), **kw}
    return GenerationEngine(cfg, **kw)


def slow_events(seq):
    return [r for r in events_mod.buffer().since(seq)
            if r["source"] == "perf" and r["message"] == "slow tick"]


# -- the meter on a synthetic clock -------------------------------------------

def phase(wall, cpu=None, vol=0, invol=0, gc_s=0.0):
    return (wall, wall if cpu is None else cpu, vol, invol, gc_s)


def test_meter_sums_by_kind_and_keeps_a_slow_ticks_record_and_no_other():
    m = _TickMeter("synthetic")
    seq = events_mod.buffer().last_seq()
    # ten healthy ticks of 5 ms (the 4-8 ms bucket), one of 10 ms (8-16: not
    # slow, three buckets above the median's begin at 32 ms)
    for _ in range(10):
        assert m.tick_host(phase(0.002, 0.0015, vol=1), phase(0.002),
                           phase(0.001, gc_s=0.0002)) is None
    assert m.tick_host(phase(0.006), phase(0.003), phase(0.001)) is None
    assert m.host.threshold_s == pytest.approx(0.032)
    # 45 ms, most of it off the core in admission, the core taken six times
    t0 = time.time()
    record = m.tick_host(phase(0.040, 0.002, vol=1, invol=6), phase(0.003),
                         phase(0.002, gc_s=0.001), began=123.0,
                         rows=3, steps=16, prefill_calls=1)
    snap = m.snapshot()
    assert snap["ticks_live"] == 12
    assert snap["host_s"] == pytest.approx(
        {"admit": 0.066, "dispatch": 0.026, "drain_book": 0.013})
    assert snap["host_cpu_s"] == pytest.approx(
        {"admit": 0.023, "dispatch": 0.026, "drain_book": 0.013})
    assert snap["host_switches"] == {
        "voluntary": {"admit": 11, "dispatch": 0, "drain_book": 0},
        "involuntary": {"admit": 6, "dispatch": 0, "drain_book": 0}}
    assert snap["host_gc_s"] == pytest.approx(
        {"admit": 0.0, "dispatch": 0.0, "drain_book": 0.003})
    assert snap["host_hist"]["ticks"] == [0, 0, 0, 10, 1, 0, 1, 0, 0, 0, 0, 0]
    assert snap["host_hist"]["seconds"] == pytest.approx(
        [0, 0, 0, 0.05, 0.010, 0, 0.045, 0, 0, 0, 0, 0])
    # the one record, in the ring and as ONE event
    assert snap["slow_ticks"] == [record]
    assert t0 <= record["t"] <= time.time()
    assert record["wall_s"] == {"admit": 0.040, "dispatch": 0.003,
                                "drain_book": 0.002}
    assert record["cpu_s"]["admit"] == 0.002
    assert (record["voluntary"], record["involuntary"]) == (1, 6)
    assert record["gc_s"] == 0.001
    assert (record["rows"], record["steps"], record["prefill_calls"]) == (
        3, 16, 1)
    assert record["drained_class"] is None and record["stacks"] == []
    assert record["cause"] == "preempted"
    events = slow_events(seq)
    assert len(events) == 1 and events[0]["entity_id"] == "synthetic"
    assert events[0]["span_dur"] == pytest.approx(0.045)
    assert events[0]["data"]["cause"] == "preempted"
    # a second one right behind it is kept and not emitted (rate limit)
    assert m.tick_host(phase(0.02), phase(0.02), phase(0.02)) is not None
    assert len(m.snapshot()["slow_ticks"]) == 2 and len(slow_events(seq)) == 1
    # a SUSTAINED slowdown raises the median, and from then on fills no ring
    for _ in range(40):
        m.tick_host(phase(0.02), phase(0.02), phase(0.005))
    assert m.host.threshold_s == pytest.approx(0.256)  # 8 x the 32-64 bucket
    kept = len(m.snapshot()["slow_ticks"])
    for _ in range(40):
        assert m.tick_host(phase(0.02), phase(0.02), phase(0.005)) is None
    assert len(m.snapshot()["slow_ticks"]) == kept < 32


@pytest.mark.parametrize("record, cause", [
    (dict(wall=0.2, cpu=0.19, vol=0, invol=1, gc_s=0.0), "cpu"),
    (dict(wall=0.2, cpu=0.01, vol=1, invol=30, gc_s=0.0), "preempted"),
    (dict(wall=0.2, cpu=0.01, vol=40, invol=1, gc_s=0.0), "waiting"),
    # one switch into a long wait for a lock, two preemptions around it
    (dict(wall=0.3, cpu=0.01, vol=1, invol=2, gc_s=0.0), "waiting"),
    (dict(wall=0.2, cpu=0.15, vol=0, invol=0, gc_s=0.15), "gc"),
])
def test_a_record_names_one_of_four_causes(record, cause):
    assert tracing.stall_cause({
        "wall_s": {"admit": record["wall"], "dispatch": 0.0},
        "cpu_s": {"admit": record["cpu"], "dispatch": 0.0},
        "voluntary": record["vol"], "involuntary": record["invol"],
        "gc_s": record["gc_s"]}) == cause


def test_host_readings_between_two_summaries():
    m = _TickMeter("readings")
    before = m.host.summary()
    for _ in range(20):
        m.tick_host(phase(0.002, 0.001), phase(0.002, 0.001, invol=1),
                    phase(0.001, 0.0005))
    m.tick_host(phase(0.03, 0.001), phase(0.003), phase(0.002))
    after = m.host.summary()
    got = tracing.host_readings(before, after)
    assert got["tick_host_max_ms"] == 64.0
    assert got["slow_ticks_s"] == pytest.approx(0.035)
    assert got["thread_offcore_pct"] == pytest.approx(
        100 * (0.135 - 0.056) / 0.135)
    assert got["thread_preempted_per_s"] == pytest.approx(20 / 0.135)
    assert got["gc_pause_pct"] == 0.0


# -- forced stalls --------------------------------------------------------------

@pytest.fixture(scope="module")
def stalled():
    """A warm tiny engine under the process's sampler, and ``stall(fn)``:
    the NEXT admission runs ``fn`` inside its phase (``after=True``: once the
    admission has returned, still before the tick's next phase)."""
    prof = sampling_profiler.ContinuousProfiler(
        "test-host-stalls", ingest_fn=lambda *a: None).start()
    eng = tiny_engine().start()
    for _ in range(16):  # programs built, a median to compare with
        eng.generate([3, 5, 7], 6, timeout=LIMIT_S)
    admit, armed = eng._admit, []

    def stalling_admit():
        fn, after = armed.pop() if armed else (None, False)
        if fn and not after:
            fn()
        out = admit()
        if fn and after:
            fn()
        return out

    eng._admit = stalling_admit

    def stall(fn, after=False, need=True):
        since = time.time()
        armed.append((fn, after))
        eng.generate([3, 5, 7], 6, timeout=LIMIT_S)
        assert not armed
        records = [r for r in eng.perf_stats()["slow_ticks"]
                   if r["t"] >= since]
        assert records or not need, "the stalled tick left no record"
        return max(records, key=lambda r: sum(r["wall_s"].values()),
                   default=None)

    yield eng, stall
    eng.stop()
    prof.stop()


def test_a_busy_loop_on_the_engine_thread_is_cpu(stalled):
    _, stall = stalled

    def spin():  # a quarter of a second of the engine thread's OWN CPU
        end = time.thread_time() + 0.25
        while time.thread_time() < end:
            pass

    record = stall(spin)
    wall, cpu = record["wall_s"]["admit"], record["cpu_s"]["admit"]
    assert wall >= cpu >= 0.25
    if cpu >= 0.5 * wall:
        assert record["cause"] == "cpu", record
    else:  # a loaded machine took the core for longer than the loop ran
        assert record["cause"] == "preempted", record
        assert record["involuntary"] * tracing.PREEMPTED_SLICE_S >= (
            0.5 * (wall - cpu))
    # the sampler caught the engine thread at it
    assert any("spin" in stack for caught in record["stacks"]
               for stack, _ in caught["top"]), record["stacks"]
    assert {c["phase"] for c in record["stacks"]} == {"admit"}


def test_a_collection_inside_a_phase_is_gc(stalled):
    _, stall = stalled
    graph = [[] for _ in range(400_000)]  # a large cyclic graph, kept alive
    for a, b in zip(graph, graph[1:] + graph[:1]):
        a.append(b)
    before = tracing.gc_stats()
    record = stall(lambda: gc.collect())
    del graph
    assert record["cause"] == "gc", record
    assert record["gc_s"] >= 0.5 * record["wall_s"]["admit"] > 0.01
    after = tracing.gc_stats()
    assert after["collections"][2] > before["collections"][2]
    assert after["pause_s"] - before["pause_s"] >= record["gc_s"] * 0.99
    t, generation, seconds = max(after["recent"], key=lambda p: p[2])
    assert generation == 2 and seconds == pytest.approx(record["gc_s"], rel=0.5)


def test_another_thread_holding_the_engines_lock_is_waiting(stalled):
    eng, stall = stalled
    go, held = threading.Event(), threading.Event()

    def hold():
        assert go.wait(LIMIT_S)
        with eng._lock:
            held.set()
            time.sleep(0.3)

    holder = threading.Thread(target=hold, name="lock-holder")
    holder.start()

    def let_it_take_the_lock():
        go.set()
        assert held.wait(LIMIT_S)

    record = stall(let_it_take_the_lock, after=True)
    holder.join(LIMIT_S)
    assert record["cause"] == "waiting", record
    # the wait is step()'s own ``with self._lock`` (engine.lock_wait): the
    # dispatch phase, off the core, by its own doing
    assert record["wall_s"]["dispatch"] >= 0.25
    assert record["cpu_s"]["dispatch"] <= 0.1 * record["wall_s"]["dispatch"]
    assert record["voluntary"] >= 1
    caught = [c for c in record["stacks"] if c["phase"] == "dispatch"]
    assert caught and any(stack.endswith("llm.py:_locked")
                          for stack, _ in caught[0]["top"]), record["stacks"]
    # nobody kept the sampler off the GIL meanwhile: a lock, not the GIL
    assert caught[0]["lateness_frac"] < 0.5


def test_a_spinning_python_thread_is_waiting_on_the_gil(stalled):
    """A pure-Python loop on another thread under a long switch interval:
    the engine thread spends its phases waiting for the GIL (off the core,
    voluntary switches), and the stacks name the spinner."""
    eng, stall = stalled
    stop = threading.Event()

    def spinner():
        n = 0
        while not stop.is_set():
            n += 1

    was = sys.getswitchinterval()
    thread = threading.Thread(target=spinner, name="spinner")
    sys.setswitchinterval(0.05)
    thread.start()
    try:
        deadline = time.time() + LIMIT_S
        named = None
        while named is None and time.time() < deadline:
            # (a request whose every tick waited alike leaves no record:
            # the sustained shape raises the median)
            record = stall(lambda: None, need=False)
            if record and any("spinner" in stack for c in record["stacks"]
                              for stack, _ in c["top"]):
                named = record
    finally:
        stop.set()
        sys.setswitchinterval(was)
        thread.join(LIMIT_S)
    assert named is not None, record
    assert named["cause"] == "waiting", named
    wall, cpu = (sum(named[k].values()) for k in ("wall_s", "cpu_s"))
    assert wall >= 0.05 and cpu < 0.5 * wall
    assert named["voluntary"] > named["involuntary"]


# -- what the process did meanwhile ---------------------------------------------

def test_process_threads_name_the_engine_thread_and_add_up(stalled):
    eng, _ = stalled

    burnt, done = threading.Event(), threading.Event()

    def burn(then=None):
        end = time.perf_counter() + 0.5
        while time.perf_counter() < end:
            pass
        if then:  # stay alive, and off the GIL, until the second read
            burnt.set()
            then.wait(LIMIT_S)

    before = eng.perf_stats()
    worker = threading.Thread(target=burn, args=(done,), name="burner")
    worker.start()
    try:
        for _ in range(3):
            eng.generate([3, 5, 7], 6, timeout=LIMIT_S)
        burn()
        assert burnt.wait(LIMIT_S)
        after = eng.perf_stats()  # ``burner`` still lives: it has a row
    finally:
        done.set()
        worker.join(LIMIT_S)
    assert before["t"] < after["t"] <= time.time()
    proc0, proc1 = before["process"], after["process"]
    assert "generation-engine" in proc1["threads"]
    assert proc1["threads"]["burner"] >= 0.05
    assert "(native)" in proc1["threads"] or len(proc1["threads"]) >= 3
    moved = lambda name: proc1["threads"].get(name, 0.0) - proc0[  # noqa: E731
        "threads"].get(name, 0.0)
    # (a row that lost an ended thread's seconds meanwhile went DOWN: a
    # thread that has ended is in ``process_time()`` and in no row)
    by_thread = sum(max(0.0, moved(name)) for name in proc1["threads"])
    assert by_thread == pytest.approx(proc1["cpu_s"] - proc0["cpu_s"],
                                      rel=0.10)
    # the engine thread's own clock agrees with its row
    assert proc1["engine_thread_cpu_s"] - proc0["engine_thread_cpu_s"] == (
        pytest.approx(moved("generation-engine"), abs=0.02))
    assert proc1["gc"]["pause_s"] >= proc0["gc"]["pause_s"]
    assert len(proc1["gc"]["recent"]) <= tracing.GC_RECENT


def test_the_real_engines_kinds_of_time_are_consistent(stalled):
    eng, _ = stalled
    before = eng.perf_stats()
    for _ in range(3):
        eng.generate([3, 5, 7], 6, timeout=LIMIT_S)
    after = eng.perf_stats()
    ticks = after["ticks_live"] - before["ticks_live"]
    assert ticks > 0
    assert sum(after["host_hist"]["ticks"]) - sum(
        before["host_hist"]["ticks"]) == ticks
    for phase_ in _TickMeter.HOST_PHASES:
        wall = after["host_s"][phase_] - before["host_s"][phase_]
        cpu = after["host_cpu_s"][phase_] - before["host_cpu_s"][phase_]
        assert 0 <= cpu <= wall + 0.02  # (two clocks: a tick of slack)
    assert sum(after["host_hist"]["seconds"]) == pytest.approx(
        sum(after["host_s"].values()))


def test_nothing_is_recorded_with_events_off(monkeypatch):
    monkeypatch.setattr(events_mod, "ENABLED", False)
    eng = tiny_engine().start()
    try:
        seq = events_mod.buffer().last_seq()
        paused = tracing.gc_stats()
        assert eng.generate([3, 5, 7], 6, timeout=LIMIT_S)
        gc.collect()
        stats = eng.perf_stats()
    finally:
        eng.stop()
    assert events_mod.buffer().last_seq() == seq
    assert stats["ticks_live"] == 0 and stats["slow_ticks"] == []
    assert sum(stats["host_hist"]["ticks"]) == 0
    assert not any(stats["host_cpu_s"].values())
    assert not any(stats["host_switches"]["voluntary"].values())
    assert tracing.gc_stats() == paused  # the callback sums nothing
    # what is READ at the call is still read
    assert stats["t"] > 0 and stats["process"]["cpu_s"] > 0
    assert eng._ticks.host.now is None  # no phase published either


# -- annotations that tile the tick --------------------------------------------

def test_one_tick_event_a_tick_and_the_lock_wait_inside_it(tmp_path):
    from jax.profiler import ProfileData

    from ray_tpu.util import profiling

    eng = tiny_engine().start()
    try:
        eng.generate([3, 5, 7], 6, timeout=LIMIT_S)  # programs built
        with profiling.profile_trace(str(tmp_path)):
            eng.generate([3, 5, 7], 6, timeout=LIMIT_S)
            eng.generate([3, 5, 7], 6, timeout=LIMIT_S)
            time.sleep(0.1)
    finally:
        eng.stop()
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert files
    spans = {}
    for plane in ProfileData.from_file(str(files[0])).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    ticks = spans["engine.tick"]
    assert len(ticks) == len(spans["engine.admit"]) >= 4  # one a step()
    inside = lambda span: any(s <= span[0] and span[1] <= t  # noqa: E731
                              for s, t in ticks)
    # every phase of a tick lies inside its tick; the idle wait lies outside
    for name in ("engine.admit", "engine.decode_dispatch", "engine.drain",
                 "engine.drain_wait", "engine.lock_wait"):
        assert spans[name] and all(inside(span) for span in spans[name]), name
    # two lock waits a tick that dispatched a chunk, one a tick that did not
    assert len(ticks) <= len(spans["engine.lock_wait"]) <= 2 * len(ticks)
    assert not any(inside(span) for span in spans["engine.wait_work"])


# -- the train loop reports to the same recorder ---------------------------------

def test_the_train_loops_reports_feed_the_recorder_and_the_histogram():
    from ray_tpu.air import session as air_session

    s = air_session._Session(world_rank=3)
    from ray_tpu.util import metrics

    air_session._step_time_hist()
    count = lambda: sum(  # noqa: E731
        v["count"] for k, v in metrics.registry().snapshot()[
            "ray_tpu_train_step_time_s"]["values"].items()
        if dict(k).get("rank") == "3")
    had, seq = count(), events_mod.buffer().last_seq()
    s.report({})  # the first report opens the first period
    for _ in range(12):
        time.sleep(0.004)
        s.report({"loss": 1.0})
    time.sleep(0.2)  # one slow period
    s.report({})
    assert s.steps.count == 13 and count() == had + 13
    assert s.steps.wall_s["step"] == pytest.approx(0.25, abs=0.15)
    assert s.steps.cpu_s["step"] < s.steps.wall_s["step"]
    [record] = [r for r in s.steps.slow if r["wall_s"]["step"] >= 0.2]
    assert record["what"] == "train step" and record["cause"] == "waiting"
    events = slow_events(seq)
    assert events and events[-1]["entity_id"] == "train-rank3"


# -- the operator's view -----------------------------------------------------------

def slow_tick_event(seconds, cause, entity="engine-1-1", ts=100.0):
    return {"source": "perf", "message": "slow tick", "severity": "DEBUG",
            "entity_id": entity, "origin": "worker-a", "ts": ts,
            "span_dur": seconds,
            "data": {"cause": cause, "t": ts, "what": "tick",
                     "wall_s": {"admit": seconds}, "stacks": []}}


def test_doctor_names_the_cause_of_a_host_stall():
    # a healthy replica's few slow ticks: nothing
    quiet = [slow_tick_event(0.03, "waiting", ts=100.0 + i) for i in range(5)]
    assert not [f for f in doctor.diagnose(quiet) if f["rule"] == "host_stall"]
    # one 2 s stall is enough; so is a run of 45 ms ticks
    [single] = doctor.diagnose(quiet + [slow_tick_event(2.0, "gc")])
    assert single["rule"] == "host_stall" and "cause: gc" in single["summary"]
    assert "gc.freeze" in single["remedy"] and "ROADMAP" not in single["remedy"]
    assert single["evidence"][0]["span_dur"] == 2.0
    run = [slow_tick_event(0.045, "preempted", ts=100.0 + i) for i in range(30)]
    [sustained] = doctor.diagnose(run)
    assert "cause: preempted" in sustained["summary"]
    assert "cores" in sustained["remedy"]
    # ... inside two minutes: an old stall and a new slow tick are no finding
    assert not doctor.diagnose([slow_tick_event(0.9, "gc", ts=100.0),
                                slow_tick_event(0.9, "gc", ts=400.0)])
    # threads are judged apart: two that stay under the bar add up to nothing
    apart = [slow_tick_event(0.6, "cpu", entity=f"engine-{i}") for i in (1, 2)]
    assert not doctor.diagnose(apart)
    assert doctor.render([single])


def test_ray_tpu_perf_prints_the_host_table():
    """The six readings between an engine's oldest and newest meter event on
    the head's record, and the newest slow ticks with their cause in a word
    and the stack that ran meanwhile."""
    import io
    from contextlib import redirect_stdout

    import ray_tpu
    from ray_tpu.experimental.state import api as state
    from ray_tpu.scripts.cli import main as cli_main

    ray_tpu.init(num_cpus=1, num_tpus=0)
    try:
        m = _TickMeter("engine-host-table")
        m.ticks["interleaved"] = 1  # (its event rides the interference meter's)
        for _ in range(10):
            m.tick_host(phase(0.002, 0.001), phase(0.002, 0.001, invol=1),
                        phase(0.001))
        m.emit_event()
        time.sleep(0.05)
        for _ in range(10):
            m.tick_host(phase(0.002, 0.001), phase(0.002, 0.001, invol=1),
                        phase(0.001))
        m.host.caught.append((5.0, "admit", [["a.py:main|b.py:hog", 9]], 0.8))
        m.tick_host(phase(0.090, 0.002, vol=18), phase(0.003), phase(0.002),
                    began=1.0)
        m.emit_event()
        host = state.perf_summary(window_s=600.0)["host"]
        [(eid, got)] = [(e, h) for e, h in host["readings"].items()
                        if e.endswith("engine-host-table")]
        assert got["ticks"] == 11 and got["tick_host_max_ms"] == 128.0
        assert got["slow_ticks_s"] == pytest.approx(0.095)
        assert got["thread_offcore_pct"] == pytest.approx(
            100 * (0.145 - 0.037) / 0.145, abs=0.01)
        [record] = host["slow"][eid]
        assert record["cause"] == "waiting"
        buf = io.StringIO()
        with redirect_stdout(buf):
            cli_main(["perf", "--window", "600"])
        text = buf.getvalue()
        assert "HOST" in text and "OFFCORE" in text
        assert "slow tick 95ms (admit)" in text
        assert "waiting <- a.py:main|b.py:hog" in text
    finally:
        ray_tpu.shutdown()
