"""One span tree per served request, ingress to the last reply, the
per-process aggregate of its stages that ``perf_stats()`` returns, and the
engine's tick meter: what the benchmark's stage and counter readers rest on."""

import http.client
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import events as events_mod
from ray_tpu.serve.llm import (
    FIRST_REPLY_STAGES,
    PER_GAP,
    STAGES,
    GenerationEngine,
    _TickMeter,
    make_config,
)
from ray_tpu.util import compile_cache, tracing

TILE = [p for p in FIRST_REPLY_STAGES if p != "serve.first_reply"]


def tiny_engine(**kw):
    cfg = make_config("gpt2", "tiny", dtype=jnp.float32)
    kw = {"n_slots": 2, "max_new_tokens": 6, "decode_chunk_steps": 3,
          "prefill_buckets": (8,), **kw}
    return GenerationEngine(cfg, **kw).start()


@pytest.fixture(scope="module")
def llm_http():
    """A tiny llm_deployment behind the HTTP proxy, warmed (every program
    built) by one streamed request."""
    os.environ["RAY_TPU_EVENTS_FLUSH_S"] = "0.2"
    ray_tpu.init(num_cpus=4, num_tpus=0)
    serve.start(serve.HTTPOptions(host="127.0.0.1", port=0))
    from ray_tpu.serve.llm import llm_deployment

    dep = llm_deployment(
        "gpt2", "tiny",
        engine_kwargs=dict(n_slots=2, max_new_tokens=24,
                           decode_chunk_steps=4, prefill_buckets=(8,)),
        config_kwargs=dict(dtype=jnp.float32))
    handle = serve.run(dep.bind(), port=0, timeout_s=300)
    host, port = serve.get_http_address()

    def post(n_new=24):
        conn = http.client.HTTPConnection(host, port, timeout=300)
        try:
            conn.request("POST", "/llm", body=json.dumps(
                {"tokens": [3, 5, 7], "max_new_tokens": n_new, "stream": True}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200, resp.read()
            return [int(x) for x in resp.read().decode().split()]
        finally:
            conn.close()

    assert len(post()) == 24
    yield post, handle
    serve.shutdown()
    ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_EVENTS_FLUSH_S", None)


def stages_of(handle):
    return ray_tpu.get(handle.perf_stats.remote(), timeout=60)["stages"]


def test_streamed_request_is_one_span_tree(llm_http):
    """root -> router admission -> replica task -> task.dispatch,
    serve.submit, the engine's request (queue, first token, stream yield,
    decode, last yield), serve.pickup, serve.first_reply, serve.last_pickup
    and serve.stream, under ONE trace id, ONE of each however many tokens
    the request got.  Before the stream thread adopted the request's context
    the engine's span of a streamed request was dropped."""
    from ray_tpu.experimental.state import api as state

    post, _ = llm_http
    known = {t["trace_id"] for t in state.list_traces(limit=1000)}
    post()
    want = set(STAGES) - {"serve.route"} | {"http", "router_admission",
                                             "llm_generate", "task"}
    deadline = time.time() + 30
    tr, phases = None, set()
    while time.time() < deadline and not want <= phases:
        time.sleep(0.3)
        for t in state.list_traces(limit=1000):
            if t["trace_id"] not in known and "POST /llm" in (t.get("name") or ""):
                tr = state.get_trace(t["trace_id"])
                phases = {s["phase"] for s in tr["spans"]}
    assert want <= phases, (want - phases, phases)
    assert "serve.route" not in phases  # folded, never drawn: the
    # router's admission span already covers the interval
    by_id = {s["span_id"]: s for s in tr["spans"]}
    call = next(s for s in tr["spans"] if s["phase"] == "task"
                and s["name"] == "ServeReplica.handle_request")

    def one(phase):
        # the router's own calls to the controller are traced tasks too,
        # with a task.dispatch each: take the replica call's
        found = [s for s in tr["spans"] if s["phase"] == phase
                 and (phase != "task.dispatch"
                      or s["parent_span_id"] == call["span_id"])]
        assert len(found) == 1, (phase, found)
        return found[0]

    def lineage(span):
        out = []
        while span is not None:
            out.append(span["phase"])
            span = by_id.get(span["parent_span_id"])
        return out

    assert {s["trace_id"] for s in tr["spans"]} == {tr["trace_id"]}
    for phase in ("task.dispatch", "serve.submit", "serve.pickup",
                  "serve.first_reply", "serve.last_pickup", "serve.stream"):
        assert lineage(one(phase)) == [
            phase, "task", "router_admission", "http"], phase
    for phase in ("engine.queue", "engine.first_token", "engine.stream_yield",
                  "engine.decode", "engine.last_yield"):
        assert lineage(one(phase)) == [
            phase, "llm_generate", "task", "router_admission", "http"], phase
    # 24 tokens in chunks of 4 (five whole, and one CUT to the 3 steps the
    # answer had left): nothing of it is emitted per token or chunk
    # (every traced task has a task.dispatch: the proxy's polls too)
    mine = [s["phase"] for s in tr["spans"]
            if s["phase"] in STAGES and s["phase"] != "task.dispatch"]
    assert sorted(mine) == sorted(
        set(STAGES) - {"serve.route", "task.dispatch"})
    assert not any(s["phase"] in PER_GAP for s in tr["spans"])  # folded only
    decode = one("engine.decode")
    assert (decode["data"]["tokens"], decode["data"]["chunks"],
            decode["data"]["chunk_steps"]) == (24, 6, 23)
    assert one("serve.stream")["data"]["stream_chunks"] == 24  # one a token
    # the decode stages are drawn where they happened, not where they were
    # emitted: decode from the first token's landing, the stream from the
    # first reply to the last reply that carried data
    assert decode["start"] == pytest.approx(
        one("engine.first_token")["end"], abs=0.02)
    assert one("serve.stream")["start"] == pytest.approx(
        one("serve.first_reply")["end"], abs=0.02)
    assert one("serve.stream")["end"] == pytest.approx(
        one("serve.last_pickup")["end"], abs=1e-6)
    assert decode["end"] <= one("engine.last_yield")["end"] <= (
        one("serve.stream")["end"] + 0.005)
    # in time order, each stage starts where the one before it ended
    order = [one(p) for p in TILE[1:]]
    for a, b in zip(order, order[1:]):
        assert b["start"] == pytest.approx(a["end"], abs=0.02), (
            a["phase"], b["phase"])
    assert one("serve.first_reply")["end"] == pytest.approx(
        one("serve.pickup")["end"], abs=0.005)


def window(llm_http, n):
    """The stages' count and sum over ``n`` concurrent streamed requests:
    the difference of two cumulative snapshots."""
    post, handle = llm_http
    before = stages_of(handle)
    with ThreadPoolExecutor(4) as pool:  # 4 clients on 2 slots: a real queue
        assert all(len(t) == 24 for t in pool.map(lambda _: post(), range(n)))
    after = stages_of(handle)
    assert after["clock_skew"] == 0
    return {p: {k: after[p][k] - before.get(p, {}).get(k, 0)
                for k in ("count", "sum_s")} for p in STAGES + PER_GAP}, after


def test_stage_counts_are_exact_over_a_window(llm_http):
    """N requests between two snapshots: every request phase counts N, the
    decode stages and the two per-gap folds too (one a FINISHED request,
    none a token)."""
    diff, after = window(llm_http, 8)
    assert {p: d["count"] for p, d in diff.items()} == {
        p: 8 for p in STAGES + PER_GAP}
    for p in STAGES + PER_GAP:
        assert 0 <= after[p]["p50_s"] <= after[p]["p95_s"]
        # the window's own durations: the last 8 of ``recent``
        assert sum(after[p]["recent"][-8:]) == pytest.approx(
            diff[p]["sum_s"], rel=1e-6, abs=1e-9)


def test_the_seven_stages_tile_the_first_reply(llm_http):
    """serve.first_reply is read on its own two clock reads; the seven
    stages, each on its own, add up to it: no hole and no overlap."""
    diff, _ = window(llm_http, 8)
    whole = diff["serve.first_reply"]["sum_s"]
    parts = sum(diff[p]["sum_s"] for p in TILE)
    assert parts == pytest.approx(whole, rel=0.05), diff


def test_the_decode_stages_tile_the_stream(llm_http):
    """serve.stream (first data reply -> last data reply, its own two clock
    reads) = engine.decode + (engine.last_yield + serve.last_pickup) -
    (engine.stream_yield + serve.pickup), up to the encode-and-put between a
    yield and its put; and a request's pace is the same statistic at both
    depths, 23 gaps a request here."""
    diff, _ = window(llm_http, 8)
    sums = {p: d["sum_s"] for p, d in diff.items()}
    parts = (sums["engine.decode"]
             + sums["engine.last_yield"] + sums["serve.last_pickup"]
             - sums["engine.stream_yield"] - sums["serve.pickup"])
    assert parts == pytest.approx(sums["serve.stream"], abs=8 * 0.004), diff
    assert sums["engine.decode_per_token"] == pytest.approx(
        sums["engine.decode"] / 23, rel=1e-6)
    assert sums["serve.stream_per_chunk"] == pytest.approx(
        sums["serve.stream"] / 23, rel=1e-6)


def test_recent_is_in_closing_order_and_a_window_is_its_tail():
    """``recent``: the reservoir's newest durations as they closed, at most
    STATS_RECENT; a reader that differences ``count`` takes that many off
    the end and never sees what closed before its window."""
    phase = "test.recent"
    for d in (9.0, 8.0, 7.0):  # before the window: slow, must not be read
        tracing.fold(phase, d)
    before = tracing.span_stats([phase])[phase]
    assert before["recent"] == [9.0, 8.0, 7.0]
    for d in (0.3, 0.1, 0.2):
        tracing.fold(phase, d)
    after = tracing.span_stats([phase])[phase]
    assert after["recent"] == [9.0, 8.0, 7.0, 0.3, 0.1, 0.2]  # not sorted
    assert after["recent"][-(after["count"] - before["count"]):] == [
        0.3, 0.1, 0.2]
    for i in range(tracing.STATS_RECENT):
        tracing.fold(phase, float(i))
    row = tracing.span_stats([phase])[phase]
    assert row["count"] == 6 + tracing.STATS_RECENT
    assert row["recent"] == [float(i) for i in range(tracing.STATS_RECENT)]


def test_tick_meter_bills_the_period_to_the_tick_that_held_the_prefill():
    """Decode-only, a tick with a prefill call, decode-only, on a synthetic
    clock: a period runs from the previous landing to the tick's own, so the
    prefill's 0.25 s are in the tick that held the call, not in the next;
    the first tick after an idle engine has no previous landing and is left
    out, though the second call of such a tick has one."""
    m = _TickMeter("test")
    m.begin(chained=False)            # the first tick after idle: left out
    m.chunk_landed(10.0, 0, 2)
    m.begin(chained=True)             # decode-only, 0.1 s
    m.chunk_landed(10.1, 0, 2)
    m.begin(chained=True)             # a 0.25 s call, then its 0.1 s chunk
    m.call_landed(10.35)
    mark = (m.prefill_s, m.prefill_calls)  # the admitted request's own call
    m.chunk_landed(10.45, 1, 3)
    m.begin(chained=True)             # decode-only again, 0.1 s
    m.chunk_landed(10.55, 0, 3)
    m.tick_host(0.001, 0.002, 0.003)
    m.request_done(tokens=18, chunk_steps=32, span_s=0.2,  # two whole chunks
                   prefill_s=m.prefill_s - mark[0])
    snap = m.snapshot()
    assert snap["ticks"] == {"decode_only": 2, "interleaved": 1,
                             "prefill_only": 0}
    assert snap["tick_s"] == pytest.approx(
        {"decode_only": 0.2, "interleaved": 0.35, "prefill_only": 0.0})
    assert "periods" not in snap  # the class sums above hold them
    assert snap["decode_tick_baseline_s"] == pytest.approx(0.1)
    assert snap["interference_s"] == pytest.approx(0.25)
    assert snap["interference_frac"] == pytest.approx(0.25 / 0.55, abs=1e-4)
    assert snap["tick_excess_s"] == pytest.approx(0.25)
    assert snap["excess_billed_to_prefill"] == pytest.approx(1.0)
    assert snap["host_s"] == {"admit": 0.001, "dispatch": 0.002,
                              "drain_book": 0.003}
    assert snap["ticks_live"] == 1
    # the request's decode span saw none of its OWN call's seconds
    assert snap["decode"] == {"requests": 1, "gaps": 17,
                              "chunk_steps_paid": 32, "span_s": 0.2,
                              "prefill_s": 0.0}
    # an idle engine, then a burst of two calls in one tick: the tick is
    # left out, but a request of the first call waits for the second
    m.begin(chained=False)
    m.call_landed(20.0)
    m.call_landed(20.3)
    m.chunk_landed(20.4, 2, 2)
    assert m.snapshot()["ticks"] == snap["ticks"]
    assert (m.prefill_s, m.prefill_calls) == pytest.approx((0.55, 2))
    # a tick that holds nothing but admissions is no interference
    m.begin(chained=True)
    m.call_landed(20.6)
    m.chunk_landed(20.7, 1, 1)
    snap = m.snapshot()
    assert snap["ticks"]["prefill_only"] == 1
    assert snap["interference_s"] == pytest.approx(0.25)
    assert snap["tick_s"]["prefill_only"] == pytest.approx(0.3)


def test_engine_meter_and_decode_counters_over_real_requests():
    """The engine feeds the meter from its drains: a request of 6 tokens in
    chunks of 3 rides two chunks, the second CUT to the two steps it has
    left, so it pays five steps for five gaps; its ``engine.decode`` span
    runs landing to landing, and ``itl``/``ttft`` are stamped there too."""
    eng = tiny_engine()
    try:
        eng.generate([3, 5, 7], 6)  # build the programs
        before = eng.perf_stats()
        seq = events_mod.buffer().last_seq()
        futs = [eng.submit([3, 5, 7], 6) for _ in range(3)]  # 3 on 2 slots
        for f in futs:
            assert len(f.result(timeout=120)) == 6
    finally:
        eng.stop()
    after = eng.perf_stats()
    d = {k: after["decode"][k] - before["decode"][k] for k in after["decode"]}
    assert (d["requests"], d["gaps"], d["chunk_steps_paid"]) == (3, 15, 15)
    assert 0 <= d["prefill_s"] <= d["span_s"]
    spans = [r for r in events_mod.buffer().since(seq)
             if (r.get("data") or {}).get("phase") == "engine.decode"]
    assert len(spans) == 3
    assert sum(r["span_dur"] for r in spans) == pytest.approx(d["span_s"])
    assert all(r["data"]["tokens"] == 6 and r["data"]["chunks"] == 2
               and r["data"]["chunk_steps"] == 5 for r in spans)
    assert after["ticks_live"] > before["ticks_live"]
    host = {k: after["host_s"][k] - before["host_s"][k]
            for k in after["host_s"]}
    assert sorted(host) == ["admit", "dispatch", "drain_book"]
    assert all(v >= 0 for v in host.values()) and host["dispatch"] > 0
    # the third request was admitted while the others decoded or right
    # after them: its tick was dispatched behind an undrained one
    assert sum(after["ticks"].values()) > sum(before["ticks"].values())
    assert after["itl"]["count"] >= before["itl"]["count"] + 6
    assert after["ttft"]["count"] == before["ttft"]["count"] + 3


def test_engine_spans_do_not_depend_on_the_ingress():
    """A caller with no context (a DeploymentHandle, bench.py) still gets
    the engine's stages, under a root the engine made."""
    assert tracing.current_context() is None
    eng = tiny_engine()
    try:
        seq = events_mod.buffer().last_seq()
        assert len(eng.generate([3, 5, 7], 6)) == 6
        assert len(list(eng.stream([3, 5, 7], 6))) == 6
    finally:
        eng.stop()
    rows = [r for r in events_mod.buffer().since(seq)
            if (r.get("data") or {}).get("trace_id")]
    by_trace = {}
    for r in rows:
        by_trace.setdefault(r["data"]["trace_id"], []).append(r["data"])
    assert len(by_trace) == 2  # one root a request
    for spans in by_trace.values():
        root = next(d for d in spans if d["phase"] == "llm_generate")
        assert root["parent_span_id"] == ""
        stages = {d["phase"] for d in spans
                  if d["parent_span_id"] == root["span_id"]}
        assert {"engine.queue", "engine.first_token",
                "engine.decode"} <= stages
    for phase in ("engine.stream_yield", "engine.last_yield"):
        # the streamed request's alone
        assert [r["data"]["phase"] for r in rows].count(phase) == 1
    # no task carried them here, so the upstream stages are absent
    assert not any(r["data"]["phase"] in ("task.dispatch", "serve.submit")
                   for r in rows)


def test_last_yield_is_emitted_when_the_future_resolves_a_wake_late(
        monkeypatch):
    """The engine appends a request's last token, emits what it emits once a
    request, and only then resolves the future.  An answer of ONE token is
    its prefill's: the drain's first wake hands it to ``stream``, which sees
    the request done a wake later (after the chunk's read and the rows'
    bookkeeping).  The stage is still emitted, once, timed at the yield."""
    eng = tiny_engine()
    emit_done = eng._emit_done

    def slow_emit_done(*a):
        time.sleep(0.08)  # between the last append and the resolution
        emit_done(*a)

    try:
        eng.generate([3, 5, 7], 6)  # build the programs
        monkeypatch.setattr(eng, "_emit_done", slow_emit_done)
        seq = events_mod.buffer().last_seq()
        t0 = time.perf_counter()
        for n, _ in enumerate(eng.stream([3, 5, 7], 1), 1):
            got_last = time.perf_counter() - t0
        took = time.perf_counter() - t0
    finally:
        eng.stop()
    assert n == 1
    assert took - got_last > 0.03  # the last token came before ``done``
    found = [r for r in events_mod.buffer().since(seq)
             if (r.get("data") or {}).get("phase") == "engine.last_yield"]
    assert len(found) == 1
    assert 0 <= found[0]["span_dur"] < 0.06  # a wake, not the wait for done


def _stage_durations(seq, phase):
    return [r["span_dur"] for r in events_mod.buffer().since(seq)
            if (r.get("data") or {}).get("phase") == phase]


def test_stream_yields_at_the_drains_wake_not_at_a_poll():
    """A token the drain appended is handed over by ``stream`` at the
    drain's signal: the wake-up's latency, not a share of a 20 ms sleep (a
    uniform 0 - 20 ms wait has a median of 10), and every token of a chunk
    comes with the same wake."""
    import inspect

    assert "sleep" not in inspect.getsource(GenerationEngine.stream)
    eng = tiny_engine()
    try:
        eng.generate([3, 5, 7], 6)  # build the programs
        seq = events_mod.buffer().last_seq()
        stamps = []
        for i in range(8):
            stamps.append([time.perf_counter()
                           for _ in eng.stream([3, 5, 7 + i], 6)])
    finally:
        eng.stop()
    first = sorted(_stage_durations(seq, "engine.stream_yield"))
    last = sorted(_stage_durations(seq, "engine.last_yield"))
    assert len(first) == len(last) == 8
    assert first[4] < 0.008 and last[4] < 0.008, (first, last)
    for row in stamps:
        # 1 token of the prefill, then chunks of 3 + 2 (cut at the cap):
        # a chunk's tokens are yielded together, no sleep between them
        assert len(row) == 6
        assert row[3] - row[1] < 0.005 and row[5] - row[4] < 0.005, row


def test_engine_failure_wakes_every_waiting_stream_with_the_error(
        monkeypatch):
    """``_loop``'s failure path fails every victim and wakes the streams
    that wait for them: each raises the engine's error at once, the ones
    that held slots and the ones still queued alike."""
    eng = tiny_engine()
    caught = []

    def consume(i):
        t0 = time.perf_counter()
        try:
            list(eng.stream([3, 5, 7 + i], 6, timeout=60.0))
        except Exception as e:  # noqa: BLE001 — the error IS the result
            caught.append((type(e), str(e), time.perf_counter() - t0))

    def broken_tick(meter):
        raise RuntimeError("device on fire")

    try:
        eng.generate([3, 5, 7], 6)
        monkeypatch.setattr(eng, "_tick", broken_tick)
        with ThreadPoolExecutor(4) as pool:  # 2 slots: two of them queue
            list(pool.map(consume, range(4)))
    finally:
        eng.stop()
    assert [(c, m) for c, m, _ in caught] == [
        (RuntimeError, "device on fire")] * 4
    assert max(t for *_, t in caught) < 5.0  # not the stream's timeout


def test_stream_timeout_still_raises_and_stop_wakes_the_waiters():
    """Nothing drains a never-started engine: ``stream`` waits on the
    condition and its ``timeout`` is the only clock.  ``stop()`` notifies,
    and a waiter that finds nothing new goes back to waiting."""
    cfg = make_config("gpt2", "tiny", dtype=jnp.float32)
    eng = GenerationEngine(cfg, n_slots=2, max_new_tokens=6,
                           decode_chunk_steps=3, prefill_buckets=(8,))
    t0 = time.perf_counter()
    with ThreadPoolExecutor(1) as pool:
        fut = pool.submit(lambda: list(eng.stream([3, 5, 7], 6, timeout=0.6)))
        time.sleep(0.2)
        eng.stop()  # wakes it; nothing landed, so it waits the rest out
        with pytest.raises(TimeoutError):
            fut.result(timeout=30)
    assert 0.55 < time.perf_counter() - t0 < 5.0


def test_events_off_means_no_stage_and_no_span(monkeypatch):
    folded = lambda: {p: r["count"] for p, r in tracing.span_stats(  # noqa: E731
        STAGES + PER_GAP).items()}
    had = folded()
    monkeypatch.setattr(events_mod, "ENABLED", False)
    eng = tiny_engine()
    try:
        seq = events_mod.buffer().last_seq()
        assert len(list(eng.stream([3, 5, 7], 6))) == 6
        stats = eng.perf_stats()
    finally:
        eng.stop()
    assert stats["stages"] == {}
    assert events_mod.buffer().last_seq() == seq
    assert "ttft" in stats and "compiles" in stats and "device" in stats
    # the meter's keys are there and nothing was added to them
    assert stats["ticks_live"] == 0 and stats["decode"]["requests"] == 0
    assert sum(stats["ticks"].values()) == 0
    assert stats["ttft"]["count"] == stats["itl"]["count"] == 0
    assert folded() == had


def test_clock_skew_clamps_and_counts():
    """A stage across processes is the receiver's clock minus the context's
    stamp: a context stamped in the future reads 0, and is counted."""
    skew = tracing.clock_skew()
    assert tracing.since(time.time() - 1.0) == pytest.approx(1.0, abs=0.1)
    assert tracing.clock_skew() == skew
    assert tracing.since(time.time() + 5.0) == 0.0
    assert tracing.clock_skew() == skew + 1
    with tracing.trace("root") as root:
        ctx = tracing.child_context("call")
    assert ctx["t_root"] == root["t"] <= ctx["t"]
    ctx["t"] += 5.0  # the submitter's clock runs ahead
    seq = events_mod.buffer().last_seq()
    before = tracing.span_stats(["task.dispatch"]).get(
        "task.dispatch", {"count": 0, "sum_s": 0.0})
    now = time.time()
    adopted = tracing.task_arrived(ctx, now)
    assert adopted["t_exec"] == now and adopted["span_id"] == ctx["span_id"]
    assert tracing.clock_skew() == skew + 2
    row = events_mod.buffer().since(seq)[-1]
    assert row["data"]["phase"] == "task.dispatch" and row["span_dur"] == 0.0
    assert row["data"]["parent_span_id"] == ctx["span_id"]
    after = tracing.span_stats(["task.dispatch"])["task.dispatch"]
    assert (after["count"], after["sum_s"]) == (
        before["count"] + 1, before["sum_s"])


def test_compile_counter_counts_new_shapes_only():
    import jax

    compile_cache.listen()
    compile_cache.listen()  # once, however often it is asked
    f = jax.jit(lambda x: x * 2 + 1)
    a, b = np.ones(3, np.float32), np.ones(5, np.float32)
    start = compile_cache.counts()
    f(a).block_until_ready()
    one = compile_cache.counts()
    assert one["count"] == start["count"] + 1
    assert one["seconds"] > start["seconds"]
    f(a).block_until_ready()
    assert compile_cache.counts() == one
    f(b).block_until_ready()
    two = compile_cache.counts()
    assert two["count"] == one["count"] + 1
    assert two["cache_misses"] == two["count"] - two["cache_hits"]


def test_engine_phases_are_in_the_profilers_trace(tmp_path):
    """The engine thread's phases land in the same trace file as the
    device's ops, under stable names, with the python tracer off; the
    drain's blocking reads have a name of their own, and ONE
    ``engine.wait_work`` event covers an idle period however long."""
    from jax.profiler import ProfileData

    from ray_tpu.util import profiling

    eng = tiny_engine()
    try:
        eng.generate([3, 5, 7], 6)  # build the programs outside the trace
        with profiling.profile_trace(str(tmp_path)):
            eng.generate([3, 5, 7], 6)
            time.sleep(0.3)  # one idle period
            eng.generate([3, 5, 7], 6)
    finally:
        eng.stop()
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert files
    events = [e for plane in ProfileData.from_file(str(files[0])).planes
              for line in plane.lines for e in line.events]
    names = {e.name for e in events}
    assert {"engine.admit", "engine.decode_dispatch", "engine.drain",
            "engine.drain_wait", "engine.wait_work"} <= names
    assert not any(n.startswith("$") for n in names)  # python frames
    # the 0.3 s between the two requests is ONE event, not six of 50 ms (a
    # submit that lands while the engine is busy leaves a wait of no length)
    waits = [e.duration_ns / 1e9 for e in events
             if e.name == "engine.wait_work" and e.duration_ns > 60e6]
    assert len(waits) == 1 and waits[0] >= 0.25, waits
    drains = [(e.start_ns, e.start_ns + e.duration_ns) for e in events
              if e.name == "engine.drain"]
    for e in events:  # every blocking read lies inside a drain
        if e.name == "engine.drain_wait":
            assert any(s <= e.start_ns and e.start_ns + e.duration_ns <= t
                       for s, t in drains)
