"""One span tree per served request, ingress to first token, and the
per-process aggregate of its stages that ``perf_stats()`` returns: what the
benchmark's stage readers rest on."""

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
from ray_tpu.serve.llm import STAGES, GenerationEngine, make_config
from ray_tpu.util import compile_cache, tracing

TILE = [p for p in STAGES if p != "serve.first_reply"]


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
    serve.submit, the engine's request (queue, first token, stream yield),
    serve.pickup and serve.first_reply, under ONE trace id.  Before the
    stream thread adopted the request's context the engine's span of a
    streamed request was dropped."""
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
                  "serve.first_reply"):
        assert lineage(one(phase)) == [
            phase, "task", "router_admission", "http"], phase
    for phase in ("engine.queue", "engine.first_token", "engine.stream_yield"):
        assert lineage(one(phase)) == [
            phase, "llm_generate", "task", "router_admission", "http"], phase
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
                for k in ("count", "sum_s")} for p in STAGES}, after


def test_stage_counts_are_exact_over_a_window(llm_http):
    """N requests between two snapshots: every request phase counts N."""
    diff, after = window(llm_http, 8)
    assert {p: d["count"] for p, d in diff.items()} == {p: 8 for p in STAGES}
    for p in STAGES:
        assert 0 <= after[p]["p50_s"] <= after[p]["p95_s"]


def test_the_seven_stages_tile_the_first_reply(llm_http):
    """serve.first_reply is read on its own two clock reads; the seven
    stages, each on its own, add up to it: no hole and no overlap."""
    diff, _ = window(llm_http, 8)
    whole = diff["serve.first_reply"]["sum_s"]
    parts = sum(diff[p]["sum_s"] for p in TILE)
    assert parts == pytest.approx(whole, rel=0.05), diff


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
        assert {"engine.queue", "engine.first_token"} <= stages
    assert any(d["phase"] == "engine.stream_yield" for d in rows
               for d in [d["data"]])
    # no task carried them here, so the upstream stages are absent
    assert not any(r["data"]["phase"] in ("task.dispatch", "serve.submit")
                   for r in rows)


def test_events_off_means_no_stage_and_no_span(monkeypatch):
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
    device's ops, under stable names, with the python tracer off."""
    from jax.profiler import ProfileData

    from ray_tpu.util import profiling

    eng = tiny_engine()
    try:
        eng.generate([3, 5, 7], 6)  # build the programs outside the trace
        with profiling.profile_trace(str(tmp_path)):
            eng.generate([3, 5, 7], 6)
            time.sleep(0.12)  # an idle loop turn or two
    finally:
        eng.stop()
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert files
    names = {e.name for plane in ProfileData.from_file(str(files[0])).planes
             for line in plane.lines for e in line.events}
    assert {"engine.admit", "engine.decode_dispatch", "engine.drain",
            "engine.wait_work"} <= names
    assert not any(n.startswith("$") for n in names)  # python frames
