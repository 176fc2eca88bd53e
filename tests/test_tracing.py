"""End-to-end distributed request tracing + critical path + doctor.

Covers the trace-context plane (``util/tracing.py``): nested task chains
sharing a trace_id, actor and serve-handle calls continuing the caller's
trace, compiled-graph executions joining the submitting trace through
channel payloads, disabled-by-default spec hygiene; the head-side
assembly (``TraceTable``, ``get_trace``/``list_traces``/
``summarize_traces``); critical-path analysis
(``util/trace_analysis.py``); the rule-based ``ray_tpu doctor``
(``util/doctor.py`` — induced pathologies flag, healthy runs stay
clean); the head-side ``summarize_state`` RPC; and the collapsed
sampling-profile format.
"""

import json
import os
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu._private import events as events_mod
from ray_tpu.util import tracing


@pytest.fixture(scope="module")
def trace_cluster():
    """One cluster for the tracing tests: traces are isolated by
    construction (fresh trace_id per block), and sharing the boot keeps
    the tier-1 wall-clock flat.  Fast event flush so worker-shipped spans
    land quickly."""
    os.environ["RAY_TPU_EVENTS_FLUSH_S"] = "0.2"
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_EVENTS_FLUSH_S", None)


def _get_trace_until(tid, pred, timeout=20.0):
    """Poll get_trace until ``pred(trace)`` holds (worker spans ship on
    the pusher cadence)."""
    from ray_tpu.experimental.state import api as state

    deadline = time.time() + timeout
    tr = None
    while time.time() < deadline:
        tr = state.get_trace(tid)
        if tr is not None and pred(tr):
            return tr
        time.sleep(0.2)
    return tr


# ---------------------------------------------------------------------------
# context plumbing (no cluster)
# ---------------------------------------------------------------------------

def test_no_context_means_no_propagation():
    """Disabled-by-default: outside any trace() block nothing is created
    — child contexts are None, span() is a no-op, emit_span drops."""
    assert tracing.current_context() is None
    assert tracing.child_context("x") is None
    assert tracing.child_context_for_task("x") is None
    before = events_mod.buffer().last_seq()
    with tracing.span("noop"):
        pass
    tracing.emit_span("nothing", 1.0, None)
    assert events_mod.buffer().last_seq() == before


def test_trace_context_nesting_and_span_events():
    with tracing.trace("outer") as outer:
        assert tracing.current_context() == outer
        child = tracing.child_context("hop")
        assert child["trace_id"] == outer["trace_id"]
        assert child["parent_span_id"] == outer["span_id"]
        with tracing.trace("inner") as inner:
            assert inner["trace_id"] == outer["trace_id"]
            assert inner["parent_span_id"] == outer["span_id"]
    assert tracing.current_context() is None
    rows = [r for r in events_mod.local_events()
            if r["source"] == "trace"
            and (r.get("data") or {}).get("trace_id") == outer["trace_id"]]
    names = {r["message"] for r in rows}
    assert {"outer", "inner"} <= names
    inner_row = next(r for r in rows if r["message"] == "inner")
    assert inner_row["data"]["parent_span_id"] == outer["span_id"]
    assert inner_row["span_dur"] >= 0


def test_trace_table_assembles_and_caps():
    t = events_mod.TraceTable(max_traces=2, max_spans=3)
    def row(tid, sid, parent="", ts=1.0, dur=0.5, msg="m"):
        return {"ts": ts, "source": "trace", "severity": "DEBUG",
                "message": msg, "span_dur": dur,
                "data": {"trace_id": tid, "span_id": sid,
                         "parent_span_id": parent, "phase": "span"}}
    t.add("w1", [row("a", "s1"), row("a", "s2", parent="s1", ts=1.4),
                 {"ts": 2.0, "source": "scheduler", "message": "no trace"}])
    got = t.get("a")
    assert [s["span_id"] for s in got["spans"]] == ["s1", "s2"]
    assert got["spans"][0]["start"] == pytest.approx(0.5)
    # per-trace span cap: LAST-N kept (spans arrive child-first, so the
    # root closes last — keep-last preserves the upper tree), the
    # overflow counted as dropped
    t.add("w1", [row("a", f"x{i}", ts=3.0 + i) for i in range(4)])
    got = t.get("a")
    assert len(got["spans"]) == 3 and got["dropped_spans"] == 3
    assert [s["span_id"] for s in got["spans"]] == ["x1", "x2", "x3"]
    # trace cap: LRU eviction of the least recently updated
    t.add("w1", [row("b", "s1")])
    t.add("w1", [row("c", "s1")])
    assert t.get("a") is None and t.get("c") is not None
    assert len(t) == 2
    summary = t.summarize()
    assert summary["num_traces"] == 2


# ---------------------------------------------------------------------------
# critical-path analysis (pure)
# ---------------------------------------------------------------------------

def test_critical_path_phase_attribution():
    from ray_tpu.util.trace_analysis import analyze, render_trace

    trace = {"trace_id": "t", "spans": [
        {"name": "root", "span_id": "r", "parent_span_id": "",
         "phase": "http", "source": "trace", "start": 0.0, "end": 10.0},
        {"name": "queue", "span_id": "q", "parent_span_id": "r",
         "phase": "scheduler_queue", "source": "task",
         "start": 1.0, "end": 4.0},
        {"name": "exec", "span_id": "x", "parent_span_id": "r",
         "phase": "execution", "source": "task", "start": 4.0, "end": 9.0},
        {"name": "wait", "span_id": "w", "parent_span_id": "x",
         "phase": "channel_wait", "source": "compiled_dag",
         "start": 5.0, "end": 7.0},
    ]}
    a = analyze(trace)
    assert a["wall_s"] == pytest.approx(10.0)
    # phases sum exactly to wall time; the deepest span wins its window
    assert a["phases"]["http"] == pytest.approx(2.0)  # 0-1 + 9-10
    assert a["phases"]["scheduler_queue"] == pytest.approx(3.0)
    assert a["phases"]["execution"] == pytest.approx(3.0)  # 4-5 + 7-9
    assert a["phases"]["channel_wait"] == pytest.approx(2.0)
    assert sum(a["phases"].values()) == pytest.approx(a["wall_s"])
    path = [(s["name"], s["phase"]) for s in a["critical_path"]]
    assert path == [("root", "http"), ("queue", "scheduler_queue"),
                    ("exec", "execution"), ("wait", "channel_wait"),
                    ("exec", "execution"), ("root", "http")]
    text = render_trace(trace, a)
    assert "critical path" in text and "scheduler_queue" in text
    # uninstrumented gaps attribute to "idle", not to a random span
    gap = analyze({"spans": [
        {"name": "a", "span_id": "a", "phase": "p", "start": 0.0, "end": 1.0},
        {"name": "b", "span_id": "b", "phase": "p", "start": 3.0, "end": 4.0},
    ]})
    assert gap["phases"]["idle"] == pytest.approx(2.0)
    assert analyze(None) == {"wall_s": 0.0, "num_spans": 0, "phases": {},
                             "critical_path": []}


# ---------------------------------------------------------------------------
# doctor rules (pure)
# ---------------------------------------------------------------------------

def test_doctor_healthy_run_is_clean():
    from ray_tpu.util.doctor import diagnose

    events = [
        {"source": "scheduler", "message": "dispatch tick",
         "severity": "DEBUG"},
        {"source": "streaming", "message": "backpressure stall",
         "severity": "DEBUG", "data": {"op": "map", "total_stalled_s": 0.1}},
        {"source": "serve", "message": "router stalled: no replica available",
         "severity": "WARNING", "data": {"replicas": 0}},  # startup, not saturation
        {"source": "train", "message": "gang started", "severity": "INFO"},
        {"source": "compiled_dag", "message": "channel wait",
         "severity": "DEBUG", "span_dur": 60.0, "data": {"op": "recv"}},
        # healthy perf plane (PR 11 rules must stay silent on these):
        # bucketed compiles below the storm threshold, low ingest share,
        # mild prefill interference
        {"source": "perf", "message": "jit compile", "severity": "DEBUG",
         "span_dur": 0.4, "data": {"fn": "prefill", "n_sigs": 4,
                                   "misses": 4, "hits": 900}},
        {"source": "perf", "message": "step phases", "severity": "DEBUG",
         "entity_id": "rank0", "span_dur": 0.1,
         "data": {"wall_s": 0.1, "mfu": 0.4,
                  "phases": {"ingest": 0.01, "compute": 0.09}}},
        {"source": "perf", "message": "prefill interference",
         "severity": "DEBUG", "entity_id": "engine-1",
         "data": {"interference_s": 0.5, "interference_frac": 0.05,
                  "interleaved_ticks": 400, "decode_only_ticks": 5000}},
    ]
    tasks = [{"name": "t", "node_id": "n1", "exec_start": 0.0,
              "exec_end": 0.01}] * 20
    assert diagnose(events, tasks) == []


def test_doctor_flags_each_pathology():
    from ray_tpu.util import doctor

    cases = {
        "backpressure_stall": [
            {"source": "streaming", "message": "backpressure stall",
             "severity": "DEBUG",
             "data": {"op": "map", "total_stalled_s": 4.2}}],
        "split_starvation": [
            {"source": "streaming", "message": "split starved",
             "severity": "DEBUG", "data": {"wait_s": 1.5}}] * 3,
        "spill_thrash": [
            {"source": "object_store", "message": "spilled object to disk",
             "severity": "WARNING", "data": {"size_mb": 100}}] * 4,
        "oom_kills": [
            {"source": "scheduler", "message": "OOM kill",
             "severity": "WARNING"}],
        "gang_restart": [
            {"source": "train", "message": "gang restarted",
             "severity": "WARNING"}],
        "stuck_channel": [
            {"source": "compiled_dag", "message": "actor loop died",
             "severity": "ERROR"}],
        "router_saturation": [
            {"source": "serve",
             "message": "router stalled: no replica available",
             "severity": "WARNING", "data": {"replicas": 3}}],
        "worker_churn": [
            {"source": "worker_pool", "message": "worker died: signal 9",
             "severity": "WARNING"}] * 3,
    }
    for rule, events in cases.items():
        findings = doctor.diagnose(events)
        assert [f["rule"] for f in findings] == [rule], (rule, findings)
        assert findings[0]["evidence"] and findings[0]["remedy"]
    # blocked SEND-side channel wait = stuck consumer (recv idle is fine)
    send_stuck = doctor.diagnose([
        {"source": "compiled_dag", "message": "channel wait",
         "severity": "DEBUG", "span_dur": 9.0, "data": {"op": "send"}}])
    assert [f["rule"] for f in send_stuck] == ["stuck_channel"]
    # slow-node skew needs same-name tasks on >= 2 nodes with real deltas
    slow = [{"name": "step", "node_id": "n-slow", "exec_start": 0.0,
             "exec_end": 0.9}] * 6
    fast = [{"name": "step", "node_id": "n-fast", "exec_start": 0.0,
             "exec_end": 0.1}] * 6
    findings = doctor.diagnose([], slow + fast)
    assert [f["rule"] for f in findings] == ["slow_node_skew"]
    assert "n-slow" in findings[0]["summary"]
    assert doctor.render(findings).startswith("ray_tpu doctor: 1 finding")
    assert "no findings" in doctor.render([])


# ---------------------------------------------------------------------------
# cluster end-to-end
# ---------------------------------------------------------------------------

def test_nested_tasks_share_trace_and_specs_stay_clean(trace_cluster):
    from ray_tpu.experimental.state import api as state

    @ray_tpu.remote
    def child(x):
        return x + 1

    @ray_tpu.remote
    def parent(x):
        return ray_tpu.get(child.remote(x)) + 1

    # untraced: no trace_ctx key anywhere
    assert ray_tpu.get(parent.remote(0), timeout=60) == 2
    with tracing.trace("req") as ctx:
        assert ray_tpu.get(parent.remote(1), timeout=60) == 3
    tid = ctx["trace_id"]
    deadline = time.time() + 15
    while time.time() < deadline:
        rows = [t for t in state.list_tasks(limit=10_000)
                if (t.get("trace_ctx") or {}).get("trace_id") == tid]
        if len(rows) >= 2 and all(t.get("exec_end") for t in rows):
            break
        time.sleep(0.2)
    by_name = {t["name"]: t for t in rows}
    assert set(by_name) == {"parent", "child"}
    # the nested submission chains: child's parent span IS parent's span
    assert (by_name["child"]["trace_ctx"]["parent_span_id"]
            == by_name["parent"]["trace_ctx"]["span_id"])
    assert by_name["parent"]["trace_ctx"]["parent_span_id"] == ctx["span_id"]
    # untraced rows stay clean (presence of a context IS the switch)
    untraced = [t for t in state.list_tasks(limit=10_000)
                if t["name"] == "parent" and not t.get("trace_ctx")]
    assert untraced


def test_actor_calls_continue_trace(trace_cluster):
    from ray_tpu.experimental.state import api as state

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

    c = Counter.remote()
    assert ray_tpu.get(c.bump.remote(), timeout=60) == 1
    with tracing.trace("actor-req") as ctx:
        assert ray_tpu.get(c.bump.remote(), timeout=60) == 2
    rows = [t for t in state.list_tasks(limit=10_000)
            if (t.get("trace_ctx") or {}).get("trace_id") == ctx["trace_id"]]
    assert any(t["name"] == "Counter.bump" for t in rows)


def test_get_trace_assembles_task_and_span_tree(trace_cluster):
    from ray_tpu.experimental.state import api as state

    @ray_tpu.remote
    def work(x):
        time.sleep(0.05)
        return x

    with tracing.trace("assembled") as ctx:
        ray_tpu.get([work.remote(i) for i in range(3)], timeout=60)
    tid = ctx["trace_id"]
    tr = _get_trace_until(
        tid, lambda t: sum(s["phase"] == "execution"
                           for s in t["spans"]) >= 3)
    phases = {s["phase"] for s in tr["spans"]}
    assert {"span", "task", "scheduler_queue", "execution",
            "get_wait"} <= phases
    # root span + queue/exec sub-spans parented under their task spans
    by_id = {s["span_id"]: s for s in tr["spans"]}
    execs = [s for s in tr["spans"] if s["phase"] == "execution"]
    for s in execs:
        parent = by_id[s["parent_span_id"]]
        assert parent["phase"] == "task"
    # list/summarize surfaces
    summaries = state.list_traces(limit=100)
    assert any(r["trace_id"] == tid for r in summaries)
    assert state.summarize_traces()["num_traces"] >= 1
    assert state.get_trace("no-such-trace") is None
    # the analysis is consistent: phases sum to wall
    from ray_tpu.util.trace_analysis import analyze

    a = analyze(tr)
    assert a["wall_s"] > 0
    # each phase rounds to 1us in the payload; the identity holds to that
    assert sum(a["phases"].values()) == pytest.approx(a["wall_s"], abs=1e-4)


def test_compiled_graph_joins_submitting_trace(trace_cluster):
    from ray_tpu.dag import InputNode
    from ray_tpu.experimental.state import api as state

    @ray_tpu.remote
    class Stage:
        def fwd(self, x):
            return x + 1

    with InputNode() as inp:
        dag = Stage.bind().fwd.bind(Stage.bind().fwd.bind(inp))
    cg = dag.experimental_compile(max_inflight=4)
    try:
        assert cg.execute(0).get(timeout=60) == 2  # untraced warm
        with tracing.trace("cdag-req") as ctx:
            assert cg.execute(10).get(timeout=60) == 12
        # untraced executions still work after a traced one (payloads
        # revert to bare values)
        assert cg.execute(5).get(timeout=60) == 7
        tid = ctx["trace_id"]
        tr = _get_trace_until(
            tid, lambda t: sum(s["phase"] == "node_exec"
                               for s in t["spans"]) >= 2)
        nodes = [s for s in tr["spans"] if s["phase"] == "node_exec"]
        assert {s["name"] for s in nodes} == {"fwd:0", "fwd:1"}
        assert all(s["source"] == "compiled_dag" for s in nodes)
        # the stages chain: fwd:1's span parents to fwd:0's
        by_id = {s["span_id"]: s for s in tr["spans"]}
        fwd1 = next(s for s in nodes if s["name"] == "fwd:1")
        assert by_id[fwd1["parent_span_id"]]["name"] == "fwd:0"
        # driver-side submit span present
        assert any(s["phase"] == "submit" for s in tr["spans"])
    finally:
        cg.teardown()


def test_serve_request_trace_spans_router_replica_and_graph(trace_cluster):
    """Acceptance: a serve request through prefill_decode_graph yields ONE
    trace spanning router admission -> replica execution -> compiled-graph
    node executions with channel-wait attribution."""
    from ray_tpu import serve
    from ray_tpu.experimental.state import api as state

    serve.start(_http=False)

    @serve.deployment
    class Gen:
        def __init__(self):
            from ray_tpu.serve.llm import prefill_decode_graph

            self.graph = prefill_decode_graph(
                "gpt2", "tiny", max_new_tokens=4, prefill_bucket=16)
            self.graph.execute([1, 2]).get(timeout=120)  # warm/compile

        def __call__(self, tokens):
            return self.graph.execute(list(tokens)).get(timeout=120)

        def shutdown(self):
            self.graph.teardown()

    handle = serve.run(Gen.bind(), _blocking=True, timeout_s=300)
    try:
        with tracing.trace("serve-req") as ctx:
            out = ray_tpu.get(handle.remote([3, 5, 7]), timeout=120)
        assert isinstance(out, list) and len(out) == 4
        tid = ctx["trace_id"]
        tr = _get_trace_until(
            tid,
            lambda t: {"router_admission", "execution"}
            <= {s["phase"] for s in t["spans"]}
            and sum(s["phase"] == "node_exec" for s in t["spans"]) >= 2)
        phases = {s["phase"] for s in tr["spans"]}
        assert "router_admission" in phases      # router
        assert "execution" in phases             # replica task exec
        names = {s["name"] for s in tr["spans"]}
        assert "ServeReplica.handle_request" in names
        nodes = {s["name"] for s in tr["spans"] if s["phase"] == "node_exec"}
        assert {"prefill:0", "decode:1"} <= nodes
        # channel-wait attribution: decode waited on prefill's output
        # inside THIS request's window (clamped to it)
        waits = [s for s in tr["spans"] if s["phase"] == "channel_wait"]
        t0 = min(s["start"] for s in tr["spans"])
        assert all(s["start"] >= t0 - 0.5 for s in waits)
        from ray_tpu.util.trace_analysis import analyze

        a = analyze(tr)
        assert a["critical_path"], a
    finally:
        serve.delete("Gen")
        serve.shutdown()


def test_doctor_flags_induced_stall_and_gang_restart(trace_cluster):
    """Induced pathologies reach the doctor through the real event
    pipeline: a budget-1 streaming pump stalled by a slow consumer, and a
    gang-restart event emitted from a worker."""
    import numpy as np

    from ray_tpu import data as rd
    from ray_tpu.experimental.state import api as state
    from ray_tpu.util.doctor import diagnose, run_doctor

    # NO healthy-run precondition here: the driver's event ring is
    # process-global, so under the full suite earlier modules' deliberate
    # OOM/chaos events are still visible to list_events.  The
    # healthy-run-is-clean gate lives in test_doctor_healthy_run_is_clean
    # (pure rules) and in the bench harness (own subprocess).

    # budget 1 + a consumer sleeping per block: the pump stalls for well
    # over the rule threshold, and the 1/s-throttled stall events have
    # time to report a cumulative total past it
    os.environ["RAY_TPU_STREAMING_BLOCK_BUDGET"] = "1"
    try:
        blocks = 24
        ds = rd.from_numpy(np.arange(blocks << 11, dtype=np.int64),
                           parallelism=blocks)
        ds = ds.map_batches(lambda b: np.asarray(b) * 2)
        n = 0
        for batch in ds.iter_batches(batch_size=1 << 11):
            time.sleep(0.08)  # slow consumer: the pump stalls on budget 1
            n += len(batch)
        assert n == blocks << 11
    finally:
        os.environ.pop("RAY_TPU_STREAMING_BLOCK_BUDGET", None)

    @ray_tpu.remote
    def restart_gang():
        from ray_tpu._private import events

        events.emit("train", "gang restarted", severity="WARNING",
                    restarts=2, world_size=4)
        return 1

    assert ray_tpu.get(restart_gang.remote(), timeout=60) == 1

    def _mine_shipped():
        # MY induced event (marked world_size=4) made it worker ring ->
        # ship -> head table; earlier suites' train events could satisfy
        # the rule alone, so wait for the marked row specifically
        return any(
            r.get("message") == "gang restarted"
            and (r.get("data") or {}).get("world_size") == 4
            for r in state.list_events(limit=10_000, source="train"))

    deadline = time.time() + 20
    rules = set()
    while time.time() < deadline:
        findings = run_doctor()
        rules = {f["rule"] for f in findings}
        if {"backpressure_stall", "gang_restart"} <= rules \
                and _mine_shipped():
            break
        time.sleep(0.3)
    assert {"backpressure_stall", "gang_restart"} <= rules, rules
    assert _mine_shipped()
    # evidence rows ride along for the operator
    by_rule = {f["rule"]: f for f in findings}
    assert by_rule["gang_restart"]["evidence"]
    assert by_rule["backpressure_stall"]["count"] >= 1


def test_summarize_state_head_side(trace_cluster):
    from ray_tpu.experimental.state import api as state

    @ray_tpu.remote
    def tick():
        return 1

    ray_tpu.get([tick.remote() for _ in range(4)], timeout=60)
    # a result can reach the driver before the head has marked its task
    # FINISHED (seen once under a loaded tier-1 run: 3 of 4)
    deadline = time.time() + 10
    while True:
        tasks = state.summarize_state("tasks")
        if tasks["tick"].get("FINISHED", 0) >= 4 or time.time() > deadline:
            break
        time.sleep(0.05)
    assert tasks["tick"]["FINISHED"] >= 4
    assert state.summarize_tasks() == tasks
    ev = state.summarize_events()
    assert "scheduler" in ev
    assert isinstance(state.summarize_actors(), dict)
    with pytest.raises(ValueError):
        state.summarize_state("nonsense")


def test_profile_collapsed_format(trace_cluster):
    from ray_tpu._private.sampling_profiler import (
        SamplingProfiler,
        collapsed_from_report,
    )

    p = SamplingProfiler(period_s=0.001)
    p.samples["a.py:f|b.py:g"] = 7
    p.samples["a.py:f"] = 3
    folded = p.report_collapsed()
    assert "a.py:f;b.py:g 7" in folded.splitlines()
    assert collapsed_from_report(p.report()) == folded
    # dashboard endpoint serves it as plain text
    from ray_tpu._private.worker import global_worker

    host, port = global_worker.node.dashboard.address
    url = (f"http://{host}:{port}/api/profile"
           f"?duration=0.3&format=collapsed")
    with urllib.request.urlopen(url, timeout=60) as r:
        body = r.read().decode()
        assert "json" not in r.headers.get("Content-Type", "")
    for line in body.splitlines():
        stack, _, count = line.rpartition(" ")
        assert stack and count.isdigit()
        assert "|" not in stack


def test_timeline_merges_trace_flow_arrows():
    from ray_tpu.util.timeline import merged_timeline

    rows = [
        {"ts": 10.0, "source": "trace", "severity": "DEBUG",
         "message": "root", "span_dur": 2.0, "entity_id": "t1",
         "origin": "head",
         "data": {"trace_id": "t1", "span_id": "a",
                  "parent_span_id": "", "phase": "http"}},
        {"ts": 9.9, "source": "trace", "severity": "DEBUG",
         "message": "admission", "span_dur": 0.5, "entity_id": "t1",
         "origin": "head",
         "data": {"trace_id": "t1", "span_id": "b",
                  "parent_span_id": "a", "phase": "router_admission"}},
    ]
    events = merged_timeline([], rows)
    json.loads(json.dumps(events))
    # per-trace row: trace spans keyed by trace_id, not origin
    slices = [e for e in events if e.get("cat") == "trace" and e["ph"] == "X"]
    assert slices and all(e["tid"] == "t1" for e in slices)
    flows = [e for e in events if e.get("cat") == "trace_flow"]
    assert {e["ph"] for e in flows} == {"s", "f"}
    s = next(e for e in flows if e["ph"] == "s")
    f = next(e for e in flows if e["ph"] == "f")
    assert s["id"] == f["id"] == "b"
    assert f["ts"] >= s["ts"]  # arrow never points backwards
