"""Flight recorder + deep instrumentation (events, metrics, timeline).

Covers the cluster flight recorder (`_private/events.py`): ring-buffer
boundedness, the worker->head transport (`events_report`, the
``metrics_report`` path), crash dumps, the state/dashboard exposure, the
metrics exposition fixes (cumulative buckets, label escaping, negative
inc, pusher retry), the merged chrome-trace timeline, and the Grafana
dashboard factory.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu._private import events as events_mod
from ray_tpu._private.worker import global_worker


@pytest.fixture
def obs_cluster(monkeypatch):
    """Cluster with a fast event-flush cycle (workers inherit the env)."""
    monkeypatch.setenv("RAY_TPU_EVENTS_FLUSH_S", "0.3")
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# ring buffer + event table (no cluster)
# ---------------------------------------------------------------------------

def test_event_ring_bounded_after_1m_emits():
    """Memory stays O(capacity): a million emits leave exactly
    ``capacity`` rows and the newest survive."""
    buf = events_mod.EventBuffer(capacity=256)
    for i in range(1_000_000):
        buf.emit("bench", "m", "DEBUG")
    assert len(buf) == 256
    assert buf.last_seq() == 1_000_000
    rows = buf.snapshot()
    assert rows[-1]["seq"] == 1_000_000
    assert rows[0]["seq"] == 1_000_000 - 255


def test_event_table_capped_per_source_and_filters():
    table = events_mod.EventTable(capacity_per_source=10)
    rows_a = [{"ts": float(i), "source": "a", "severity": "INFO",
               "message": f"a{i}"} for i in range(30)]
    rows_b = [{"ts": float(i), "source": "b", "severity": "WARNING",
               "message": f"b{i}"} for i in range(5)]
    table.add("w1", rows_a)
    table.add("w2", rows_b)
    assert table.counts() == {"a": 10, "b": 5}  # chatty source capped
    assert [r["message"] for r in table.list(source="a")][-1] == "a29"
    assert all(r["origin"] == "w2" for r in table.list(source="b"))
    assert len(table.list(severity="WARNING")) == 5
    merged = table.list(limit=8)
    assert len(merged) == 8
    assert merged == sorted(merged, key=lambda r: r["ts"])


def test_emit_disabled_is_noop():
    code = ("from ray_tpu._private import events; "
            "events.emit('x', 'y'); print(len(events.local_events()))")
    env = dict(os.environ, RAY_TPU_EVENTS="0")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip().splitlines()[-1] == "0", out.stderr


def test_events_pusher_ships_and_dumps(tmp_path):
    sent = []
    dump = str(tmp_path / "events-test.jsonl")
    pusher = events_mod.EventsPusher(sent.append, origin="t",
                                     interval_s=60.0, dump_path=dump)
    events_mod.emit("pushertest", "one", severity="INFO", k=1)
    pusher.flush()
    assert sent and sent[-1]["type"] == "events_report"
    assert any(r["source"] == "pushertest" for r in sent[-1]["events"])
    rows = events_mod.load_dump(dump)
    assert any(r["source"] == "pushertest" for r in rows)
    # both cursors advanced: nothing new -> nothing shipped or re-dumped
    n, n_rows = len(sent), len(rows)
    pusher.flush()
    assert len(sent) == n
    assert len(events_mod.load_dump(dump)) == n_rows
    # the dump trail is incremental: a second emit appends exactly one row
    events_mod.emit("pushertest", "two", severity="INFO")
    pusher.flush()
    assert len(events_mod.load_dump(dump)) == n_rows + 1
    # emit(**data) takes arbitrary app payloads: a non-JSON-serializable
    # value (numpy scalar) must neither kill the pusher nor corrupt the
    # trail (repr fallback)
    import numpy as np

    events_mod.emit("pushertest", "np", severity="INFO",
                    loss=np.float32(0.5), arr=np.arange(2))
    pusher.flush()
    rows = events_mod.load_dump(dump)
    assert len(rows) == n_rows + 2 and rows[-1]["message"] == "np"


# ---------------------------------------------------------------------------
# metrics exposition + transport fixes
# ---------------------------------------------------------------------------

def test_histogram_cumulative_bucket_rendering():
    from ray_tpu.util.metrics import Histogram, prometheus_text, registry

    h = Histogram("obs_test_hist", "t", boundaries=[0.01, 0.1, 1.0])
    h.observe(0.005)
    h.observe(0.05)
    h.observe(0.05)
    h.observe(50.0)
    snap = {"obs_test_hist": registry().snapshot()["obs_test_hist"]}
    text = prometheus_text(snap)
    assert 'obs_test_hist_bucket{le="0.01"} 1' in text
    assert 'obs_test_hist_bucket{le="0.1"} 3' in text  # cumulative
    assert 'obs_test_hist_bucket{le="1.0"} 3' in text
    assert 'obs_test_hist_bucket{le="+Inf"} 4' in text
    assert "obs_test_hist_count 4" in text
    assert "obs_test_hist_sum 50.105" in text


def test_prometheus_label_escaping():
    from ray_tpu.util.metrics import Counter, prometheus_text, registry

    c = Counter("obs_test_escape", "t", tag_keys=("name",))
    c.inc(1.0, tags={"name": 'a"b\\c\nd'})
    snap = {"obs_test_escape": registry().snapshot()["obs_test_escape"]}
    text = prometheus_text(snap)
    assert 'name="a\\"b\\\\c\\nd"' in text
    # the rendered line stays one line: the raw newline must not survive
    line = [l for l in text.splitlines() if l.startswith("obs_test_escape{")]
    assert len(line) == 1 and line[0].endswith(" 1.0")


def test_counter_rejects_negative():
    from ray_tpu.util.metrics import Counter

    c = Counter("obs_test_negative", "t")
    with pytest.raises(ValueError):
        c.inc(-1.0)


def test_metrics_pusher_retries_after_send_failure():
    from ray_tpu.util.metrics import Counter, MetricsPusher

    Counter("obs_test_pusher", "t").inc()
    calls = {"n": 0}
    delivered = []

    def flaky_send(msg):
        calls["n"] += 1
        if calls["n"] <= 2:
            raise OSError("transient")
        delivered.append(msg)

    pusher = MetricsPusher(flaky_send, origin="t", interval_s=0.05).start()
    deadline = time.time() + 10
    while not delivered and time.time() < deadline:
        time.sleep(0.05)
    pusher.stop()
    assert delivered, "pusher died on the first failed send"
    assert delivered[0]["type"] == "metrics_report"
    assert "obs_test_pusher" in delivered[0]["metrics"]


def test_metrics_pusher_stops_when_client_closed():
    from ray_tpu.util.metrics import Counter, MetricsPusher

    Counter("obs_test_closed", "t").inc()
    closed = {"v": False}
    sent = []
    pusher = MetricsPusher(sent.append, origin="t", interval_s=0.05,
                           closed_fn=lambda: closed["v"]).start()
    deadline = time.time() + 10
    while not sent and time.time() < deadline:
        time.sleep(0.05)
    assert sent
    closed["v"] = True
    time.sleep(0.3)
    assert not pusher._thread.is_alive()


# ---------------------------------------------------------------------------
# timeline + grafana (pure functions)
# ---------------------------------------------------------------------------

def test_timeline_merges_recorder_spans_and_metadata():
    from ray_tpu.util.timeline import merged_timeline

    tasks = [{"task_id": "ab", "name": "tick", "state": "FINISHED",
              "node_id": "node-head", "worker_pid": 123,
              "start_time": 100.0, "end_time": 101.0,
              "exec_start": 100.2, "exec_end": 100.9}]
    recorder = [
        {"ts": 100.5, "source": "streaming", "severity": "DEBUG",
         "message": "map", "span_dur": 0.25, "origin": "head"},
        {"ts": 100.7, "source": "scheduler", "severity": "WARNING",
         "message": "OOM kill", "entity_id": "w1", "data": {"x": 1}},
    ]
    events = merged_timeline(tasks, recorder)
    json.loads(json.dumps(events))  # chrome-trace JSON must round-trip
    spans = [e for e in events if e.get("cat") == "streaming"]
    assert len(spans) == 1 and spans[0]["ph"] == "X"
    assert spans[0]["ts"] == pytest.approx((100.5 - 0.25) * 1e6)
    assert spans[0]["dur"] == pytest.approx(0.25 * 1e6)
    instants = [e for e in events if e.get("ph") == "i"]
    assert instants and instants[0]["args"]["x"] == 1
    # M metadata labels every pid/tid row (perfetto names)
    meta = [e for e in events if e.get("ph") == "M"]
    names = {(e["name"], e["args"]["name"]) for e in meta}
    assert ("process_name", "node node-head") in names
    assert ("thread_name", "worker pid 123") in names
    assert ("process_name", "flight recorder · streaming") in names
    # task flow/exec slices are still intact next to the recorder rows
    assert any(e.get("cat") == "task" for e in events)
    assert any(e.get("cat") == "queue" for e in events)


def test_grafana_dashboard_factory():
    from ray_tpu.dashboard.grafana_dashboard_factory import (
        generate_grafana_dashboard,
    )

    snap = {
        "my_counter_total": {"type": "counter", "help": "c", "values": {}},
        "my_hist_s": {"type": "histogram", "help": "h", "values": {}},
        "my_gauge": {"type": "gauge", "help": "g", "values": {}},
    }
    dash = generate_grafana_dashboard(snap)
    json.loads(json.dumps(dash))
    panels = {p["description"].split(" ")[0]: p for p in dash["panels"]}
    assert "my_counter_total" in panels and "my_hist_s" in panels
    assert "rate(my_counter_total[5m])" in panels["my_counter_total"]["targets"][0]["expr"]
    exprs = [t["expr"] for t in panels["my_hist_s"]["targets"]]
    assert any("histogram_quantile(0.99" in e and "my_hist_s_bucket" in e
               for e in exprs)
    assert panels["my_gauge"]["targets"][0]["expr"] == "my_gauge"
    # core cluster metrics are always charted, registry state aside
    assert any("ray_tpu_sched_queue_depth" in p["description"]
               for p in dash["panels"])


# ---------------------------------------------------------------------------
# cluster end-to-end
# ---------------------------------------------------------------------------

def test_events_flow_end_to_end(obs_cluster):
    """Workload -> structured events from the scheduler, object store,
    streaming executor, and a worker-side emitter, all on one table."""
    import numpy as np

    from ray_tpu import data as rd

    @ray_tpu.remote
    def emit_from_worker(x):
        from ray_tpu._private import events

        events.emit("workertest", "hello", severity="INFO", x=x)
        return x

    assert ray_tpu.get([emit_from_worker.remote(i) for i in range(4)]) \
        == list(range(4))
    # streaming executor events (stalls/spans/starvation) + a >1MiB put
    # for the object_store source
    ray_tpu.put(np.zeros(1 << 19))  # 4 MiB of float64
    ds = rd.from_numpy(np.arange(65536, dtype=np.int64), parallelism=4)
    ds = ds.map_batches(lambda b: np.asarray(b) * 2)
    n = 0
    for batch in ds.iter_batches(batch_size=8192):
        n += len(batch)
    assert n == 65536

    from ray_tpu.experimental.state import api as state

    deadline = time.time() + 15
    sources = set()
    while time.time() < deadline:
        sources = {e["source"] for e in state.list_events(limit=10_000)}
        if {"scheduler", "object_store", "streaming", "workertest"} <= sources:
            break
        time.sleep(0.3)
    assert {"scheduler", "object_store", "streaming", "workertest"} <= sources
    # worker-shipped rows carry their origin; filters work
    rows = state.list_events(source="workertest")
    assert rows and all(r["origin"] != "head" for r in rows)
    assert state.list_events(source="workertest", severity="ERROR") == []
    assert "scheduler" in state.summarize_events()
    # filters apply HEAD-SIDE, before the limit: a single rare row stays
    # findable behind any number of newer chatty rows
    events_mod.emit("raretest", "needle", severity="WARNING")
    for _ in range(50):
        events_mod.emit("chattytest", "hay", severity="DEBUG")
    rare = state.list_events(limit=10, source="raretest")
    assert [r["message"] for r in rare] == ["needle"]


def test_llm_engine_emits_slot_admission_events():
    """The continuous-batching engine's slot admissions and completions
    land in the flight recorder (no cluster needed — the engine runs
    in-process): an ``engine.queue`` stage a request (submit -> its
    prefill's dispatch), a ``request complete`` span a request."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2
    from ray_tpu.serve.llm import GenerationEngine
    from ray_tpu.util import metrics as mm

    def admitted():
        vals = mm.registry().snapshot().get(
            "ray_tpu_llm_slot_admission_latency_s", {}).get("values", {})
        return sum(h["count"] for h in vals.values())

    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    params = gpt2.init(cfg, jax.random.PRNGKey(0))
    before = events_mod.buffer().last_seq()
    had = admitted()
    eng = GenerationEngine(cfg, params, n_slots=2, max_new_tokens=6,
                           decode_chunk_steps=3,
                           prefill_buckets=(8, 16)).start()
    try:
        futs = [eng.submit([3, 17, 5], 6), eng.submit([9, 2], 6),
                eng.submit([6], 6)]
        for f in futs:
            f.result(timeout=120)
    finally:
        eng.stop()
    rows = [r for r in events_mod.local_events() if r["seq"] > before]
    queued = [r for r in rows if r["source"] == "trace"
              and r["data"]["phase"] == "engine.queue"]
    assert len(queued) == 3 and all(r["span_dur"] >= 0 for r in queued)
    # three prompts on two slots: the last waited for a slot
    assert max(r["span_dur"] for r in queued) > min(
        r["span_dur"] for r in queued)
    done = [r for r in rows if r["source"] == "serve_llm"
            and r["message"] == "request complete"]
    assert len(done) == 3
    assert all(r["span_dur"] > 0 for r in done)
    # each engine.queue stage is a child of its request's span
    assert {r["data"]["parent_span_id"] for r in queued} == {
        r["data"]["span_id"] for r in done}
    # admission latency histogram recorded each admitted request
    assert admitted() == had + 3


def test_dashboard_events_metrics_grafana_endpoints(obs_cluster):
    @ray_tpu.remote
    def tick():
        return 1

    ray_tpu.get(tick.remote())
    host, port = global_worker.node.dashboard.address

    def get(path):
        with urllib.request.urlopen(f"http://{host}:{port}{path}",
                                    timeout=30) as r:
            return r.read().decode()

    rows = json.loads(get("/api/events?limit=500"))
    assert isinstance(rows, list)
    assert any(r["source"] == "scheduler" for r in rows)
    dash = json.loads(get("/api/grafana_dashboard"))
    assert dash["panels"]
    metrics = get("/metrics")
    assert "ray_tpu_sched_dispatch_latency_s_bucket" in metrics
    assert "ray_tpu_object_put_latency_s" in metrics
    tl = json.loads(get("/api/timeline"))
    assert any(e.get("ph") == "M" for e in tl)
    assert any(e.get("cat") == "task" for e in tl)


def test_worker_sigkill_leaves_crash_dump(obs_cluster):
    @ray_tpu.remote
    def emit_and_pid():
        from ray_tpu._private import events

        events.emit("crashtest", "about to be killed", severity="WARNING")
        return os.getpid()

    pid = ray_tpu.get(emit_and_pid.remote())
    # one pusher cycle (0.3s flush) writes the dump; then SIGKILL — no
    # atexit, no handler, only the already-flushed file survives
    deadline = time.time() + 10
    logs_dir = os.path.join(global_worker.node.session_dir, "logs")
    found = None
    while time.time() < deadline and found is None:
        for path in glob.glob(os.path.join(logs_dir, "events-worker-*.jsonl")):
            try:
                rows = events_mod.load_dump(path)
            except OSError:
                continue
            if any(r["source"] == "crashtest" for r in rows):
                found = path
        time.sleep(0.2)
    assert found, "no crash dump written before the kill"
    os.kill(pid, signal.SIGKILL)
    time.sleep(0.5)
    rows = events_mod.load_dump(found)  # survives the SIGKILL, still valid
    assert any(r["source"] == "crashtest" for r in rows)


def test_timeline_cli_path_merges_recorder_rows(obs_cluster, tmp_path):
    import numpy as np

    from ray_tpu import data as rd

    @ray_tpu.remote
    def work(x):
        return x * 2

    ray_tpu.get([work.remote(i) for i in range(8)])
    ds = rd.from_numpy(np.arange(4096, dtype=np.int64), parallelism=2)
    for _ in ds.iter_batches(batch_size=1024):
        pass
    from ray_tpu.util.timeline import timeline_dump, timeline_events

    events = timeline_events()
    cats = {e.get("cat") for e in events}
    assert "task" in cats
    assert "streaming" in cats  # operator spans merged with task slices
    assert any(e.get("ph") == "M" for e in events)
    path = timeline_dump(str(tmp_path / "trace.json"))
    with open(path) as f:
        json.load(f)


def test_scheduler_and_store_metrics_recorded(obs_cluster):
    from ray_tpu.util import metrics as mm

    @ray_tpu.remote
    def tick():
        return 1

    ray_tpu.get([tick.remote() for _ in range(5)])
    # >64KiB payloads are never sampled away (small ones observe 1:8)
    ray_tpu.get(ray_tpu.put(b"x" * (128 << 10)))
    snap = mm.registry().snapshot()
    # pipelined follow-ons skip _dispatch, so only a lower bound holds
    disp = snap["ray_tpu_sched_dispatch_latency_s"]["values"]
    assert sum(h["count"] for h in disp.values()) >= 1
    put = snap["ray_tpu_object_put_latency_s"]["values"]
    assert sum(h["count"] for h in put.values()) >= 1
    get_ = snap["ray_tpu_object_get_latency_s"]["values"]
    assert sum(h["count"] for h in get_.values()) >= 1
