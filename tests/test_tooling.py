"""Cluster tooling: state API, dashboard, metrics, jobs, CLI, timeline.

Reference surfaces: state API (``experimental/state/api.py:729-1333``),
dashboard head (``dashboard/head.py:69``), ``ray.util.metrics``, job
submission (``dashboard/modules/job/job_manager.py:431``), ``ray`` CLI
(``python/ray/scripts/scripts.py``), ``ray timeline``
(``_private/state.py:829``).
"""

import json
import os
import sys
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.experimental.state import (
    list_actors,
    list_nodes,
    list_objects,
    list_tasks,
    list_workers,
    summarize_tasks,
)


def _http_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def test_state_api(ray_start_regular):
    @ray_tpu.remote
    def f(x):
        return x + 1

    @ray_tpu.remote
    class A:
        def ping(self):
            return "ok"

    a = A.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "ok"
    ray_tpu.get([f.remote(i) for i in range(3)], timeout=60)

    nodes = list_nodes()
    assert any(n["node_id"] == "node-head" for n in nodes)
    actors = list_actors()
    assert any(x["class_name"] == "A" and x["state"] == "ALIVE" for x in actors)
    # seal (which completes get) slightly precedes task_done bookkeeping
    deadline = time.time() + 10
    while time.time() < deadline:
        tasks = [t for t in list_tasks() if t["name"] == "f"]
        if len(tasks) == 3 and all(t["state"] == "FINISHED" for t in tasks):
            break
        time.sleep(0.1)
    assert len(tasks) == 3
    assert all(t["state"] == "FINISHED" for t in tasks)
    workers = list_workers()
    assert any(w["is_actor_worker"] for w in workers)
    ref = ray_tpu.put(list(range(100)))
    objs = list_objects()
    assert any(o["object_id"] == ref.hex() for o in objs)
    summary = summarize_tasks()
    assert summary["f"]["FINISHED"] == 3


def test_dashboard_endpoints(ray_start_regular):
    from ray_tpu._private.worker import global_worker

    dash = global_worker.node.dashboard
    assert dash is not None
    host, port = dash.address

    @ray_tpu.remote
    def noop():
        return 1

    ray_tpu.get(noop.remote(), timeout=60)

    status = _http_json(f"http://{host}:{port}/api/cluster_status")
    assert status["num_nodes"] >= 1
    assert "CPU" in status["cluster_resources"]["node-head"]
    nodes = _http_json(f"http://{host}:{port}/api/nodes")
    assert nodes[0]["node_id"] == "node-head"
    with urllib.request.urlopen(f"http://{host}:{port}/metrics", timeout=30) as r:
        text = r.read().decode()
    assert "ray_tpu_num_workers" in text and "ray_tpu_tasks" in text


def test_app_metrics_flow_to_head(ray_start_regular):
    from ray_tpu._private.worker import global_worker

    @ray_tpu.remote
    def record():
        from ray_tpu.util.metrics import Counter

        Counter("my_app_events", "test counter").inc(5, tags={"kind": "x"})
        # pusher interval is 5s; push promptly via the worker's client
        from ray_tpu.util import metrics as mm
        global_worker_client = None
        import ray_tpu._private.worker as wmod

        wmod.global_worker.client.send({
            "type": "metrics_report",
            "origin": wmod.global_worker.worker_id.hex(),
            "metrics": mm.registry().snapshot(),
        })
        return 1

    assert ray_tpu.get(record.remote(), timeout=60) == 1
    deadline = time.time() + 10
    while time.time() < deadline:
        snap = global_worker.node.worker_metrics_registry.snapshot()
        if "my_app_events" in snap:
            break
        time.sleep(0.2)
    assert "my_app_events" in snap
    from ray_tpu.util.metrics import prometheus_text

    text = prometheus_text(snap)
    assert 'my_app_events{kind="x"' in text


def test_job_submission(ray_start_regular, tmp_path):
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient

    client = JobSubmissionClient()
    script = tmp_path / "job.py"
    script.write_text(
        "import ray_tpu, os\n"
        "ray_tpu.init(address=os.environ['RAY_TPU_ADDRESS'])\n"
        "@ray_tpu.remote\n"
        "def sq(x):\n"
        "    return x * x\n"
        "print('result:', ray_tpu.get(sq.remote(7), timeout=120))\n"
    )
    job_id = client.submit_job(entrypoint=f"{sys.executable} {script}")
    status = client.wait_until_finish(job_id, timeout=180)
    logs = client.get_job_logs(job_id)
    assert status == JobStatus.SUCCEEDED, logs
    assert "result: 49" in logs
    assert any(j["job_id"] == job_id for j in client.list_jobs())


def test_job_stop(ray_start_regular):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(entrypoint=f"{sys.executable} -c 'import time; time.sleep(600)'")
    time.sleep(0.5)
    assert client.stop_job(job_id)
    assert client.wait_until_finish(job_id, timeout=30) == "STOPPED"


def test_timeline_dump(ray_start_regular, tmp_path):
    @ray_tpu.remote
    def work():
        time.sleep(0.05)
        return 1

    ray_tpu.get([work.remote() for _ in range(3)], timeout=60)
    from ray_tpu.util.timeline import timeline_dump

    path = timeline_dump(str(tmp_path / "trace.json"))
    events = json.loads(open(path).read())
    mine = [e for e in events if e["name"] == "work"]
    assert len(mine) == 3
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in mine)


def test_cli_status_and_list(ray_start_regular):
    """The CLI's list path against a live session (in-process)."""
    from ray_tpu.scripts import cli

    sess = cli._session()
    assert sess["address"].startswith("tcp://")

    @ray_tpu.remote
    def g():
        return 1

    ray_tpu.get(g.remote(), timeout=60)
    # list command goes through the already-initialized driver
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        cli.main(["list", "tasks", "--limit", "50"])
    rows = json.loads(buf.getvalue())
    assert any(r["name"] == "g" for r in rows)


def test_autoscaler_scales_up_and_down(ray_start_regular):
    """Unmet demand launches real node_agent workers; idle nodes reap."""
    from ray_tpu._private.worker import global_worker
    from ray_tpu.autoscaler import LocalNodeProvider, Monitor, StandardAutoscaler
    from ray_tpu.autoscaler.autoscaler import AutoscalingConfig

    head = global_worker.node
    provider = LocalNodeProvider(head)
    scaler = StandardAutoscaler(
        head, provider,
        AutoscalingConfig(min_workers=0, max_workers=2, idle_timeout_s=3.0,
                          worker_node={"num_cpus": 4}),
    )
    monitor = Monitor(scaler, interval_s=0.5).start()
    try:
        # head has 4 CPUs; each task wants 3, so only one fits at a time —
        # the queued remainder is unmet demand the autoscaler must absorb
        @ray_tpu.remote(num_cpus=3)
        def heavy(i):
            time.sleep(3.0)
            return i

        refs = [heavy.remote(i) for i in range(4)]  # 12 CPUs of demand
        deadline = time.time() + 60
        while not provider.non_terminated_nodes() and time.time() < deadline:
            time.sleep(0.2)
        assert provider.non_terminated_nodes(), "autoscaler never launched a node"
        assert sorted(ray_tpu.get(refs, timeout=240)) == [0, 1, 2, 3]

        # idle nodes terminate after the timeout
        deadline = time.time() + 60
        while provider.non_terminated_nodes() and time.time() < deadline:
            time.sleep(0.5)
        assert not provider.non_terminated_nodes(), "idle nodes never reaped"
    finally:
        monitor.stop()
        provider.shutdown()


def test_timeline_exec_slices(ray_start_regular, tmp_path):
    """Worker-reported exec windows show up as per-worker-pid slices with a
    separate queued slice (profile-event enrichment)."""
    @ray_tpu.remote
    def tick():
        time.sleep(0.05)
        return 1

    ray_tpu.get([tick.remote() for _ in range(2)], timeout=60)
    deadline = time.time() + 10
    while time.time() < deadline:
        from ray_tpu.util.timeline import timeline_events

        evs = [e for e in timeline_events() if e["name"] == "tick"]
        if len(evs) == 2 and all(isinstance(e["tid"], int) for e in evs):
            break
        time.sleep(0.1)
    assert len(evs) == 2
    assert all(e["dur"] >= 0.04e6 for e in evs)
    queued = [e for e in timeline_events() if e["name"] == "tick (queued)"]
    assert len(queued) == 2


def test_usage_report_written(tmp_path):
    """Opt-out usage stats: a session report lands in the session dir
    (local-only; the reference posts the same schema to a collector)."""
    import ray_tpu
    from ray_tpu._private import usage

    ray_tpu.init(num_cpus=2)
    node = ray_tpu._private.worker.global_worker.node
    session_dir = node.session_dir

    @ray_tpu.remote
    def f():
        return 1

    assert ray_tpu.get(f.remote(), timeout=60) == 1
    usage.record_feature("unit-test-feature")
    ray_tpu.shutdown()

    report = json.load(open(os.path.join(session_dir, "usage_report.json")))
    assert "unit-test-feature" in report["features_used"]
    assert report["counters"]["tasks_total"] >= 1


def test_trace_context_propagates_across_tasks(ray_start_regular):
    """util.tracing: tasks submitted inside trace() carry the context;
    nested submissions in workers chain under the same trace (the
    reference's tracing_helper span-injection analog)."""
    import ray_tpu
    from ray_tpu.util import tracing

    @ray_tpu.remote
    def child():
        from ray_tpu.util import tracing as t

        return t.current_context()

    @ray_tpu.remote
    def parent():
        from ray_tpu.util import tracing as t

        ctx = t.current_context()
        nested = ray_tpu.get(child.remote(), timeout=120)
        return ctx, nested

    with tracing.trace("experiment") as root:
        ref = parent.remote()
    p_ctx, c_ctx = ray_tpu.get(ref, timeout=120)
    assert p_ctx["trace_id"] == root["trace_id"]
    assert p_ctx["parent_span_id"] == root["span_id"]
    # nested task chains under the parent task's span, same trace
    assert c_ctx["trace_id"] == root["trace_id"]
    assert c_ctx["parent_span_id"] == p_ctx["span_id"]

    # untraced tasks carry nothing
    @ray_tpu.remote
    def plain():
        from ray_tpu.util import tracing as t

        return t.current_context()

    assert ray_tpu.get(plain.remote(), timeout=120) is None

    # head recorded the context; the timeline links parent -> child
    from ray_tpu.util.timeline import timeline_events

    events = timeline_events()
    traced = [e for e in events
              if e.get("args", {}).get("trace_id") == root["trace_id"]]
    assert len(traced) >= 2
    flows = [e for e in events if e.get("cat") == "trace" and e["ph"] in ("s", "f")]
    assert any(e["ph"] == "s" for e in flows) and any(e["ph"] == "f" for e in flows)
