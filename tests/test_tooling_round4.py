"""Round-4 cluster tooling: YAML cluster launcher (``ray up/down``
analog), remote experiment storage sync, dashboard on-demand profiling,
and multi-node chaos (agent SIGKILL under load)."""

import json
import os
import subprocess
import tempfile
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu


# ---------------------------------------------------------------------------
# cluster launcher
# ---------------------------------------------------------------------------


def test_cluster_up_down_local_provider(tmp_path):
    """`ray_tpu up` from a YAML with the local provider: a real head
    process + a real worker agent, then `down` reaps both.  Judged by what
    `up` returned, never by /tmp/ray_tpu/last_session.json: every head on
    the box (each xdist worker's tests) overwrites that record."""
    from ray_tpu.autoscaler.commands import down, load_cluster_config, up

    cfg_path = tmp_path / "cluster.yaml"
    cfg_path.write_text(
        "cluster_name: lt\n"
        "provider: {type: local}\n"
        "head_node: {address: 127.0.0.1, num_cpus: 2, num_tpus: 0}\n"
        "worker_nodes:\n"
        "  - {address: 127.0.0.1, num_cpus: 1, num_tpus: 0}\n"
    )
    config = load_cluster_config(str(cfg_path))
    out = up(config)
    try:
        assert out["address"].startswith("tcp://")
        assert len(out["workers"]) == 1
        # join the launched cluster as a driver and see BOTH nodes
        ray_tpu.init(address=out["address"],
                     _authkey=bytes.fromhex(out["authkey"]))
        deadline = time.time() + 60
        while time.time() < deadline:
            if len(ray_tpu.nodes()) >= 2:
                break
            time.sleep(0.5)
        assert len(ray_tpu.nodes()) >= 2, ray_tpu.nodes()

        @ray_tpu.remote
        def ping():
            return "up"

        assert ray_tpu.get(ping.remote(), timeout=120) == "up"
        ray_tpu.shutdown()
    finally:
        down(config)
    # the head process is gone (or a zombie — this container's pid 1 does
    # not reap orphans, and a zombie still answers os.kill(pid, 0))
    time.sleep(1.5)
    pid = out["head_pid"]
    try:
        with open(f"/proc/{pid}/stat") as f:
            state = f.read().rsplit(")", 1)[-1].split()[0]
        assert state == "Z", f"head pid {pid} still running (state {state})"
    except FileNotFoundError:
        pass  # fully reaped


def test_ssh_runner_command_shape():
    """SSHCommandRunner builds a correct ssh argv (no ssh daemon here —
    verified against /bin/echo as the transport)."""
    from ray_tpu.autoscaler.commands import SSHCommandRunner

    r = SSHCommandRunner(ssh_user="alice", ssh_private_key="/k.pem")
    captured = {}

    def fake_run(argv, **kw):
        captured["argv"] = argv

        class P:
            returncode = 0
            stdout = "ok"
            stderr = ""

        return P()

    import ray_tpu.autoscaler.commands as cmds

    orig = cmds.subprocess.run
    cmds.subprocess.run = fake_run
    try:
        r.run("10.0.0.5", "echo hi")
    finally:
        cmds.subprocess.run = orig
    argv = captured["argv"]
    assert argv[0] == "ssh" and "alice@10.0.0.5" in argv
    assert "-i" in argv and "/k.pem" in argv
    assert argv[-1] == "echo hi"


# ---------------------------------------------------------------------------
# remote experiment storage
# ---------------------------------------------------------------------------


def test_tune_syncs_experiment_to_storage_uri(ray_start_regular, tmp_path):
    from ray_tpu import tune
    from ray_tpu.air import RunConfig, remote_storage

    root = str(tmp_path / "cloud")
    remote_storage.register_filesystem(
        "mock", remote_storage.DirBackedFilesystem(root))

    def trainable(config):
        from ray_tpu.air import session

        session.report({"score": config["x"] * 2, "done": True})

    tuner = tune.Tuner(
        trainable,
        param_space={"x": tune.grid_search([1, 2])},
        run_config=RunConfig(storage_path="mock://bucket/exps", name="e1"),
    )
    results = tuner.fit()
    assert len(results) == 2
    synced = os.path.join(root, "bucket", "exps", "e1")
    assert os.path.isfile(os.path.join(synced, "experiment_state.pkl"))

    state_file = os.path.join(synced, "experiment_state.pkl")
    mtime = os.path.getmtime(state_file)
    time.sleep(0.05)
    restored = tune.Tuner.restore("mock://bucket/exps/e1", trainable)
    grid = restored.fit()  # all trials terminal: returns immediately
    assert sorted(r.metrics["score"] for r in grid) == [2, 4]
    # a resumed run keeps syncing to the ORIGINAL URI (not just locally)
    assert os.path.getmtime(state_file) > mtime


def test_unknown_storage_scheme_is_actionable():
    from ray_tpu.air import remote_storage

    with pytest.raises(ValueError, match="register_filesystem"):
        remote_storage.upload_dir("/tmp", "s3://bucket/x")


# ---------------------------------------------------------------------------
# dashboard on-demand profiling
# ---------------------------------------------------------------------------


def test_dashboard_profile_head_and_worker(ray_start_regular):
    node = ray_tpu._private.worker.global_worker.node
    host, port = node.dashboard.address

    def get(path):
        with urllib.request.urlopen(f"http://{host}:{port}{path}", timeout=120) as r:
            return json.loads(r.read())

    head = get("/api/profile?duration=1")
    assert head["target"] == "head"
    assert head["report"] and all("stack" in row for row in head["report"])

    # keep a worker busy so its profile shows the executing frame
    @ray_tpu.remote
    def spin():
        t0 = time.time()
        while time.time() - t0 < 6:
            sum(i * i for i in range(1000))
        return "done"

    ref = spin.remote()
    time.sleep(1.5)
    workers = [w for w in get("/api/workers?limit=100")
               if w["state"] == "busy" and not w["is_actor_worker"]]
    assert workers, "no busy worker to profile"
    prof = get(f"/api/profile?duration=2&worker_id={workers[0]['worker_id']}")
    assert prof.get("report"), prof
    joined = " ".join(row["stack"] for row in prof["report"])
    assert "spin" in joined or "_execute_task" in joined, joined[:500]
    assert ray_tpu.get(ref, timeout=120) == "done"


# ---------------------------------------------------------------------------
# multi-node chaos: a whole NODE dies under load (agent SIGKILL)
# ---------------------------------------------------------------------------


def test_tasks_survive_node_agent_kill(tmp_path):
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(
        initialize_head=True,
        head_node_args={"num_cpus": 2, "num_tpus": 0},
        real_processes=True,
    )
    try:
        node_b = cluster.add_node(num_cpus=2)

        @ray_tpu.remote(num_cpus=1, max_retries=6)
        def slow(i):
            time.sleep(0.4)
            return i * 3

        refs = [slow.remote(i) for i in range(16)]
        time.sleep(1.2)  # let tasks spread onto node B
        proc = cluster.agents[node_b]
        proc.kill()  # SIGKILL the whole remote node mid-load
        out = ray_tpu.get(refs, timeout=240)
        assert out == [i * 3 for i in range(16)]
        # the dead node was detected and removed from membership
        deadline = time.time() + 60
        while time.time() < deadline:
            alive = [n for n in ray_tpu.nodes() if n["Alive"]]
            if len(alive) == 1:
                break
            time.sleep(0.5)
        assert len([n for n in ray_tpu.nodes() if n["Alive"]]) == 1
    finally:
        cluster.shutdown()


def test_dashboard_web_ui_serves(ray_start_regular):
    """The single-page UI (the TS-frontend seat) renders with live tables."""
    node = ray_tpu._private.worker.global_worker.node
    host, port = node.dashboard.address
    with urllib.request.urlopen(f"http://{host}:{port}/", timeout=60) as r:
        html = r.read().decode()
    assert "<table" in html and "auto-refresh" in html
    for tab in ("nodes", "actors", "tasks", "workers"):
        assert f'"{tab}"' in html  # tab registry present


def test_dashboard_ui_escapes_interpolations(ray_start_regular):
    """Server-fed strings (log stream names, row ids, cell payloads) must
    never reach innerHTML/onclick unescaped: a job_id containing a quote
    or angle bracket would otherwise inject markup into the UI."""
    node = ray_tpu._private.worker.global_worker.node
    host, port = node.dashboard.address
    with urllib.request.urlopen(f"http://{host}:{port}/", timeout=60) as r:
        html = r.read().decode()
    # the escaping helper exists and guards the row-id attribute and cells
    assert "function esc(" in html
    assert 'data-id="${esc(id)}"' in html
    assert "${esc(cell(r[c]))}" in html
    # log stream buttons are built via createElement/textContent, not an
    # onclick string a stream name could break out of
    assert "showLog('${s.stream}')" not in html
    assert "b.onclick=()=>showLog(s.stream)" in html
    # path segments are URI-encoded before interpolation into fetch URLs
    assert "encodeURIComponent(stream)" in html
    assert "encodeURIComponent(id)" in html


# -----------------------------------------------------------------------
# round 5 dashboard depth: log viewer, drill-down details, timeline
# (reference dashboard/modules/log + client detail pages + ray timeline)


def test_dashboard_log_viewer(ray_start_regular):
    """Per-worker log files surface as streams; tailing one returns the
    worker's captured stdout."""
    import json as _json
    import time as _time

    @ray_tpu.remote
    def shout(i):
        print(f"dash-log-probe-{i}")
        return i

    assert ray_tpu.get([shout.remote(i) for i in range(2)], timeout=120) \
        == [0, 1]
    _time.sleep(0.5)
    node = ray_tpu._private.worker.global_worker.node
    host, port = node.dashboard.address

    def get(p):
        with urllib.request.urlopen(f"http://{host}:{port}{p}", timeout=60) as r:
            return r.read()

    streams = _json.loads(get("/api/logs"))
    workers = [s for s in streams if s["kind"] == "worker"]
    assert workers, streams
    texts = [get(f"/api/logs/{s['stream']}?tail=200").decode()
             for s in workers]
    assert any("dash-log-probe" in t for t in texts)
    # path traversal is rejected (urllib.error is loaded by
    # urllib.request at module scope)
    with pytest.raises(urllib.error.HTTPError):
        get("/api/logs/..%2f..%2fetc%2fpasswd")


def test_dashboard_drilldown_and_timeline(ray_start_regular):
    import json as _json

    @ray_tpu.remote
    class Probe:
        def hit(self):
            return 1

    a = Probe.remote()
    assert ray_tpu.get(a.hit.remote(), timeout=120) == 1
    node = ray_tpu._private.worker.global_worker.node
    host, port = node.dashboard.address

    def get(p):
        with urllib.request.urlopen(f"http://{host}:{port}{p}", timeout=60) as r:
            return r.read()

    tasks = _json.loads(get("/api/tasks?limit=50"))
    tid = tasks[0]["task_id"]
    detail = _json.loads(get(f"/api/tasks/{tid}"))
    assert detail["task_id"] == tid

    actors = _json.loads(get("/api/actors?limit=10"))
    aid = actors[0]["actor_id"]
    adetail = _json.loads(get(f"/api/actors/{aid}"))
    assert adetail["actor_id"] == aid
    assert "recent_tasks" in adetail

    tl = _json.loads(get("/api/timeline"))
    assert any(e.get("cat") == "task" for e in tl)
    assert all("ts" in e and "name" in e for e in tl)


def test_dashboard_per_node_stats(ray_start_regular):
    """/api/nodes rows carry live host utilization — head-local nodes
    read /proc at query time; remote nodes report via agent pongs
    (reference dashboard-agent reporter metrics)."""
    import json as _json

    node = ray_tpu._private.worker.global_worker.node
    host, port = node.dashboard.address
    with urllib.request.urlopen(f"http://{host}:{port}/api/nodes",
                                timeout=60) as r:
        rows = _json.loads(r.read())
    assert rows
    head_row = next(r for r in rows if r.get("node_id") == "node-head")
    hs = head_row["host_stats"]
    assert hs["cpu_count"] >= 1
    if os.path.exists("/proc/meminfo"):  # host_stats degrades off-Linux
        assert hs["mem_total_mb"] > 0
    assert "resource_utilization" in head_row


def test_remote_node_stats_via_agent_pong(tmp_path):
    """A REAL remote agent's pong carries host stats; they surface on
    the head's /api/nodes row for that node."""
    import json as _json

    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2, "num_tpus": 0},
                      real_processes=True)
    try:
        node_b = cluster.add_node(num_cpus=1)
        node = ray_tpu._private.worker.global_worker.node
        host, port = node.dashboard.address
        deadline = time.time() + 60  # ping period is 2s
        row = None
        while time.time() < deadline:
            with urllib.request.urlopen(
                    f"http://{host}:{port}/api/nodes", timeout=60) as r:
                rows = _json.loads(r.read())
            row = next((r_ for r_ in rows if r_.get("node_id") == node_b), None)
            if row and row.get("host_stats"):
                break
            time.sleep(0.5)
        assert row and row.get("host_stats"), row
        assert row["host_stats"]["mem_total_mb"] > 0
    finally:
        cluster.shutdown()
