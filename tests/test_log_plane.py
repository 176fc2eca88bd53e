"""Cluster log plane: capture (context-stamped redirect), ship
(rotation-safe tailing, rate limiting), store (rings, retirement,
bursts), and the consume surfaces (state API, driver streaming, trace
join, doctor rules).

Reference behaviors: ``python/ray/_private/log_monitor.py`` (rotation-
safe tailing), ``worker.print_to_stdstream`` (driver re-emission with
``(name pid=… node=…)`` prefixes), ``ray logs`` (state API log surface).
"""

import os
import time

import pytest

import ray_tpu
from ray_tpu._private import log_plane
from ray_tpu._private.log_plane import (
    ContextStampingStream,
    LogMonitor,
    _RotatingFile,
    format_stamp,
    parse_line,
)
from ray_tpu.util.log_store import LogStore


@pytest.fixture
def fast_ship(monkeypatch):
    """Boot the runtime with a fast ship cadence so tests wait ~0.2s,
    not the production 1s, for records to reach the head."""
    monkeypatch.setenv("RAY_TPU_LOG_SHIP_S", "0.1")
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


def _wait_for(fn, timeout=20.0, interval=0.1):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = fn()
        if v:
            return v
        time.sleep(interval)
    raise AssertionError(f"condition not met within {timeout}s: {fn}")


# ---------------------------------------------------------------------------
# stamp protocol
# ---------------------------------------------------------------------------

def test_stamp_roundtrip():
    s = format_stamp("o") + "hello world"
    src, job, task, actor, trace, text = parse_line(s)
    assert src == "o" and text == "hello world"

    # unstamped lines (C-level writes) keep the stream's default src
    assert parse_line("plain", "e") == ("e", "", "", "", "", "plain")
    # a corrupt stamp degrades to an unstamped line, never an exception
    assert parse_line("\x1frt1|broken")[5] == "\x1frt1|broken"


def test_stamp_tracks_context_epoch():
    from ray_tpu._private.worker import global_worker as gw

    old_task = gw.current_task_id
    try:
        gw.current_task_id = b"\xab\xcd"
        assert parse_line(format_stamp("o") + "x")[2] == "abcd"
        # the cached stamp must be invalidated by the setter
        gw.current_task_id = b"\x12\x34"
        assert parse_line(format_stamp("o") + "x")[2] == "1234"
        gw.current_task_id = None
        assert parse_line(format_stamp("o") + "x")[2] == ""
    finally:
        gw.current_task_id = old_task


def _stamped_stream(tmp_path, name="out.log", rotate=1 << 30):
    path = str(tmp_path / name)
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND)
    rot = _RotatingFile(path, rotate, fds=(fd,))
    return path, fd, ContextStampingStream(fd, "o", rot)


def test_stamping_stream_print_shapes(tmp_path):
    path, fd, st = _stamped_stream(tmp_path)
    try:
        print("one line", file=st)              # write(text) + write("\n")
        st.write("single call line\n")          # one complete line
        st.write("partial ")                    # three-part line
        st.write("continued")
        st.write(" end\n")
        st.write("a\nb\nc\n")                   # several lines in one call
        st.write("multi with tail\npartial2")   # complete + trailing partial
        st.flush()
    finally:
        os.close(fd)

    lines = open(path).read().splitlines()
    parsed = [parse_line(ln) for ln in lines]
    texts = [p[5] for p in parsed]
    assert texts == ["one line", "single call line", "partial continued end",
                     "a", "b", "c", "multi with tail", "partial2"]
    # every line got exactly one stamp (split lines included)
    assert all(p[0] == "o" for p in parsed)
    assert not any("\x1f" in t for t in texts)


def test_stamping_stream_write_record(tmp_path):
    path, fd, st = _stamped_stream(tmp_path)
    try:
        st.write("partial print ")
        st.write_record("E", "logger error line")
        st.flush()
    finally:
        os.close(fd)
    lines = open(path).read().splitlines()
    # the pending partial was terminated, then the record written with
    # its own level src
    assert parse_line(lines[0])[5] == "partial print "
    assert parse_line(lines[1])[0] == "E"
    assert parse_line(lines[1])[5] == "logger error line"


def test_rotating_file_caps_and_keeps_backup(tmp_path):
    path, fd, st = _stamped_stream(tmp_path, rotate=2000)
    try:
        for i in range(200):
            st.write(f"line number {i:04d} with padding text\n")
    finally:
        os.close(fd)
    assert os.path.exists(path + ".1")
    assert os.path.getsize(path) < 4000  # bounded, not unbounded growth
    # the union of current + backup holds a contiguous recent suffix
    all_lines = open(path + ".1").read() + open(path).read()
    assert "line number 0199" in all_lines


# ---------------------------------------------------------------------------
# LogMonitor: rotation-safe tailing
# ---------------------------------------------------------------------------

def _mk_monitor(shipped):
    return LogMonitor("test-node",
                      ingest_fn=lambda origin, recs, metas: shipped.extend(recs))


def test_monitor_tails_and_parses(tmp_path):
    path = str(tmp_path / "w.log")
    shipped = []
    mon = _mk_monitor(shipped)
    mon.register("w", path, pid=123)
    open(path, "a").write(format_stamp("o") + "hello\nunstamped\n")
    assert mon.poll_once() == 2
    assert shipped[0][log_plane.REC_LINE] == "hello"
    assert shipped[1][log_plane.REC_SRC] == "o"
    # nothing new -> nothing re-shipped
    assert mon.poll_once() == 0


def test_monitor_survives_rotation_without_loss(tmp_path):
    path = str(tmp_path / "w.log")
    shipped = []
    mon = _mk_monitor(shipped)
    mon.register("w", path)

    with open(path, "a") as f:
        for i in range(10):
            f.write(f"pre {i}\n")
    mon.poll_once()
    # rotate under the tailer: old inode renamed, fresh file at path
    with open(path, "a") as f:
        f.write("old tail line\n")
    os.replace(path, path + ".1")
    with open(path, "a") as f:
        for i in range(5):
            f.write(f"post {i}\n")
    mon.poll_once()  # drains old fd fully, detects rotation, reopens
    mon.poll_once()  # reads the new inode from offset 0

    texts = [r[log_plane.REC_LINE] for r in shipped]
    expected = [f"pre {i}" for i in range(10)] + ["old tail line"] + \
        [f"post {i}" for i in range(5)]
    assert texts == expected  # no line lost, none shipped twice


def test_monitor_rotation_terminates_partial_line(tmp_path):
    path = str(tmp_path / "w.log")
    shipped = []
    mon = _mk_monitor(shipped)
    mon.register("w", path)
    with open(path, "a") as f:
        f.write("no newline yet")  # partial at rotation time
    mon.poll_once()
    os.replace(path, path + ".1")
    open(path, "a").write("new file line\n")
    mon.poll_once()
    mon.poll_once()
    texts = [r[log_plane.REC_LINE] for r in shipped]
    # the old file's dangling partial became its final line
    assert texts == ["no newline yet", "new file line"]


def test_monitor_survives_truncation(tmp_path):
    path = str(tmp_path / "w.log")
    shipped = []
    mon = _mk_monitor(shipped)
    mon.register("w", path)
    with open(path, "a") as f:
        f.write("a\nb\n")
    mon.poll_once()
    os.truncate(path, 0)  # copytruncate-style rotation
    mon.poll_once()       # shrink observed: offset resets to 0
    with open(path, "a") as f:
        f.write("after truncate\n")
    mon.poll_once()
    texts = [r[log_plane.REC_LINE] for r in shipped]
    assert texts == ["a", "b", "after truncate"]


def test_monitor_rate_limit_suppression_marker(tmp_path):
    path = str(tmp_path / "w.log")
    shipped = []
    mon = LogMonitor(
        "test-node", rate_lps=5,
        ingest_fn=lambda origin, recs, metas: shipped.extend(recs))
    mon.register("w", path)
    with open(path, "a") as f:
        for i in range(100):
            f.write(f"spam {i}\n")
    t0 = time.time()
    mon.poll_once(now=t0)
    # bucket starts with one second's budget: 5 lines passed, 95 counted
    assert len([r for r in shipped if r[log_plane.REC_SRC] != "m"]) == 5
    # tokens recover after a quiet second -> one marker with the count
    with open(path, "a") as f:
        f.write("after storm\n")
    mon.poll_once(now=t0 + 2.0)
    markers = [r for r in shipped if r[log_plane.REC_SRC] == "m"]
    assert len(markers) == 1
    assert "(suppressed 95 lines)" in markers[0][log_plane.REC_LINE]
    assert shipped[-1][log_plane.REC_LINE] == "after storm"


def test_monitor_unregister_final_drain(tmp_path):
    """The death-tail guarantee: unregister ships everything the file
    gained since the last poll, including a dangling partial line."""
    path = str(tmp_path / "w.log")
    shipped = []
    mon = _mk_monitor(shipped)
    mon.register("w", path)
    mon.poll_once()
    with open(path, "a") as f:
        f.write("last words\nFatal: dying now")  # no trailing newline
    mon.unregister("w")
    texts = [r[log_plane.REC_LINE] for r in shipped]
    assert texts == ["last words", "Fatal: dying now"]
    assert "w" not in mon.streams()


# ---------------------------------------------------------------------------
# LogStore
# ---------------------------------------------------------------------------

def _rec(stream, line, src="o", job="", task="", actor="", trace="", ts=None):
    return (ts if ts is not None else time.time(),
            stream, src, job, task, actor, trace, line)


def test_store_ingest_query_filters():
    store = LogStore(max_lines_per_stream=100, max_total_bytes=1 << 20,
                     max_streams=10)
    store.ingest("node-1", [
        _rec("w1", "alpha", job="j1", task="t1"),
        _rec("w1", "beta error", src="e", job="j1", task="t2"),
        _rec("w2", "gamma", job="j2", trace="tr9"),
    ], metas={"w1": {"pid": 11}, "w2": {"pid": 22}})

    rows, cursor = store.query(task="t1")
    assert [r["line"] for r in rows] == ["alpha"]
    assert cursor == 3
    rows, _ = store.query(errors=True)
    assert [r["line"] for r in rows] == ["beta error"]
    rows, _ = store.query(grep="GAMMA")
    assert rows and rows[0]["stream"] == "w2" and rows[0]["pid"] == 22
    rows, _ = store.query(trace="tr9")
    assert len(rows) == 1
    # cursor-follow: only records past since_seq come back
    store.ingest("node-1", [_rec("w1", "delta", job="j1")])
    rows, c2 = store.query(since_seq=cursor)
    assert [r["line"] for r in rows] == ["delta"] and c2 == 4


def test_store_caps_and_retirement():
    store = LogStore(max_lines_per_stream=5, max_total_bytes=1 << 20,
                     max_streams=10)
    store.ingest("n", [_rec("w", f"line {i}") for i in range(20)])
    rows, _ = store.query(stream="w", limit=100)
    assert len(rows) == 5 and rows[0]["line"] == "line 15"
    meta = store.stats()[0]
    assert meta["total_lines"] == 20  # history count survives the ring cap

    store.retire("w")
    # retired ring stays queryable (the death-tail property)...
    assert store.tail_text("w", n=2) == ["line 18", "line 19"]
    # ...until the horizon passes
    assert store.retire_stale(0.0, now=time.time() + 10) == ["w"]
    assert "w" not in store


def test_store_byte_pressure_sheds_oldest():
    store = LogStore(max_lines_per_stream=10_000, max_total_bytes=3000,
                     max_streams=10)
    store.ingest("n", [_rec("quiet", "x" * 100) for _ in range(20)],
                 now=100.0)
    store.ingest("n", [_rec("busy", "y" * 100) for _ in range(20)],
                 now=200.0)
    # the least-recently-active stream lost records first
    quiet = [r for r in store.stats() if r["stream"] == "quiet"][0]
    busy = [r for r in store.stats() if r["stream"] == "busy"][0]
    assert quiet["lines"] < busy["lines"]


def test_store_error_burst_emits_event():
    events = []
    store = LogStore(max_lines_per_stream=1000, max_total_bytes=1 << 20,
                     max_streams=10, burst_n=5, burst_window_s=30.0,
                     emit_fn=lambda *a, **k: events.append((a, k)))
    now = time.time()
    store.ingest("n", [_rec("w", f"err {i}", src="e", ts=now)
                       for i in range(6)], now=now)
    assert len(events) == 1
    (source, message), kw = events[0]
    assert source == "log" and "error burst" in message
    assert kw["entity_id"] == "w"
    # cooldown: an immediately following burst doesn't double-fire
    store.ingest("n", [_rec("w", f"err2 {i}", src="e", ts=now)
                       for i in range(6)], now=now + 1)
    assert len(events) == 1


# ---------------------------------------------------------------------------
# doctor rules
# ---------------------------------------------------------------------------

def test_doctor_log_rules_fire_and_stay_silent():
    from ray_tpu.util.doctor import diagnose

    assert diagnose([], []) == []  # healthy gate: no events, no findings

    burst = {"source": "log", "severity": "WARNING",
             "message": "error burst: 60 error/traceback lines in 30s "
                        "from worker-ab", "entity_id": "worker-ab"}
    death = {"source": "log", "severity": "ERROR",
             "message": "worker died with uncollected stderr: exited with "
                        "code -9",
             "entity_id": "ab", "data": {"tail": ["Fatal: boom"]}}
    findings = diagnose([burst, death], [])
    rules = {f["rule"]: f for f in findings}
    assert "log_error_burst" in rules
    assert "worker-ab" in rules["log_error_burst"]["summary"]
    assert "worker_stderr_at_death" in rules
    assert rules["worker_stderr_at_death"]["severity"] == "ERROR"
    assert "Fatal: boom" in rules["worker_stderr_at_death"]["summary"]

    # unrelated log-source events (stream retirement) fire neither rule
    quiet = {"source": "log", "severity": "DEBUG",
             "message": "log stream retired", "entity_id": "w"}
    assert diagnose([quiet], []) == []


# ---------------------------------------------------------------------------
# end-to-end: print() -> capture -> ship -> store -> consume surfaces
# ---------------------------------------------------------------------------

def test_worker_print_correlated_end_to_end(fast_ship):
    from ray_tpu.experimental.state import api as state

    @ray_tpu.remote
    def chatty():
        print("needle-from-task")
        return ray_tpu.get_runtime_context().task_id

    task_id = ray_tpu.get(chatty.remote(), timeout=120).hex()

    rows = _wait_for(lambda: state.get_log(grep="needle-from-task")["records"])
    r = rows[0]
    assert r["task"] == task_id      # a plain print() carries the task id
    assert r["stream"].startswith("worker-")
    assert r["src"] == "o"
    # the same record is reachable via the task filter and the stream list
    assert state.get_log(task=task_id)["records"]
    streams = {row["stream"] for row in state.list_logs()}
    assert r["stream"] in streams


def test_actor_stderr_and_logger_records(fast_ship):
    import sys

    from ray_tpu.experimental.state import api as state

    @ray_tpu.remote
    class Talker:
        def speak(self):
            print("to-stderr-needle", file=sys.stderr)
            from ray_tpu._private.logging_utils import get_logger
            get_logger("ray_tpu.testmod").warning("logger-needle")
            return ray_tpu.get_runtime_context().actor_id

    a = Talker.remote()
    actor_id = ray_tpu.get(a.speak.remote(), timeout=120).hex()

    err = _wait_for(
        lambda: state.get_log(grep="to-stderr-needle", errors=True)["records"])
    assert err[0]["actor"] == actor_id
    logged = _wait_for(lambda: state.get_log(grep="logger-needle")["records"])
    assert logged[0]["src"] == "W"   # logger level rode the stamp
    assert state.get_log(actor=actor_id)["records"]


def test_trace_join(fast_ship):
    from ray_tpu.experimental.state import api as state
    from ray_tpu.util import tracing

    @ray_tpu.remote
    def traced_work():
        print("trace-needle-line")
        return 1

    with tracing.trace("log-join-test") as ctx:
        ray_tpu.get(traced_work.remote(), timeout=120)
    trace_id = ctx["trace_id"]

    rows = _wait_for(lambda: state.get_log(trace=trace_id)["records"])
    assert any("trace-needle-line" in r["line"] for r in rows)
    trace = _wait_for(lambda: state.get_trace(trace_id))
    assert any("trace-needle-line" in r["line"]
               for r in trace.get("logs", []))


def test_driver_stream_and_follow_cursor(fast_ship):
    """The driver-side consume path: a job subscriber sees shipped
    records (prefixed re-emission is make_driver_log_callback), and the
    get_log cursor follows incrementally (the --follow loop)."""
    from ray_tpu._private.log_plane import make_driver_log_callback
    from ray_tpu._private.worker import global_worker
    from ray_tpu.experimental.state import api as state

    got = []
    cb = make_driver_log_callback(out_fn=got.append)
    global_worker.client.subscribe(
        f"logs:{global_worker.job_id}", cb)

    @ray_tpu.remote
    def noisy():
        print("driver-stream-needle")

    ray_tpu.get(noisy.remote(), timeout=120)
    _wait_for(lambda: any("driver-stream-needle" in s for s in got))
    line = next(s for s in got if "driver-stream-needle" in s)
    # reference print_to_stdstream prefix shape: "(name pid=…, node=…)"
    assert line.startswith("(worker-") and "pid=" in line and "node=" in line

    cursor = state.get_log(grep="driver-stream-needle")["cursor"]
    ray_tpu.get(noisy.remote(), timeout=120)
    fresh = _wait_for(lambda: state.get_log(
        grep="driver-stream-needle", since_seq=cursor)["records"])
    assert all(r["seq"] > cursor for r in fresh)


def test_sigkill_worker_stderr_retrievable_after_death(fast_ship):
    """Acceptance: a SIGKILL'd worker's last stderr lines are retrievable
    from the head after the process is gone."""
    import sys

    from ray_tpu.experimental.state import api as state

    @ray_tpu.remote(max_retries=0)
    def doomed():
        print("final-stderr-needle before the bullet", file=sys.stderr)
        sys.stderr.flush()
        os.kill(os.getpid(), 9)

    with pytest.raises(Exception):
        ray_tpu.get(doomed.remote(), timeout=120)

    rows = _wait_for(lambda: state.get_log(
        grep="final-stderr-needle", errors=True)["records"])
    stream = rows[0]["stream"]
    # the stream is retired (its worker is dead) but its tail still serves
    # (retirement follows the death by a reaper's beat: wait for it, as for
    # the records above; under a loaded suite the stream was once listed first)
    meta = _wait_for(lambda: [
        s for s in state.list_logs()
        if s["stream"] == stream and s["retired"]])[0]
    assert meta["retired"]
    tail = state.tail_log(stream, n=50, errors=True)
    assert any("final-stderr-needle" in ln for ln in tail)


def test_job_logs_unified_surface(fast_ship):
    """The job driver's log and `ray_tpu logs job-<id>` read the same
    store-backed surface (with on-disk fallback for aged-out rings)."""
    from ray_tpu.experimental.state import api as state
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint="python -c \"print('job-driver-needle')\"")
    status = client.wait_until_finish(job_id, timeout=120)
    assert status == "SUCCEEDED"
    rows = _wait_for(lambda: state.get_log(
        stream=f"job-{job_id}", limit=1000)["records"])
    assert any("job-driver-needle" in r["line"] for r in rows)
    # the legacy job-logs surface reads the same records
    assert "job-driver-needle" in client.get_job_logs(job_id)


def test_cross_node_print_reaches_head_and_driver(monkeypatch, capsys):
    """Acceptance: a plain print() on an emulated remote node (real agent
    process, own shm/session namespace) lands in the head store with that
    node's id and is re-emitted at the driver within a ship interval."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.experimental.state import api as state
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    monkeypatch.setenv("RAY_TPU_LOG_SHIP_S", "0.1")
    cluster = Cluster(
        initialize_head=True,
        head_node_args={"num_cpus": 2, "num_tpus": 0},
        real_processes=True,
    )
    try:
        node_b = cluster.add_node(num_cpus=2)

        @ray_tpu.remote(
            scheduling_strategy=NodeAffinitySchedulingStrategy(node_b))
        class RemoteTalker:
            def speak(self):
                print("cross-node-needle")
                return ray_tpu.get_runtime_context().node_id

        a = RemoteTalker.remote()
        assert ray_tpu.get(a.speak.remote(), timeout=120) == node_b

        rows = _wait_for(
            lambda: state.get_log(grep="cross-node-needle")["records"])
        assert rows[0]["node"] == node_b  # shipped by node B's agent
        assert rows[0]["actor"]          # actor id rode the stamp
        # driver re-emission carries the remote node id in its prefix
        # (readouterr drains, so accumulate across polls)
        chunks = []

        def _saw_line():
            chunks.append(capsys.readouterr().out)
            return [ln for ln in "".join(chunks).splitlines()
                    if "cross-node-needle" in ln and ln.startswith("(")]

        line = _wait_for(_saw_line, timeout=15)[0]
        assert f"node={node_b}" in line
    finally:
        cluster.shutdown()


def test_disabled_plane_keeps_plain_capture(tmp_path, monkeypatch):
    """RAY_TPU_LOG_PLANE=0: the redirect still captures (crash trail) but
    lines are unstamped and no monitor ships them."""
    import subprocess
    import sys

    code = (
        "import os, sys\n"
        "from ray_tpu._private.log_plane import redirect_process_output\n"
        f"redirect_process_output({str(tmp_path / 'cap.log')!r})\n"
        "print('disabled-path line')\n"
        "sys.stdout.flush()\n"
    )
    env = dict(os.environ)
    env["RAY_TPU_LOG_PLANE"] = "0"
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    content = open(tmp_path / "cap.log").read()
    assert "disabled-path line" in content
    assert "\x1f" not in content


def test_cli_logs_command(fast_ship, capsys):
    from ray_tpu.scripts import cli

    @ray_tpu.remote
    def printer():
        print("cli-logs-needle")

    ray_tpu.get(printer.remote(), timeout=120)
    from ray_tpu.experimental.state import api as state

    _wait_for(lambda: state.get_log(grep="cli-logs-needle")["records"])

    cli.main(["logs"])  # stream table
    table = capsys.readouterr().out
    assert "STREAM" in table and "worker-" in table

    cli.main(["logs", "--grep", "cli-logs-needle"])
    out = capsys.readouterr().out
    assert "cli-logs-needle" in out and out.strip().startswith("(worker-")
