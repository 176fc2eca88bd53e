"""Fault-tolerance tests (reference: python/ray/tests/test_chaos.py:66,101 —
task retry under kill, actor retry; NodeKillerActor analog)."""

import time

import pytest

import ray_tpu
from ray_tpu._private.worker import global_worker
from ray_tpu.exceptions import RayActorError, WorkerCrashedError


def test_task_retry_on_worker_crash(ray_start_regular):
    @ray_tpu.remote(max_retries=2)
    def flaky(path):
        # crash the first time, succeed once the marker exists
        import os

        if not os.path.exists(path):
            open(path, "w").close()
            os._exit(1)
        return "recovered"

    marker = f"/tmp/rtpu_flaky_{time.time()}"
    assert ray_tpu.get(flaky.remote(marker), timeout=120) == "recovered"


def test_task_no_retry_exhausted(ray_start_regular):
    @ray_tpu.remote(max_retries=1)
    def always_crash():
        import os

        os._exit(1)

    with pytest.raises(WorkerCrashedError):
        ray_tpu.get(always_crash.remote(), timeout=120)


def test_app_error_not_retried(ray_start_regular):
    """Application exceptions are NOT retried by default (reference semantics:
    max_retries covers system failures; retry_exceptions opts into app errors)."""
    counter_file = f"/tmp/rtpu_count_{time.time()}"

    @ray_tpu.remote(max_retries=3)
    def fails(path):
        with open(path, "a") as f:
            f.write("x")
        raise ValueError("app error")

    with pytest.raises(Exception, match="app error"):
        ray_tpu.get(fails.remote(counter_file), timeout=60)
    assert len(open(counter_file).read()) == 1


def test_node_death_fails_running_tasks(ray_start_cluster):
    cluster = ray_start_cluster
    nid = cluster.add_node(num_cpus=1)
    from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy

    @ray_tpu.remote(max_retries=0)
    def stuck():
        time.sleep(300)

    ref = stuck.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(node_id=nid)
    ).remote()
    time.sleep(8)  # let it get dispatched
    cluster.remove_node(nid)
    with pytest.raises(Exception):
        ray_tpu.get(ref, timeout=60)
    assert _reader_ends_quietly_when_closed_under_it(global_worker.node)


def _reader_ends_quietly_when_closed_under_it(node):
    """The head's reader of a connection that is closed under it (a node
    declared dead, a shutdown) while a frame is on its way: the frame's
    header is read from the descriptor the blocked read holds, its body from
    a handle that is gone.  The loop ends as on EOF: no exception leaves its
    thread."""
    import multiprocessing
    import threading

    from ray_tpu._private import wire

    ours, theirs = multiprocessing.Pipe()
    conn, raised = wire.wrap(ours), []
    hook, threading.excepthook = threading.excepthook, raised.append
    try:
        reader = threading.Thread(target=node._reader_loop, args=(conn,))
        reader.start()
        time.sleep(0.5)  # blocked in recv
        conn.close()
        theirs.send_bytes(b"x" * 64)
        reader.join(timeout=30)
    finally:
        threading.excepthook = hook
        theirs.close()
    return not reader.is_alive() and not raised
