"""``ray_tpu.shutdown()`` returns only when every process the node started
is gone.  What refused PR 41: workers are forked from the node's fork server,
so they are no children of whoever called ``init()`` and a caller that counts
its own children sees none of them; ``Node.shutdown`` gave a worker 2 s, killed
it and waited at most 30 s, and a chip holder unmapping 13 GB on a loaded host
outlived that, so a process was still there when the next run began."""

import os
import time

import numpy as np

import ray_tpu
from ray_tpu._private.worker import global_worker


def _alive(pid: int) -> bool:
    """A process that exists and is no zombie."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def _of_session(session_dir: str) -> list:
    """Every live process whose command line or environment names the
    session (a forked worker keeps the fork server's command line, which
    holds the session's socket)."""
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        for what in ("cmdline", "environ"):
            try:
                with open(f"/proc/{pid}/{what}", "rb") as f:
                    if session_dir.encode() in f.read() and _alive(int(pid)):
                        found.append(int(pid))
                        break
            except OSError:
                continue
    return [p for p in found if p != os.getpid()]


def test_nothing_is_left_when_shutdown_returns():
    """Two workers that each hold half a GB of touched memory and sit in a
    call that never ends (so neither answers the node's "exit"): they are
    killed, and ``shutdown()`` waits until they, and the fork server they
    came from, are gone."""
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    class Hog:
        def __init__(self):
            self.held = np.ones((64 << 20,), np.float64)  # 512 MB, touched

        def pid(self):
            return os.getpid()

        def stall(self):
            time.sleep(3600)

    hogs = [Hog.remote() for _ in range(2)]
    pids = ray_tpu.get([h.pid.remote() for h in hogs], timeout=120)
    for h in hogs:
        h.stall.remote()
    node = global_worker.node
    session_dir, template = node.session_dir, node._forkserver.pid
    assert all(_alive(p) for p in pids) and _alive(template)
    assert set(pids) <= set(_of_session(session_dir))
    t = time.time()
    ray_tpu.shutdown()
    took = time.time() - t
    assert not any(_alive(p) for p in pids + [template]), (pids, template)
    assert _of_session(session_dir) == []
    assert took < 60, took
