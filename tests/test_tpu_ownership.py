"""One process for each chip, enforced by the runtime.

CPU-only: "chips" here are the fake ones of ``ray_start_2_tpus`` (resource
counts and environment variables, no device behind them).  What is tested
is what the runtime decides — which worker may claim a device, when a chip
returns to the pool, where compiled programs are kept — and the control
flow of ``chip_smoke.py`` at tiny sizes.  That the device itself answers
is ``python chip_smoke.py`` on the chip, never a test.
"""

import json
import os
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu._private import resource_spec
from ray_tpu.util import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def rehearsals():
    """Both rehearsals, started with the module so they run alongside its
    other tests (they wait on subprocesses more than they compute); the
    rehearsal tests at the end of the file collect them."""
    def start(*args):
        return subprocess.Popen(
            [sys.executable, "chip_smoke.py", *args], cwd=ROOT, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)

    procs = {chips: start("--rehearse", "--chips", str(chips)) for chips in (1, 4)}
    procs["for real"] = start()  # as the driver first runs it: no accelerator
    yield procs
    for p in procs.values():
        p.kill()


def test_runtime_imports_leave_jax_out():
    """The head, the forkserver template, the Serve controller/proxy and a
    driver that only orchestrates never import jax, so they cannot start a
    backend on the chip."""
    code = (
        "import sys\n"
        "import ray_tpu, ray_tpu.serve, ray_tpu.train\n"
        "import ray_tpu._private.forkserver, ray_tpu._private.worker\n"
        "import ray_tpu.serve._private.controller, ray_tpu.serve._private.http_proxy\n"
        "from ray_tpu._private.resource_spec import jax_backend_initialized\n"
        "assert not jax_backend_initialized()\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib')))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                         capture_output=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"


def test_autodetect_counts_vfio_groups(monkeypatch):
    """A v5e host exposes one /dev/vfio/<n> per chip; ids are 0..n-1
    whatever the group numbers are.  No override variable exists."""
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.setenv("RAY_TPU_NUM_TPUS", "7")  # the removed override
    seen = []

    def fake_glob(pattern):
        seen.append(pattern)
        return ["/dev/vfio/1"]

    monkeypatch.setattr(resource_spec.glob, "glob", fake_glob)
    assert resource_spec.autodetect_tpus() == (1, [0])
    assert seen == ["/dev/vfio/[0-9]*"]
    monkeypatch.setattr(resource_spec.glob, "glob",
                        lambda p: [f"/dev/vfio/{i}" for i in range(4)])
    assert resource_spec.autodetect_tpus() == (4, [0, 1, 2, 3])
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")  # a restricted node
    assert resource_spec.autodetect_tpus() == (2, [2, 3])


@pytest.mark.parametrize("ids,bounds", [
    ([2], "1,1,1"), ([1, 0], "1,2,1"), ([0, 1, 2, 3], "2,2,1"), ([0, 1, 2], None)])
def test_chip_env_for_a_grant(ids, bounds):
    env = resource_spec.chip_env(ids)
    want = ",".join(map(str, sorted(ids)))
    assert env["TPU_VISIBLE_CHIPS"] == env["RAY_TPU_ASSIGNED_TPUS"] == want
    assert "JAX_PLATFORMS" not in env  # free to claim its chips
    assert env.get("TPU_CHIPS_PER_HOST_BOUNDS") == bounds
    assert env.get("TPU_HOST_BOUNDS") == ("1,1,1" if bounds else None)


def test_chip_env_without_a_grant_holds_jax_to_cpu():
    assert resource_spec.chip_env(None) == {"JAX_PLATFORMS": "cpu"}


def test_compile_cache_is_placed_from_outside(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.configure() == "/somewhere/else"
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/else"
    # unset: one fixed, git-ignored directory inside the checkout
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.configure() == os.path.join(ROOT, ".jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == os.path.join(ROOT, ".jax_cache")
    ignored = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"], cwd=ROOT)
    assert ignored.returncode == 0
    # a process held to the CPU keeps no cache at all
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert compile_cache.configure() is None
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


@pytest.fixture
def unrestricted_2_tpus(monkeypatch):
    """``ray_start_2_tpus`` started from an environment that does NOT hold
    JAX to the CPU (conftest does, for the suite) — so what a worker's
    environment says is the node's decision alone.  Nothing here uses jax."""
    monkeypatch.delenv("JAX_PLATFORMS")
    ray_tpu.init(num_cpus=4, num_tpus=2)
    yield
    ray_tpu.shutdown()


def _device_env():
    keys = ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS",
            "TPU_HOST_BOUNDS", "JAX_COMPILATION_CACHE_DIR")
    return {k: os.environ.get(k) for k in keys}, os.getpid()


def test_worker_environment_follows_the_grant(unrestricted_2_tpus):
    @ray_tpu.remote(num_tpus=1)
    class Holder:
        def env(self):
            return _device_env()

    holder = Holder.remote()
    held, _ = ray_tpu.get(holder.env.remote(), timeout=60)
    plain, _ = ray_tpu.get(ray_tpu.remote(_device_env).remote(), timeout=60)
    # no chip granted: held to the CPU at spawn, whatever it imports later
    assert plain["JAX_PLATFORMS"] == "cpu" and plain["TPU_VISIBLE_CHIPS"] is None
    # one chip granted: not restricted, owns a 1x1x1 slice of the host,
    # and keeps compiled programs in the checkout's cache
    assert held["JAX_PLATFORMS"] is None
    assert held["TPU_VISIBLE_CHIPS"] in ("0", "1")
    assert held["TPU_CHIPS_PER_HOST_BOUNDS"] == held["TPU_HOST_BOUNDS"] == "1,1,1"
    assert held["JAX_COMPILATION_CACHE_DIR"] == os.path.join(ROOT, ".jax_cache")


def test_chip_task_worker_retires_and_hands_the_chip_back(unrestricted_2_tpus):
    """A pooled worker cannot give a chip back, so it runs ONE chip-holding
    task and exits; the chip returns to the pool when it is gone."""
    chip_task = ray_tpu.remote(num_tpus=2)(_device_env)
    (env1, pid1) = ray_tpu.get(chip_task.remote(), timeout=60)
    (env2, pid2) = ray_tpu.get(chip_task.remote(), timeout=60)  # needs both back
    assert pid1 != pid2
    assert env1["JAX_PLATFORMS"] is None and env1["TPU_VISIBLE_CHIPS"] == "0,1"
    assert env1["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"

    @ray_tpu.remote(num_tpus=2)
    class Holder:
        def pid(self):
            return os.getpid()

    # ... and an actor created afterwards gets them within a bounded time
    assert ray_tpu.get(Holder.remote().pid.remote(), timeout=60) not in (pid1, pid2)
    assert _gone(pid1) and _gone(pid2)


def _gone(pid: int) -> bool:
    """Exited: no such process, or a zombie the head is about to reap."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except OSError:
        return True


def test_bench_fails_without_a_chip_and_on_a_failed_phase(monkeypatch, capsys):
    sys.path.insert(0, ROOT)
    import bench

    monkeypatch.setattr(resource_spec.glob, "glob", lambda p: [])
    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench.run_through_trainer()  # no tiny model, no CPU run
    assert not ray_tpu.is_initialized()
    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench._require_chip()  # (starts jax on the CPU in THIS process)
    monkeypatch.setattr(resource_spec, "jax_backend_initialized", lambda: False)

    headline = {"tokens_per_sec": 1.0, "flops_per_token": 1.0,
                "device_kind": "TPU v5 lite"}
    monkeypatch.setattr(bench, "run_through_trainer", lambda: headline)
    monkeypatch.setattr(bench, "run_raw", lambda: headline)
    monkeypatch.setattr(bench, "_PHASES", [
        ("fine", lambda: {"fine_row": 1}),
        ("broken", lambda: 1 / 0)])
    assert bench.main() == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["failed_phases"] == ["broken"] and out["fine_row"] == 1
    assert out["broken_error"].startswith("ZeroDivisionError")
    monkeypatch.setattr(bench, "_PHASES", [("fine", lambda: {"fine_row": 1})])
    assert bench.main() == 0
    monkeypatch.setattr(resource_spec, "jax_backend_initialized", lambda: True)
    assert bench.main() == 1  # the parent held the chip: not a clean run


# ---------------------------------------------------------------------------
# chip_smoke.py's control flow, rehearsed on the CPU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chips,phases", [
    (1, ["core", "handback", "train", "serve"]),
    (4, ["four_actors", "sharded_step"])])
def test_chip_smoke_rehearsal(rehearsals, chips, phases):
    out, err = rehearsals[chips].communicate(timeout=240)
    lines = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    assert rehearsals[chips].returncode == 0, (lines, err[-3000:])
    assert [l["phase"] for l in lines if "phase" in l] == phases
    assert all(l["ok"] for l in lines if "phase" in l)
    # a rehearsal tests the script, not the chip — and can never say otherwise
    assert out.rstrip().splitlines()[-1] == json.dumps(lines[-1])
    assert lines[-1]["ok"] is False and lines[-1]["rehearsal"] == "passed"
    assert lines[-1]["device"]["platform"] == "cpu"
    assert '"platform": "tpu"' not in out and '"ok": true}' not in out.splitlines()[-1]


def test_chip_smoke_without_a_chip_fails(rehearsals):
    """No accelerator, so no result: it does not run on the CPU instead."""
    out, _ = rehearsals["for real"].communicate(timeout=120)
    assert rehearsals["for real"].returncode != 0
    assert json.loads(out.rstrip().splitlines()[-1]) == {"ok": False, "device": None}
