"""Streaming data-plane executor tests (``ray_tpu/data/_streaming``).

The four contracts ISSUE 1 demands of the subsystem:

- **pipelining** — a consumer holds its first batch while upstream map
  tasks are still running (downstream starts before upstream finishes);
- **backpressure** — submitted-but-unconsumed blocks never exceed the
  per-split budget, however slow the consumer;
- **locality** — ``streaming_split(..., locality_hints=...)`` materializes
  each shard's blocks on the consuming node (emulated multi-node
  ``cluster_utils.Cluster``);
- **parity** — ``iter_batches`` through the streaming executor yields
  exactly what the eager engine materializes, across the transform shapes
  ``test_data.py`` exercises.
"""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rd
from ray_tpu.data._streaming import StreamingExecutor
from ray_tpu.data.plan import ExecutionPlan


# ---------------------------------------------------------------------------
# pipelining


def test_downstream_starts_before_upstream_finishes(ray_start_regular):
    """The first batch must arrive while a later block's map task is still
    blocked — consumption overlaps execution instead of following it."""

    @ray_tpu.remote(num_cpus=0)
    class Gate:
        def __init__(self):
            self.open = False

        def release(self):
            self.open = True

        def is_open(self):
            return self.open

    gate = Gate.remote()

    def hold_last(batch):
        batch = np.asarray(batch)
        if batch.max() >= 56:  # the final block of range(64) x 8 blocks
            while not ray_tpu.get(gate.is_open.remote()):
                time.sleep(0.02)
        return batch + 1

    ds = rd.range(64, parallelism=8).map_batches(hold_last)
    it = ds.iter_batches(batch_size=8)
    first = next(it)  # must not require the gated block to finish
    np.testing.assert_array_equal(np.sort(np.asarray(first)),
                                  np.arange(1, 9))
    ray_tpu.get(gate.release.remote())
    rest = [np.asarray(b) for b in it]
    got = np.concatenate([np.asarray(first)] + rest)
    np.testing.assert_array_equal(np.sort(got), np.arange(64) + 1)


# ---------------------------------------------------------------------------
# backpressure


def test_backpressure_budget_honored(ray_start_regular):
    """With a slow consumer, submitted-but-unconsumed blocks stay within
    the configured budget at every moment."""
    budget = 3
    ds = rd.range(240, parallelism=24).map(lambda x: x + 1)
    ex = StreamingExecutor(ds._plan, max_in_flight_blocks=budget)
    ex.start()
    seen = 0
    while True:
        ref = ex.get_next()
        if ref is None:
            break
        time.sleep(0.01)  # slow consumer: the pump must wait, not flood
        seen += 1
        assert ex.max_in_flight_observed <= budget
    assert seen == 24
    stats = ex.stats()
    assert stats["max_in_flight_observed"] <= budget
    assert stats["produced_blocks"] == 24


def test_backpressure_stalled_consumer_pins_only_window(ray_start_regular):
    """A consumer that never pulls caps submissions at the budget."""
    budget = 2
    ds = rd.range(160, parallelism=16).map(lambda x: x)
    ex = StreamingExecutor(ds._plan, max_in_flight_blocks=budget)
    ex.start()
    time.sleep(1.0)  # plenty of time for an unbounded pump to run ahead
    assert ex.max_in_flight_observed <= budget
    assert ex.stats()["produced_blocks"] <= budget
    ex.shutdown()


def test_backpressure_budget_env_knob(monkeypatch):
    monkeypatch.setenv("RAY_TPU_STREAMING_BLOCK_BUDGET", "5")
    ex = StreamingExecutor(ExecutionPlan([], None, []))
    assert ex._budget == 5
    monkeypatch.setenv("RAY_TPU_STREAMING_BLOCK_BUDGET", "bogus")
    ex = StreamingExecutor(ExecutionPlan([], None, []))
    assert ex._budget == 8  # default survives a bad value


def test_multi_split_slow_split_does_not_block_fast(ray_start_regular):
    """One stalled split must not stop the other split's progress."""
    ds = rd.range(120, parallelism=12).map(lambda x: x)
    ex = StreamingExecutor(ds._plan, num_splits=2, max_in_flight_blocks=2)
    ex.start()
    got = []
    # drain split 0 fully; split 1 is never consumed
    deadline = time.time() + 60
    while time.time() < deadline:
        ref = ex.get_next(0, timeout=60)
        if ref is None:
            break
        got.append(ref)
    assert got, "fast split starved behind the stalled one"
    # the stalled split holds at most its own budget
    assert ex._in_flight[1] <= 2
    ex.shutdown()


# ---------------------------------------------------------------------------
# locality


def test_locality_aware_shard_placement(ray_start_cluster):
    """A shard's map tasks run on the hinted consumer node where it has
    capacity — the block is produced (and therefore materializes) where it
    will be eaten.  The hint is soft (the next test): a task that finds its
    node full goes wherever the default policy puts it, so each hinted node
    here has a CPU for every block of its shard (3 of the 6)."""
    cluster = ray_start_cluster
    node_a = cluster.add_node(num_cpus=4)
    node_b = cluster.add_node(num_cpus=4)

    def tag_node(x):
        return {"v": x * 3,
                "node": ray_tpu.get_runtime_context().node_id}

    ds = rd.range(48, parallelism=6).map(tag_node)
    it_a, it_b = ds.streaming_split(2, locality_hints=[node_a, node_b])

    rows = {node_a: [], node_b: []}
    for nid, it in ((node_a, it_a), (node_b, it_b)):
        for row in it.iter_rows():
            assert row["node"] == nid, (
                f"block for the split pinned to {nid} was produced on "
                f"{row['node']}")
            rows[nid].append(row["v"])
    # the two shards partition the dataset
    assert sorted(rows[node_a] + rows[node_b]) == [i * 3 for i in range(48)]
    assert rows[node_a] and rows[node_b]


def test_locality_hint_is_soft_not_a_constraint(ray_start_cluster):
    """A hint toward a node with no capacity falls back to the default
    policy instead of wedging the pipeline."""
    cluster = ray_start_cluster
    tiny = cluster.add_node(num_cpus=0)  # can never run a 1-CPU map task

    ds = rd.range(20, parallelism=4).map(lambda x: x + 1)
    (it,) = ds.streaming_split(1, locality_hints=[tiny])
    got = [int(v) for b in it.iter_batches(batch_size=5)
           for v in np.asarray(b).reshape(-1)]
    assert sorted(got) == list(range(1, 21))


# ---------------------------------------------------------------------------
# parity with the eager engine


@pytest.mark.parametrize("build", [
    lambda: rd.range(100, parallelism=4).map(lambda x: x * 2),
    lambda: rd.range(60, parallelism=5).filter(lambda x: x % 3 == 0),
    lambda: rd.from_items(list(range(30)), parallelism=3)
        .flat_map(lambda x: [x, x + 100]),
    lambda: rd.range(64, parallelism=4)
        .map_batches(lambda b: np.asarray(b) * 10, batch_size=8)
        .map(lambda x: x + 1),
])
def test_iter_batches_parity_with_eager(ray_start_regular, build):
    ds_stream, ds_eager = build(), build()
    streamed = []
    for b in ds_stream.iter_batches(batch_size=7):
        streamed.extend(np.asarray(b).reshape(-1).tolist())
    # eager reference: execute the whole plan, then read the blocks
    refs, _ = ds_eager._plan.execute()
    from ray_tpu.data.block import BlockAccessor

    eager = []
    for ref in refs:
        eager.extend(BlockAccessor(ray_tpu.get(ref)).to_rows())
    assert streamed == [int(v) for v in eager]


def test_iter_batches_parity_after_shuffle_barrier(ray_start_regular):
    """A barrier stage (random_shuffle) executes eagerly once; the map
    suffix streams after it, and re-iteration replays the same shuffle."""
    ds = rd.range(50, parallelism=5).random_shuffle(seed=7).map(
        lambda x: x + 5)
    first = [int(v) for b in ds.iter_batches(batch_size=9)
             for v in np.asarray(b).reshape(-1)]
    second = [int(v) for b in ds.iter_batches(batch_size=9)
              for v in np.asarray(b).reshape(-1)]
    assert sorted(first) == [i + 5 for i in range(50)]
    assert first == second  # the shuffle prefix ran once and was cached


def test_iter_batches_lazy_until_first_batch(ray_start_regular):
    """iter_batches() must return instantly — the barrier prefix (shuffle)
    runs on the pump at first consumption, not at iterator construction."""
    ds = rd.range(30, parallelism=3).random_shuffle(seed=3).map(
        lambda x: x + 1)
    it = ds.iter_batches(batch_size=6)
    assert getattr(ds._plan, "_stream_prefix_out", None) is None, \
        "shuffle ran at iter_batches() call time"
    got = [int(v) for b in it for v in np.asarray(b).reshape(-1)]
    assert sorted(got) == [i + 1 for i in range(30)]
    assert ds._plan._stream_prefix_out is not None


def test_streaming_iter_caches_plan_result(ray_start_regular):
    """A full drain seals the plan: count()/re-iteration reuse the refs."""
    calls = []

    ds = rd.range(40, parallelism=4).map(lambda x: x + 2)
    out1 = [int(v) for b in ds.iter_batches(batch_size=10)
            for v in np.asarray(b).reshape(-1)]
    assert ds._plan._out is not None  # sealed by the streamed drain
    cached_refs = list(ds._plan._out[0])
    out2 = [int(v) for b in ds.iter_batches(batch_size=10)
            for v in np.asarray(b).reshape(-1)]
    assert out1 == out2
    assert list(ds._plan._out[0]) == cached_refs  # no re-execution
    assert any("streamed" in s["stage"] for s in ds.stats())


def test_streaming_error_propagates(ray_start_regular):
    def boom(x):
        if x >= 30:
            raise ValueError("block exploded")
        return x

    ds = rd.range(40, parallelism=4).map(boom)
    with pytest.raises(Exception, match="block exploded"):
        for _ in ds.iter_batches(batch_size=10):
            pass


# ---------------------------------------------------------------------------
# streaming_split semantics


def test_streaming_split_partitions_and_balances(ray_start_regular):
    its = rd.range(90, parallelism=9).map(lambda x: x).streaming_split(3)
    rows = []
    counts = []
    for it in its:
        mine = [int(v) for b in it.iter_batches(batch_size=8)
                for v in np.asarray(b).reshape(-1)]
        counts.append(len(mine))
        rows.extend(mine)
    assert sorted(rows) == list(range(90))
    # row-balanced at block granularity: every split saw a real share
    assert min(counts) >= 10


def test_streaming_split_epoch_replay_no_reexecution(ray_start_regular):
    """Epoch 2 replays the recorded refs instead of re-running map tasks."""

    @ray_tpu.remote(num_cpus=0)
    class Counter:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1

        def value(self):
            return self.n

    counter = Counter.remote()

    def counted(x):
        ray_tpu.get(counter.bump.remote())
        return x + 1

    (it,) = rd.range(24, parallelism=4).map(counted).streaming_split(1)
    epoch1 = [int(v) for b in it.iter_batches(batch_size=6)
              for v in np.asarray(b).reshape(-1)]
    ran_after_first = ray_tpu.get(counter.value.remote())
    epoch2 = [int(v) for b in it.iter_batches(batch_size=6)
              for v in np.asarray(b).reshape(-1)]
    assert sorted(epoch1) == list(range(1, 25))
    assert epoch1 == epoch2
    assert ray_tpu.get(counter.value.remote()) == ran_after_first == 24


def test_streaming_split_iterators_are_picklable(ray_start_regular):
    """The per-worker handle must cross a process boundary: each shard is
    drained inside a remote task, not the driver."""
    its = rd.range(40, parallelism=4).map(lambda x: x * 2).streaming_split(2)

    @ray_tpu.remote(num_cpus=1)
    def drain(it):
        return [int(v) for b in it.iter_batches(batch_size=5)
                for v in np.asarray(b).reshape(-1)]

    parts = ray_tpu.get([drain.remote(it) for it in its], timeout=120)
    assert sorted(parts[0] + parts[1]) == [i * 2 for i in range(40)]
    assert parts[0] and parts[1]


def test_streaming_split_validates_args(ray_start_regular):
    ds = rd.range(8, parallelism=2)
    with pytest.raises(ValueError):
        ds.streaming_split(0)
    with pytest.raises(ValueError):
        ds.streaming_split(2, locality_hints=["only-one"])


# ---------------------------------------------------------------------------
# trainer wiring: get_dataset_shard -> streaming shard per rank


def test_trainer_shards_route_through_streaming_split(ray_start_regular,
                                                      tmp_path):
    """DataConfig wires each rank a StreamSplitDataIterator; ranks see
    disjoint shards whose union is the dataset."""
    import json
    import os

    from ray_tpu.air import ScalingConfig, session
    from ray_tpu.train import JaxTrainer

    out_dir = str(tmp_path)

    def loop(config=None):
        shard = session.get_dataset_shard("train")
        rows = [int(v) for b in shard.iter_batches(batch_size=4)
                for v in np.asarray(b).reshape(-1)]
        rank = session.get_world_rank()
        with open(os.path.join(config["dir"], f"rank{rank}.json"), "w") as f:
            json.dump(rows, f)
        session.report({"rows": len(rows), "done": True})

    ds = rd.range(32, parallelism=4).map(lambda x: x + 7)
    trainer = JaxTrainer(
        loop,
        train_loop_config={"dir": out_dir},
        scaling_config=ScalingConfig(num_workers=2,
                                     resources_per_worker={"CPU": 1}),
        datasets={"train": ds},
    )
    result = trainer.fit()
    assert result.error is None, result.error
    import json as _json
    import os as _os

    per_rank = []
    for rank in (0, 1):
        with open(_os.path.join(out_dir, f"rank{rank}.json")) as f:
            per_rank.append(_json.load(f))
    assert per_rank[0] and per_rank[1]
    assert sorted(per_rank[0] + per_rank[1]) == [i + 7 for i in range(32)]


# ---------------------------------------------------------------------------
# hardening: review findings on the executor's edges


def test_equal_split_assignment_immune_to_consumer_speed(ray_start_regular):
    """Equal-mode assignment is decided up front, not by drain order: a
    split whose consumer stalls at its budget must still receive its full
    half, or a per-batch collective gang deadlocks at epoch end."""
    ds = rd.range(120, parallelism=12).map(lambda x: x)
    ex = StreamingExecutor(ds._plan, num_splits=2, max_in_flight_blocks=2)
    ex.start()
    # drain split 0 COMPLETELY while split 1 consumes nothing
    fast = []
    while True:
        ref = ex.get_next(0, timeout=60)
        if ref is None:
            break
        fast.append(ref)
    slow = []
    while True:
        ref = ex.get_next(1, timeout=60)
        if ref is None:
            break
        slow.append(ref)
    assert len(fast) == 6, "fast split stole the stalled split's blocks"
    assert len(slow) == 6
    from ray_tpu.data.block import BlockAccessor

    rows = [int(v) for r in fast + slow
            for v in BlockAccessor(ray_tpu.get(r)).to_rows()]
    assert sorted(rows) == list(range(120))


def test_concurrent_first_get_next_starts_one_pump(ray_start_regular):
    """N consumer threads racing the first poll (the SplitCoordinator's
    max_concurrency reality) must not start two pumps over one source."""
    ds = rd.range(60, parallelism=6).map(lambda x: x + 1)
    ex = StreamingExecutor(ds._plan, num_splits=3)
    barrier = threading.Barrier(3)
    got = [[] for _ in range(3)]

    def drain(i):
        barrier.wait()
        while True:
            ref = ex.get_next(i, timeout=60)
            if ref is None:
                return
            got[i].append(ref)

    threads = [threading.Thread(target=drain, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    pumps = [t for t in threading.enumerate()
             if t.name == "streaming-executor-pump" and t.is_alive()]
    assert len(pumps) <= 1, "duplicate pump threads over one source"
    from ray_tpu.data.block import BlockAccessor

    rows = [int(v) for refs in got for r in refs
            for v in BlockAccessor(ray_tpu.get(r)).to_rows()]
    assert sorted(rows) == [i + 1 for i in range(60)]


def test_abandoned_iter_batches_stops_pipeline(ray_start_regular):
    """Breaking out of iter_batches early must stop the executor even
    though the prefetch thread is suspended inside the ref generator —
    no pump thread left running, no map tasks submitted past the window."""

    @ray_tpu.remote(num_cpus=0)
    class Counter:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1

        def value(self):
            return self.n

    counter = Counter.remote()

    def counted(x):
        ray_tpu.get(counter.bump.remote())
        time.sleep(0.05)
        return x

    ds = rd.range(240, parallelism=24).map(counted)
    it = ds.iter_batches(batch_size=5)
    next(it)
    it.close()  # abandon: generator cleanup must shut the executor down
    deadline = time.time() + 30
    while time.time() < deadline and any(
            t.name == "streaming-executor-pump" and t.is_alive()
            for t in threading.enumerate()):
        time.sleep(0.1)
    assert not any(t.name == "streaming-executor-pump" and t.is_alive()
                   for t in threading.enumerate()), "pump leaked"
    # already-submitted tasks may finish, but no NEW blocks are submitted:
    # the count settles far below the full 240 rows (window is ~budget
    # blocks of 10 rows each)
    settled = ray_tpu.get(counter.value.remote())
    deadline = time.time() + 30
    while time.time() < deadline:
        time.sleep(1.0)
        now = ray_tpu.get(counter.value.remote())
        if now == settled:
            break
        settled = now
    assert settled <= 150, "pump kept submitting after abandonment"
    # abandonment must NOT have cached the partial drain as the result
    assert ds._plan._out is None
    full = [int(v) for b in ds.iter_batches(batch_size=5)
            for v in np.asarray(b).reshape(-1)]
    assert sorted(full) == list(range(240))


def test_stream_error_is_terminal_not_a_hang(ray_start_regular):
    """After the pump surfaces an error, later polls on the split must
    re-raise it immediately instead of blocking forever."""
    class Unpicklable:
        def __reduce__(self):
            raise RuntimeError("submission exploded")

    poison = Unpicklable()
    ds = rd.range(20, parallelism=2).map(lambda x, _p=poison: x)
    ex = StreamingExecutor(ds._plan)
    with pytest.raises(Exception, match="submission exploded"):
        ex.get_next(timeout=60)
    with pytest.raises(Exception, match="submission exploded"):
        ex.get_next(timeout=10)  # terminal: re-raised, no hang


def test_equal_split_preassigns_even_without_row_counts(ray_start_regular):
    """After a barrier prefix the row counts are unknown, but equal mode
    must STILL pre-assign blocks (block-balanced) instead of silently
    degrading to drain-rate assignment."""
    ds = rd.range(120, parallelism=12).random_shuffle(seed=1).map(
        lambda x: x)
    ex = StreamingExecutor(ds._plan, num_splits=2, max_in_flight_blocks=2)
    ex.start()
    fast = []
    while True:
        ref = ex.get_next(0, timeout=60)
        if ref is None:
            break
        fast.append(ref)
    slow = []
    while True:
        ref = ex.get_next(1, timeout=60)
        if ref is None:
            break
        slow.append(ref)
    assert len(fast) == 6, "fast split stole the stalled split's blocks"
    assert len(slow) == 6


def test_split_reiteration_after_midepoch_abandon_is_full(ray_start_regular):
    """Abandoning a shard mid-epoch and iterating again must replay the
    already-delivered blocks — a fresh iteration always sees the FULL
    shard, never just the epoch's remainder."""
    (it,) = rd.range(60, parallelism=6).map(lambda x: x + 1).streaming_split(
        1, max_in_flight_blocks=2)
    gen = it.iter_batches(batch_size=10)
    next(gen)  # consume one block's worth...
    gen.close()  # ...then abandon mid-epoch
    full = [int(v) for b in it.iter_batches(batch_size=10)
            for v in np.asarray(b).reshape(-1)]
    assert sorted(full) == list(range(1, 61))


def test_blocked_worker_reclaims_pipelined_child(ray_start_regular):
    """Scheduler-deadlock regression: a task whose get waits on the output
    of a task PIPELINED BEHIND IT on the same worker must not hang — the
    head reclaims a blocked worker's unstarted pipeline and reschedules it
    elsewhere.  This is the streaming consumer's shape: drains block on
    block-producing map tasks the head queued behind them."""

    @ray_tpu.remote(num_cpus=1)
    def child(x):
        return x * 2

    @ray_tpu.remote(num_cpus=1)
    def parent():
        # submit AFTER this task started (so the child can only ride this
        # worker's lease or be reclaimed), then block on it
        refs = [child.remote(i) for i in range(4)]
        return sum(ray_tpu.get(refs, timeout=120))

    assert ray_tpu.get(parent.remote(), timeout=180) == 2 * (0 + 1 + 2 + 3)


def test_arena_fd_write_min_env_guard():
    """A malformed RAY_TPU_ARENA_FD_WRITE_MIN falls back to the default
    instead of crashing every process at import."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, RAY_TPU_ARENA_FD_WRITE_MIN="64MB",
               JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "from ray_tpu._private import object_store as o; "
         "print(o._ARENA_FD_WRITE_MIN)"],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert int(out.stdout.strip()) == 64 << 20


def test_object_store_capacity_never_exceeds_shm(monkeypatch):
    """The 2 GiB floor must lose to the shm-mount clamp (docker's 64 MB
    default /dev/shm): an arena bigger than its tmpfs dies with SIGBUS
    mid-put instead of falling back cleanly."""
    import os

    from ray_tpu._private.config import Config, resolve_object_store_memory

    class TinyMount:
        f_frsize = 4096
        f_blocks = (64 << 20) // 4096  # a 64 MB tmpfs
        f_bavail = (64 << 20) // 4096  # all free

    monkeypatch.setattr(os, "statvfs", lambda path: TinyMount())
    cap = resolve_object_store_memory(Config(object_store_memory=0))
    assert cap <= int((64 << 20) * 0.8)
    # an explicit setting is always honored verbatim
    assert resolve_object_store_memory(
        Config(object_store_memory=123 << 20)) == 123 << 20
