"""The slice failure domain, end-to-end (ROADMAP item 3 / VERDICT Weak #8).

Headline scenario: four emulated hosts form one TPU slice and hold a
STRICT_PACK training gang mid-run; chaos SIGKILLs one host.  (Four is what
the assertions need: a gang spread over several hosts, ONE of which dies, and
a slice replaced WHOLE.  It was sixteen, plus the sixteen of the replacement
and a worker each, beside five other xdist workers on eight cores: the waits
below then ran out on a loaded box, one tier-1 run in three.)  The runtime
must detect the death (mesh + control EOF), declare the slice degraded,
restart the WHOLE gang from the latest checkpoint, and heal the fleet by
replacing the slice atomically (create-before-terminate) — with
``ray_tpu doctor`` explaining the incident while it is open and going
quiet after recovery.

Plus the pure-function halves: doctor's ``slice_degraded`` rule fire /
stay-silent semantics over synthetic events.
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu._private.worker import global_worker
from ray_tpu.autoscaler import AutoscalingConfig, TrendAutoscaler
from ray_tpu.autoscaler.autoscaler import Monitor
from ray_tpu.autoscaler.local_node_provider import LocalNodeProvider
from ray_tpu.devtools.chaos import ChaosMonkey, Injection
from ray_tpu.util.doctor import diagnose

SLICE_HOSTS = 4
STEPS = 40


def _make_train_loop():
    """The gang's train fn, built as a CLOSURE: the gang runs in agent
    worker processes that cannot import this test module, so the fn must
    cloudpickle by value (a module-level fn pickles by reference and dies
    with ModuleNotFoundError on the far side)."""

    def _chaos_train_loop(config):
        import time as _time

        from ray_tpu.air import session
        from ray_tpu.air.checkpoint import Checkpoint

        ckpt = session.get_checkpoint()
        start = (ckpt.to_dict()["step"] + 1) if ckpt is not None else 0
        rank = session.get_world_rank()
        for step in range(start, config["steps"]):
            _time.sleep(0.25)
            if rank == 0:
                # progress marker the driver watches to time the injection
                with open(config["progress"], "w") as f:
                    f.write(str(step))
            session.report(
                {"step": step, "resumed_from": start},
                checkpoint=(Checkpoint.from_dict({"step": step})
                            if rank == 0 else None),
            )

    return _chaos_train_loop


def _wait(pred, timeout, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(0.2)
    pytest.fail(f"timed out waiting for {what}")


@pytest.fixture
def slice_fleet():
    # the head holds NO capacity: the gang can only live on the slice
    ray_tpu.init(num_cpus=0, num_tpus=0)
    node = global_worker.node
    provider = LocalNodeProvider(node, {"slice_hosts": SLICE_HOSTS}, "chaos")
    monitor = None
    try:
        yield node, provider, lambda m: monitor
    finally:
        provider.shutdown()
        ray_tpu.shutdown()


def test_four_host_slice_chaos_recovery(slice_fleet, tmp_path):
    node, provider, _ = slice_fleet
    from ray_tpu.air import FailureConfig, RunConfig, ScalingConfig
    from ray_tpu.train.trainer import DataParallelTrainer

    cfg = AutoscalingConfig(
        min_workers=1, max_workers=1, idle_timeout_s=3600.0,
        worker_node={"num_cpus": 1, "slice_hosts": SLICE_HOSTS})
    autoscaler = TrendAutoscaler(node, provider, cfg)

    sid = provider.create_node({"num_cpus": 1}, 1)[0]
    members = provider.slice_members(sid)
    assert len(members) == SLICE_HOSTS
    _wait(lambda: all(m in node.nodes and node.nodes[m].alive
                      for m in members),
          120, "all the slice's hosts to register")

    progress = tmp_path / "progress"
    trainer = DataParallelTrainer(
        _make_train_loop(),
        train_loop_config={"steps": STEPS, "progress": str(progress)},
        scaling_config=ScalingConfig(
            num_workers=SLICE_HOSTS,
            resources_per_worker={"CPU": 1},
            placement_strategy="STRICT_PACK"),
        run_config=RunConfig(
            storage_path=str(tmp_path), name="slice-chaos",
            failure_config=FailureConfig(max_failures=2)),
    )
    box = {}

    def run():
        try:
            box["result"] = trainer.fit()
        except BaseException as e:  # noqa: BLE001 — surfaced by the test
            box["error"] = e

    th = threading.Thread(target=run, daemon=True)
    th.start()

    # mid-train: rank 0 has taken (and checkpointed) a few steps.  The
    # progress file is written BEFORE the step's report, and the driver books
    # a checkpoint only once every rank's report is in, so on a loaded box
    # step 3 can be reached with nothing booked yet: wait for the third
    # checkpoint (step 2) itself, which is what ``resumed_from >= 3`` needs
    _wait(lambda: progress.exists() and int(progress.read_text() or 0) >= 3
          and (tmp_path / "slice-chaos" / "checkpoint_000002").exists(),
          240, "training to reach step 3 with step 2's checkpoint booked")

    # the gang leased STRICT_PACK *within the slice*: one bundle per host
    with node.lock:
        pgs = [rt for rt in node.pgs.values() if rt.info.state == "CREATED"]
        assert pgs, "no placement group created for the gang"
        bundle_nodes = list(pgs[0].info.bundle_nodes)
    assert set(bundle_nodes) <= set(members)
    assert len(set(bundle_nodes)) == SLICE_HOSTS  # spread across all hosts

    # chaos: SIGKILL a seeded-random member of THE slice, mid-train
    cm = ChaosMonkey(node=node, procs=provider.procs, seed=7)
    rec = cm.inject(Injection(at_s=0.0, op="sigkill", slice_id=sid))
    victim = rec["target"]
    assert victim in members

    _wait(lambda: not node.nodes[victim].alive, 60,
          "head to observe the member death")

    # doctor DURING the incident: slice degraded, no replacement in flight
    from ray_tpu.experimental.state import api as state

    events = state.list_events(limit=10_000)
    open_findings = diagnose(events)
    assert "slice_degraded" in [f["rule"] for f in open_findings], \
        [f["rule"] for f in open_findings]
    assert any(e.get("source") == "chaos" and e.get("entity_id") == victim
               for e in events), "injection missing from the flight recorder"

    # now let the autoscaler heal: slice-atomic replacement
    monitor = Monitor(autoscaler, interval_s=0.5).start()
    try:
        th.join(timeout=420)
        assert not th.is_alive(), "training never completed after the kill"
    finally:
        monitor.stop()
        cm.stop()
    assert "error" not in box, box.get("error")
    result = box["result"]
    assert result.error is None, result.error

    # whole-gang restart + checkpoint resume: the final report comes from
    # a SECOND gang incarnation that started from a mid-run checkpoint
    assert result.metrics["step"] == STEPS - 1
    assert result.metrics["resumed_from"] >= 3, result.metrics

    events = state.list_events(limit=20_000)

    def _rows(source, message):
        return [e for e in events if e.get("source") == source
                and e.get("message") == message]

    assert _rows("train", "gang restarted"), "no whole-gang restart"
    replaced = _rows("autoscaler", "slice replaced")
    assert any(r.get("entity_id") == sid for r in replaced), replaced

    # slice-atomic replacement: the old slice is gone WHOLE, the new one
    # is whole and holds the gang's world size
    live = provider.non_terminated_nodes()
    assert sid not in live
    new_sid = next(r["data"]["replacement"] for r in replaced
                   if r.get("entity_id") == sid)
    assert new_sid in live
    new_members = provider.slice_members(new_sid)
    assert len(new_members) == SLICE_HOSTS
    _wait(lambda: all(m in node.nodes and node.nodes[m].alive
                      for m in new_members),
          60, "replacement slice fully registered")

    # the restarted gang lives ON the replacement slice
    with node.lock:
        pgs = [rt for rt in node.pgs.values() if rt.info.state == "CREATED"]
        placed = {n for rt in pgs for n in rt.info.bundle_nodes}
    assert placed <= set(new_members) | set()  # old hosts are dead

    # doctor AFTER recovery: the replacement closed the incident — the
    # slice_degraded finding clears (gang_restart remains as the
    # explanation of what happened, which is the point of the recorder)
    closed = diagnose(events)
    assert "slice_degraded" not in [f["rule"] for f in closed], [
        (e["message"], e.get("entity_id"), e.get("ts"), e.get("data"))
        for e in events if e.get("message", "").startswith("slice ")]

    # the failure-domain view agrees: only the healthy replacement remains
    rows = state.list_slices()
    by_id = {r["slice_id"]: r for r in rows}
    assert by_id[new_sid]["alive_members"] == SLICE_HOSTS
    assert not by_id[new_sid]["degraded"]


# ---------------------------------------------------------------------------
# doctor rule: pure-function fire / stay-silent
# ---------------------------------------------------------------------------

def _ev(source, message, entity_id, ts, **data):
    return {"source": source, "message": message, "entity_id": entity_id,
            "ts": ts, "severity": "ERROR", "data": data}


def test_slice_degraded_rule_fires_without_repair():
    f = diagnose([_ev("node", "slice degraded", "s1", 100.0)])
    rules = {x["rule"]: x for x in f}
    assert "slice_degraded" in rules
    assert rules["slice_degraded"]["severity"] == "ERROR"
    assert "s1" in rules["slice_degraded"]["summary"]


def test_slice_degraded_rule_clears_once_repair_in_flight():
    evs = [_ev("node", "slice degraded", "s1", 100.0)]
    evs.append(_ev("autoscaler", "slice replacement started", "s1", 101.0))
    assert "slice_degraded" not in [x["rule"] for x in diagnose(evs)]

    # a NEW degradation after the last repair re-opens the incident
    evs.append(_ev("node", "slice degraded", "s1", 200.0))
    assert "slice_degraded" in [x["rule"] for x in diagnose(evs)]

    # repairing a DIFFERENT slice does not close it
    evs.append(_ev("autoscaler", "slice replaced", "s2", 300.0))
    assert "slice_degraded" in [x["rule"] for x in diagnose(evs)]

    # repairing THE slice does
    evs.append(_ev("autoscaler", "slice replaced", "s1", 301.0))
    assert "slice_degraded" not in [x["rule"] for x in diagnose(evs)]


def test_slice_degraded_rule_reopens_when_replacement_fails():
    """'started' alone is only a suppression while IN FLIGHT: a later
    'failed' means the slice is still degraded — doctor must not stay
    silent under e.g. persistent quota exhaustion."""
    evs = [
        _ev("node", "slice degraded", "s1", 100.0),
        _ev("autoscaler", "slice replacement started", "s1", 101.0),
        _ev("autoscaler", "slice replacement failed", "s1", 102.0),
    ]
    assert "slice_degraded" in [x["rule"] for x in diagnose(evs)]

    # a retry puts it back in flight...
    evs.append(_ev("autoscaler", "slice replacement started", "s1", 103.0))
    assert "slice_degraded" not in [x["rule"] for x in diagnose(evs)]
    # ...and its success closes the incident for good
    evs.append(_ev("autoscaler", "slice replaced", "s1", 104.0))
    assert "slice_degraded" not in [x["rule"] for x in diagnose(evs)]


def test_slice_degraded_rule_ignores_a_late_death_of_a_replaced_slice():
    """Degraded, replaced, and then a member of the OLD slice reported dead
    (its death lands late on a loaded head): the slice is gone whole, so
    nothing re-opens; the replacement's own degradation is an incident of
    its own."""
    evs = [
        _ev("node", "slice degraded", "s1", 100.0, dead_node="h3"),
        _ev("autoscaler", "slice replacement started", "s1", 101.0),
        _ev("autoscaler", "slice replaced", "s1", 110.0, replacement="s2"),
        _ev("node", "slice degraded", "s1", 111.0, dead_node="h7"),
    ]
    assert "slice_degraded" not in [x["rule"] for x in diagnose(evs)]
    evs.append(_ev("node", "slice degraded", "s2", 120.0, dead_node="h20"))
    found = {x["rule"]: x for x in diagnose(evs)}
    assert "s2" in found["slice_degraded"]["summary"]
    assert "s1" not in found["slice_degraded"]["summary"]


def test_slice_degraded_rule_silent_on_healthy_events():
    evs = [
        _ev("node", "node removed", "n1", 1.0),
        _ev("autoscaler", "scale up: launched nodes", None, 2.0),
        _ev("chaos", "inject sigkill", "n1", 3.0),
    ]
    assert "slice_degraded" not in [x["rule"] for x in diagnose(evs)]
