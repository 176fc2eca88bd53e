"""The eight readers over the replica's stage spans and counters, each on a
hand-made ``raw``: the value it computes, and None where the program offers
no such key (the commit before the spans) or the cell is a train cell."""

import importlib.util
import os

import pytest

FOLDER = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "layer_metrics")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), os.path.join(FOLDER, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def stage(count, sum_s, p95_s=0.0):
    return {"count": count, "sum_s": sum_s, "p50_s": 0.0, "p95_s": p95_s}


def raw_with_stages():
    """Four warm-up requests before, ten more in the window."""
    phases = {"serve.route": 0.002, "task.dispatch": 0.0005, "serve.submit": 0.001,
              "engine.queue": 0.150, "engine.first_token": 0.400,
              "engine.stream_yield": 0.010, "serve.pickup": 0.012,
              "serve.first_reply": 0.580}
    before = {p: stage(4, 4 * 9.0) for p in phases}  # slow warm-up: must cancel
    after = {p: stage(14, 4 * 9.0 + 10 * d, p95_s=2 * d) for p, d in phases.items()}
    before["clock_skew"] = after["clock_skew"] = 0
    done = {"done": True, "sent": 1.0, "times": [1.6, 1.7], "due": 1.0}
    lost = {"done": False, "sent": 2.0, "times": [], "due": 2.0}
    return {"kind": "serve",
            "engine_before": {"stages": before, "compiles": {"count": 40}},
            "engine_after": {"stages": after, "compiles": {"count": 41}},
            "records": [(done, 16)] * 10 + [(lost, 16)]}


VALUES = {
    "proxy.route_ms": 2.0,
    "core.actor_call_dispatch_ms": 0.5,
    "engine.queue_wait_ms": 150.0,
    "engine.queue_wait_p95_ms": 300.0,
    "engine.first_token_ms": 400.0,
    "replica.stream_pickup_ms": 22.0,
    "proxy.ttft_unattributed_ms": 20.0,  # 600 ms at the client, 580 covered
    "engine.compiles_in_window": 1.0,
}


@pytest.mark.parametrize("name", sorted(VALUES))
def test_reader_value_and_none_without_the_key(name):
    mod = reader(name)
    assert mod.UNIT == ("count" if name.endswith("in_window") else "ms")
    assert mod.read(None, raw_with_stages()) == pytest.approx(VALUES[name])
    # the parent commit's perf_stats(): no stages, no compiles
    old = raw_with_stages()
    old["engine_before"], old["engine_after"] = {"ttft": {}}, {"ttft": {}}
    assert mod.read(None, old) is None
    # RAY_TPU_EVENTS=0: the key is there and empty
    off = raw_with_stages()
    off["engine_before"]["stages"] = off["engine_after"]["stages"] = {}
    if name != "engine.compiles_in_window":
        assert mod.read(None, off) is None
    assert mod.read(None, {"kind": "train"}) is None
