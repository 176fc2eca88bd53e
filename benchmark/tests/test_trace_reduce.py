"""The trace reduction: interval arithmetic on hand-made events, and the
whole reduction on a small trace recorded on the chip."""

import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_merge_total_subtract():
    merged = tr.merge_intervals([[5, 9], [0, 3], [2, 4], [9, 9], [8, 12]])
    assert merged == [[0, 4], [5, 12]]
    assert tr.total(merged) == 11
    assert tr.subtract([[0, 10]], [[2, 3], [5, 7]]) == [[0, 2], [3, 5], [7, 10]]
    assert tr.subtract([[0, 4], [6, 8]], [[3, 7]]) == [[0, 3], [7, 8]]
    assert tr.subtract([[0, 4]], []) == [[0, 4]]


def synthetic():
    us = 1000
    dev = {
        "XLA Ops": [
            ["fusion.1", 0, 100 * us], ["all-gather.2", 100 * us, 50 * us],
            # this all-reduce is half hidden behind a fusion on another line
            ["all-reduce.3", 200 * us, 100 * us], ["fusion.4", 400 * us, 100 * us],
        ],
        "XLA Ops overlap": [["fusion.9", 250 * us, 50 * us]],
        "XLA Modules": [["jit_train_step(123)", 0, 300 * us],
                        ["jit_train_step(123)", 400 * us, 100 * us]],
    }
    host = {"python": [["bench.window", 0, 1000 * us],
                       ["bench.host_batch", 300 * us, 100 * us],
                       ["$slow.py:1 f", 290 * us, 120 * us],
                       ["bench.report", 500 * us, 500 * us]]}
    return {"planes": {"/device:TPU:0": dev, "/host:CPU": host}}


def test_reduce_synthetic():
    ev = synthetic()
    # only the line named "XLA Ops" counts once a plane has one
    r = tr.reduce_events(ev, tr.window_of(ev, "bench.window"))
    assert r["window_s"] == pytest.approx(1e-3)
    assert r["busy_s"] == pytest.approx(350e-6)      # 0-150, 200-300, 400-500
    assert r["collective_s"] == pytest.approx(150e-6)
    assert r["collective_exposed_s"] == pytest.approx(150e-6)
    step = r["modules"]["jit_train_step"]
    assert step["count"] == 2 and step["median_s"] == pytest.approx(300e-6)
    gaps = dict(r["idle_gaps"])
    # 300-400 is the host batch (the bench.* span wins over the python frame),
    # 500-1000 the report, 150-200 has no host event covering half of it
    assert gaps["bench.host_batch"] == pytest.approx(100e-6)
    assert gaps["bench.report"] == pytest.approx(500e-6)
    assert gaps["(no host event)"] == pytest.approx(50e-6)
    assert r["device_ops"][0][0] in ("fusion.1", "all-reduce.3", "fusion.4")


def test_gap_is_named_on_the_thread_that_launches_next():
    us = 1000
    ev = synthetic()
    ev["planes"]["/host:CPU"] = {
        # a thread parked in a wait covers every gap and says nothing
        "waiter": [["$connection.py:390 _recv", 0, 1000 * us]],
        "engine": [["$llm.py:700 _drain", 310 * us, 80 * us],
                   ["PjitFunction(_decode_chunk_wrapper)", 395 * us, 20 * us]],
    }
    r = tr.reduce_events(ev, (0, 1000 * us))
    gaps = dict(r["idle_gaps"])
    assert gaps["$llm.py:700 _drain"] == pytest.approx(100e-6)   # 300-400
    # 500-1000: nothing launches afterwards, so every thread is asked
    assert gaps["$connection.py:390 _recv"] >= 500e-6


def test_loops_do_not_hide_what_is_inside_them():
    ev = synthetic()
    # the scan over layers: one while op spanning everything in the plane
    ev["planes"]["/device:TPU:0"]["XLA Ops"].append(["while.7", 0, 500 * 1000])
    r = tr.reduce_events(ev, tr.window_of(ev, "bench.window"))
    assert r["busy_s"] == pytest.approx(350e-6)
    assert r["collective_exposed_s"] == pytest.approx(150e-6)
    assert "while.7" not in dict(r["device_ops"])


def test_collective_hidden_behind_compute():
    ev = synthetic()
    dev = ev["planes"]["/device:TPU:0"]
    dev["XLA Ops"] += dev.pop("XLA Ops overlap")
    r = tr.reduce_events(ev, tr.window_of(ev, "bench.window"))
    # fusion.9 covers 250-300 of the all-reduce: only 200-250 stays exposed
    assert r["collective_exposed_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(350e-6)


def test_recorded_trace():
    path = os.path.join(DATA, "tiny_trace_events.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    ev = tr.read_events(path)
    r = tr.reduce_events(ev, tr.window_of(ev, "bench.window"))
    assert r["devices"] and 0 < r["busy_s"] <= r["window_s"]
    assert any("matmul_chain" in name for name in r["modules"])
    chain = next(m for n, m in r["modules"].items() if "matmul_chain" in n)
    # the device's clock runs about a millisecond ahead of the host's in this
    # recording, so the first of the five programs starts before the window
    assert chain["count"] == 4 and chain["median_s"] == pytest.approx(47.39e-6, rel=0.01)
    assert r["busy_s"] == pytest.approx(4 * 47.39e-6, rel=0.01)
    # the recorded loop sleeps 20 ms between its five steps
    assert dict(r["idle_gaps"]).get("bench.sleep", 0) > 0.05
