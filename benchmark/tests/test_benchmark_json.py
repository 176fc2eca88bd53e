"""BENCHMARK.json against the contract's shape rules and against the files
the harness finds by name."""

import json
import os
import re

import pytest

from benchmark import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def test_names_and_units():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for text in ([w["why"] for w in BENCH["workloads"]] + [c["why"] for c in BENCH["configs"]]
                 + [m["layer"] for m in BENCH["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def cells_of(metric):
    return set(metric.get("workloads") or [w["name"] for w in BENCH["workloads"]])


def test_every_moves_names_a_metric_the_same_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m
        assert cells_of(m) <= cells_of(e2e[m["moves"]]), m
    for w in BENCH["workloads"]:
        mine = [m for m in BENCH["end_to_end"] if w["name"] in cells_of(m)]
        assert len(mine) >= 2
        assert any(w["name"] in cells_of(m) for m in BENCH["per_layer"])
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")


def test_files_found_by_name():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        held = load("configs", c["name"] + ".json")
        assert held["source"] == c["source"] and held["reduced"] == c["reduced"]
        assert flops.gpt2_param_count(held["gpt2_config"]) > 3e8
    pairs = set()
    for w in BENCH["workloads"]:
        cell = load("workloads", w["name"] + ".json")
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert w["config"] in configs and not cell.get("rehearsal")
        assert load("traffic", w["traffic"] + ".json")["kind"] == cell["kind"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "drivers", cell["kind"] + ".py"))
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(BENCH["workloads"])
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py")), m["name"]
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_serve_cells_stay_inside_the_model_positions(cell):
    c = load("workloads", cell + ".json")
    if c["kind"] != "serve":
        pytest.skip("a train cell")
    e = c["engine"]
    positions = load("configs", c["config"] + ".json")["gpt2_config"]["max_seq_len"]
    assert max(e["prefill_buckets"]) + e["max_new_tokens"] + e["decode_chunk_steps"] <= positions
    t = load("traffic", c["traffic"] + ".json")
    assert t["prompt_len"]["max"] <= max(e["prefill_buckets"])
    assert t["output_len"]["max"] <= e["max_new_tokens"]


def test_the_yardstick_arithmetic():
    xl = load("configs", "gpt2-xl.json")["gpt2_config"]
    assert flops.gpt2_param_count(xl) == 1557686400
    n = flops.gpt2_param_count(load("configs", "gpt2-medium.json")["gpt2_config"])
    assert flops.train_flops_per_token(n, 24, 1024, 1024) == pytest.approx(2.4312e9, rel=1e-4)
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")
    # decode: weights once plus the live positions' keys and values
    assert flops.decode_step_bytes(xl, 1000) == 2 * 1557686400 + 2 * 48 * 1600 * 2 * 1000
    t, bound = flops.roofline_seconds(
        flops.decode_step_flops(xl, 16, 4000), flops.decode_step_bytes(xl, 4000), peak)
    assert bound == "memory" and t == pytest.approx(flops.decode_step_bytes(xl, 4000) / 819e9)
