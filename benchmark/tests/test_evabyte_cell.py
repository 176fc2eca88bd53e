"""What the EvaByte cell adds to the benchmark: its configuration file against
the catalog, the program and the counts; the cell's sizes against the two
pairs of cached tensors; its entries in BENCHMARK.json; the four new readers
on hand-made ``raw``s (a value where the program counts, None where it does
not, as the parent of the PR that adds the family does not)."""

import importlib.util
import json
import os
import types

import pytest

from benchmark import flops, flops_evabyte as fe, traffic_gen

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "serve-evabyte-6.5b-pp4-code"
NEW = ("model.eva_decode_roofline_pct", "model.eva_prefill_live_mfu_pct",
       "model.eva_decode_attention_roofline_pct", "cache.eva_read_share_pct")
SHARED = ("serve_tokens_per_s", "tpot_p95_ms", "engine.slots_busy_pct",
          "engine.prefill_interference_pct", "model.decode_step_ms",
          "device.idle_pct.serve", "engine.compiles_in_window",
          "engine.tpot_p95_ms", "replica.tpot_p95_ms",
          "engine.chunk_steps_per_gap", "engine.decode_prefill_wait_pct",
          "engine.decode_tick_ms", "engine.tick_host_ms",
          "cache.flush_write_share_pct")


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(HERE, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CONFIG = load("configs", "evabyte-6.5b-pp4.json")
KW = CONFIG["model_config"]
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_configuration_against_the_catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(path)) if r["name"] == "EvaByte")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers"}


def test_the_configuration_keeps_the_published_widths():
    assert CONFIG["num_hidden_layers"] == 8 and CONFIG["published"]["num_hidden_layers"] == 32
    assert "FOUR PIPELINE STAGES" in CONFIG["deployment"]
    assert "1,630,932,992 parameters" in CONFIG["deployment"]
    for key in ("pooling", "pooling_after_rotation", "remote_set", "one_softmax",
                "rotary_pairs", "head_order", "dtype", "weights", "parts"):
        assert key in CONFIG["assumed"]
    assert (KW["d_model"], KW["n_heads"], KW["n_kv_heads"], KW["d_ff"],
            KW["window_size"], KW["chunk_size"], KW["n_pred_heads"],
            KW["vocab_size"]) == (4096, 32, 32, 11008, 2048, 16, 8, 320)
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"],
            CONFIG["window_size"], CONFIG["chunk_size"]) == (4096, 11008, 2048, 16)
    assert CONFIG["reference_sizes"]["window_size"] == KW["window_size"]
    assert set(CONFIG["trace_scopes"]) >= {
        "attention.eva_window", "attention.eva_summary", "attention.eva_merge",
        "attention.eva_pool"}


def test_the_program_builds_the_configuration_and_the_counts_agree():
    import jax

    from ray_tpu.serve.llm import _default_init, make_config

    cfg = make_config(CONFIG["family"], CONFIG["size"], **KW)
    shapes = jax.eval_shape(lambda: _default_init(cfg, 0))
    held = sum(int(a.size) for a in jax.tree.leaves(shapes))
    assert held == fe.param_count(KW) == 1_630_932_992
    assert fe.layer_params(KW) == 202_391_552
    assert fe.row_bytes(KW) == 16_384
    # a step reads the layers, the final norm and ONE of the eight heads
    assert fe.param_count(KW) - fe.step_params(KW) == 320 * 4096 + 7 * 320 * 4096


def test_the_cell_fits_its_engine_and_its_traffic():
    from ray_tpu.models import generate as gen
    from ray_tpu.serve import llm

    cell, traffic = load("workloads", CELL + ".json"), load(
        "traffic", "code-bytes-lognormal-16k.json")
    e = cell["engine"]
    assert (e["n_slots"], e["decode_chunk_steps"], e["max_new_tokens"]) == (16, 16, 2048)
    assert e["prefill_buckets"] == [2048, 4096, 8192, 16384]
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                     "sigma": 0.7, "min": 2048, "max": 16384}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 512,
                                     "sigma": 0.7, "min": 64, "max": 2048}
    assert traffic["arrivals"]["process"] == "poisson" and traffic["preroll_s"] == 5
    positions = llm.cache_positions(16384, e["max_new_tokens"], e["decode_chunk_steps"])
    assert positions == 18560 <= KW["max_seq_len"]
    cfg = llm.make_config(CONFIG["family"], CONFIG["size"], **KW)
    assert gen.window_positions(2048) == 2176 and gen.summary_rows(cfg, positions) == 1152
    # a part is a window, and a chunk fits the window's slack
    assert llm.PREFILL_PART_TOKENS == KW["window_size"]
    assert e["decode_chunk_steps"] <= gen.window_positions(2048) - 2048
    # 54.5 MB a row a layer; 17 rows x 8 layers = 7.42 GB
    row = (2176 + 1152) * fe.row_bytes(KW)
    assert row == 54_525_952 and 7.4e9 < row * 8 * 17 < 7.45e9
    # every prompt fills a window; the schedule is the same for every seed
    a, b = (traffic_gen.serve_schedule(traffic, seed, 50, 320) for seed in (1, 2))
    assert a["due"] == b["due"] and a["max_new"] == b["max_new"]
    assert min(map(len, a["prompts"])) >= 2048 and max(a["max_new"]) <= 2048


def test_the_benchmark_names_the_cell_and_its_metrics():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL]["config"] == "evabyte-6.5b-pp4"
    assert cells[CELL]["traffic"] == "code-bytes-lognormal-16k"
    assert cells[CELL]["chips"] == 1
    config = next(c for c in BENCH["configs"] if c["name"] == "evabyte-6.5b-pp4")
    assert config["reduced"] == ["num_hidden_layers"]
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in SHARED:
        assert CELL in metrics[name]["workloads"], name
    assert CELL not in metrics["ttft_p95_ms"]["workloads"]
    assert set(NEW) <= set(metrics)
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "tpot_p95_ms"
        assert metrics[name]["layer"] == metrics["model.decode_step_ms"]["layer"]


def _engine(steps, tile_steps, row_steps, read, may):
    return {"cache_tiles": {
        "eva_window_tiles": 40, "eva_summary_tiles": 30, "eva_tile_steps": tile_steps,
        "eva_row_steps": row_steps, "eva_read_positions": read,
        "eva_attendable_positions": may, "eva_rollovers": 2, "eva_chunks_pooled": 256,
        "eva_prefill_windows_pooled": 9, "eva_window_cuts": 3, "eva_steps": steps,
        "eva_dispatches": steps // 16}, "prefill": {}}


def _raw(traced=True):
    zero, load_ = _engine(0, 0, 0, 0, 0), _engine(1600, 48000, 8000, 6_200_000, 6_000_000)
    raw = {"kind": "serve", "engine_before": zero, "engine_after": load_,
           "chunk_steps": 16, "decode_module": "jit__unknown",
           "device": {"kind": "TPU v5 lite"}, "client_records": [
               ({"times": [1.0, 2.0], "done": True}, 8192),
               ({"times": [3.0], "done": True}, 2048)]}
    if traced:
        raw["trace"] = {
            "counters": {"start": zero, "stop": load_},
            "marks": {"start": 0.0}, "window_s": 6.0,
            "modules": {"jit__unknown(1)": {"count": 95, "total_s": 9.0},
                        "jit_llm_decode_cut(2)": {"count": 10, "total_s": 0.5},
                        "jit_llm_prefill(3)": {"count": 2, "total_s": 0.1},
                        "jit_llm_prefill_part(4)": {"count": 3, "total_s": 0.25}},
            "scopes": {"attention.eva_window": 1.5, "attention.eva_summary": 1.0,
                       "attention.eva_merge": 0.1, "attention.eva_pool": 0.05}}
    return raw


def test_the_new_readers_answer_where_the_program_counts_and_not_elsewhere():
    ctx = types.SimpleNamespace(config=CONFIG)
    raw = _raw()
    peak = flops.peaks("TPU v5 lite")
    step = reader(NEW[0]).read(ctx, raw)
    need = (2 * fe.step_params(KW) * 1600 + 8 * 48000 * 128 * 16384) / peak["hbm_bytes_per_s"]
    assert step == pytest.approx(100 * need / 9.5)
    assert 0 < step <= 100
    mfu = reader(NEW[1]).read(ctx, raw)
    assert mfu == pytest.approx(
        100 * fe.prefill_flops(KW, [8192, 2048]) / (0.35 * peak["bf16_flops_per_s"]))
    read = reader(NEW[2]).read(ctx, raw)
    assert read == pytest.approx(
        100 * (8 * 30 * 95 * 16 * 128 * 16384 / peak["hbm_bytes_per_s"]) / 2.6)
    assert reader(NEW[3]).read(ctx, raw) == pytest.approx(100 * 6.2 / 6.0)
    # a program without the counters (the parent): nothing, and no raise
    bare = _raw()
    for ends in (bare["engine_before"], bare["engine_after"],
                 *bare["trace"]["counters"].values()):
        ends["cache_tiles"] = {"read_full": 1, "padded": 2, "flushed": 1}
    assert [reader(n).read(ctx, bare) for n in (NEW[0], NEW[2], NEW[3])] == [None] * 3
    # another family's configuration: nothing
    other = types.SimpleNamespace(config=load("configs", "dots3-note-prev-ep32.json"))
    assert [reader(n).read(other, raw) for n in NEW] == [None] * 4
    # untraced: only the counter's share
    assert [reader(n).read(ctx, _raw(False)) for n in NEW[:3]] == [None] * 3
    # the accepted readers that share the counts' names find nothing to read
    for name in ("model.moe_decode_roofline_pct", "cache.window_read_share_pct",
                 "moe.expert_load_max_over_mean", "cache.selected_read_share_pct",
                 "model.prefill_live_mfu_pct"):
        assert reader(name).read(ctx, raw) is None, name


def test_prefill_flops_count_the_block_diagonal_and_the_summaries():
    one = fe.prefill_flops(KW, [2048])
    per_byte = 2.0 * 8 * fe.layer_params(KW)
    pairs = 2048 * 2049 / 2
    assert one == pytest.approx(
        per_byte * 2048 + 2 * 4096 * 320
        + 8 * (fe.attended_flops(KW) * pairs + 6 * 32 * 128 * 2048))
    # a second window attends its own block and the first's 128 summaries
    two = fe.prefill_flops(KW, [4096])
    assert two - 2 * one == pytest.approx(
        8 * fe.attended_flops(KW) * 128 * 2048 - 2 * 4096 * 320)
