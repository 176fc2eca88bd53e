"""What the Phi-4-mini-flash cell adds to the benchmark: its configuration
file against the catalog, the program and the counts; the cell's sizes
against the cache's kinds; its entries in BENCHMARK.json; the new readers on
hand-made ``raw``s (a value where the program counts, None where it does not,
as the parent of the PR that adds the family does not); the rehearsal cell on
the CPU."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import flops, flops_phi4_flash as fp, traffic_gen

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "serve-phi-4-mini-flash-reasoning-docs"
NEW = ("model.sambay_decode_roofline_pct",
       "model.shared_kv_decode_attention_roofline_pct",
       "model.selective_state_update_roofline_pct",
       "model.sambay_prefill_live_mfu_pct",
       "prefill.upper_stack_positions_share_pct")
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


CONFIG = load("configs", "phi-4-mini-flash-reasoning.json")
KW = CONFIG["model_config"]
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_files_are_found_by_name():
    cell = load("workloads", CELL + ".json")
    assert cell["config"] == CONFIG["name"] == "phi-4-mini-flash-reasoning"
    assert cell["traffic"] == "docs-reason-lognormal-16k" and cell["chips"] == 1
    assert cell["kind"] == load("traffic", cell["traffic"] + ".json")["kind"] == "serve_family"
    for key in ("reference_module", "counts_module"):
        assert importlib.util.find_spec(CONFIG[key]) is not None
    for name in NEW:
        assert os.path.exists(os.path.join(HERE, "layer_metrics", name + ".py"))
    tiny = load("workloads", "rehearse-serve-phi4-flash-tiny.json")
    assert load("configs", tiny["config"] + ".json")["family"] == "phi4_flash"


def test_the_configuration_against_the_catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "Phi-4-mini-flash-reasoning")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == set()  # NOTHING is reduced


def test_the_configuration_keeps_the_published_sizes():
    assert (KW["n_layers"], KW["d_model"], KW["n_heads"], KW["n_kv_heads"],
            KW["d_ff"], KW["sliding_window"], KW["vocab_size"]) == (
                32, 2560, 40, 20, 10240, 512, 200064)
    assert (CONFIG["num_hidden_layers"], CONFIG["hidden_size"],
            CONFIG["intermediate_size"], CONFIG["vocab_size"]) == (
                32, 2560, 10240, 200064)
    for key in ("mamba", "attention", "layers", "positions", "prefill", "dtype",
                "weights"):
        assert key in CONFIG["assumed"]
    assert "WHOLE" in CONFIG["deployment"]
    assert set(CONFIG["trace_scopes"]) >= {
        "ssm.in_proj", "ssm.conv", "ssm.selective_scan",
        "ssm.selective_state_update", "ssm.out_proj", "gmu",
        "attention.diff_window", "attention.shared_kv", "attention.diff_combine",
        "head"}
    assert CONFIG["reference_sizes"]["sliding_window"] == KW["sliding_window"]


def test_the_program_builds_the_configuration_and_the_counts_agree():
    import jax

    from ray_tpu.serve.llm import _default_init, make_config

    cfg = make_config(CONFIG["family"], CONFIG["size"], **KW)
    shapes = jax.eval_shape(lambda: _default_init(cfg, 0))
    held = sum(int(a.size) for a in jax.tree.leaves(shapes))
    # the card's 3.8 B, by hand: 9 x 119.90 M + 9 x 98.32 M + 7 x 91.77 M +
    # 7 x 104.87 M + the 512.16 M tied embedding + the final norm
    assert held == fp.param_count(KW) == 3_852_562_944
    assert [fp.layer_params(KW, k) for k in ("mamba", "window", "cross", "gmu")] == [
        119_895_040, 98_322_304, 91_766_144, 104_867_840]
    assert fp.kinds(KW) == list(cfg.layer_types) and fp.slab_readers(KW) == 8
    assert fp.position_bytes(KW) == 5120
    assert fp.state_row_bytes(KW) == 5120 * 16 * 4 + 3 * 5120 * 2
    # a part that ends no prompt: 7.67 TFLOP of matmuls where every layer at
    # every position would be 13.68 (the vocabulary apart)
    assert fp.part_flops(KW) == pytest.approx(7.67e12, rel=5e-3)
    every = 2.0 * (fp.param_count(KW) - 200064 * 2560) * 2048
    assert every == pytest.approx(13.68e12, rel=5e-3)
    assert fp.part_flops(KW) / every == pytest.approx(0.56, abs=0.01)


def test_the_cell_fits_its_engine_and_its_traffic():
    from ray_tpu.models import generate as gen
    from ray_tpu.serve import llm

    cell, traffic = load("workloads", CELL + ".json"), load(
        "traffic", "docs-reason-lognormal-16k.json")
    e = cell["engine"]
    assert (e["n_slots"], e["decode_chunk_steps"], e["max_new_tokens"]) == (32, 16, 2048)
    assert e["prefill_buckets"] == [2048, 4096, 8192, 16384]
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                     "sigma": 0.8, "min": 1024, "max": 16384}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 512,
                                     "sigma": 0.7, "min": 64, "max": 2048}
    assert traffic["arrivals"]["process"] == "poisson" and traffic["preroll_s"] == 10
    positions = llm.cache_positions(16384, e["max_new_tokens"], e["decode_chunk_steps"])
    assert positions == 18560 <= KW["max_seq_len"]
    cfg = llm.make_config(CONFIG["family"], CONFIG["size"], **KW)
    assert gen.can_continue(cfg) and gen.shared_cache(cfg) == 17
    assert gen.ring_positions(512) == 1024
    # a slot: ONE slab (95.0 MB), eight rings (41.9 MB), nine states (3.2 MB)
    slab = positions * fp.position_bytes(KW)
    rings = 8 * 1024 * fp.position_bytes(KW)
    states = 9 * fp.state_row_bytes(KW)
    assert (slab, rings) == (95_027_200, 41_943_040) and 3.2e6 < states < 3.3e6
    assert 4.6e9 < 33 * (slab + rings + states) < 4.65e9
    a, b = (traffic_gen.serve_schedule(traffic, seed, 50, 200064) for seed in (1, 2))
    assert a["due"] == b["due"] and a["max_new"] == b["max_new"]
    assert min(map(len, a["prompts"])) >= 1024 and max(a["max_new"]) <= 2048
    assert max(max(p) for p in a["prompts"]) > 150_000  # the whole vocabulary


def test_the_benchmark_names_the_cell_and_its_metrics():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL]["config"] == "phi-4-mini-flash-reasoning"
    assert cells[CELL]["traffic"] == "docs-reason-lognormal-16k"
    assert cells[CELL]["chips"] == 1
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "phi-4-mini-flash-reasoning")
    assert config["reduced"] == [] and config["source"] == CONFIG["source"]
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in SHARED:
        assert CELL in metrics[name]["workloads"], name
    assert CELL not in metrics["ttft_p95_ms"]["workloads"]
    for name in ("model.moe_decode_roofline_pct", "model.prefill_live_mfu_pct",
                 "moe.expert_load_max_over_mean"):
        assert CELL not in metrics[name]["workloads"]
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "tpot_p95_ms"
        assert metrics[name]["layer"] == metrics["model.decode_step_ms"]["layer"]


def _engine(steps, slab, ring, state, rows, prefilled, upper):
    return {"cache_tiles": {
        "yoco_slab_tile_steps": slab, "yoco_ring_tile_steps": ring,
        "yoco_state_row_steps": state, "yoco_row_steps": rows,
        "yoco_steps": steps, "yoco_dispatches": steps // 16,
        "yoco_prefill_positions": prefilled, "yoco_upper_positions": upper},
        "prefill": {}}


def _raw(traced=True):
    zero = _engine(0, 0, 0, 0, 0, 0, 0)
    # 1,600 steps, 6 live rows of ~47 tiles: 47 x 8 readers x 6 rows a step
    load_ = _engine(1600, 1600 * 2256, 1600 * 384, 1600 * 54, 1600 * 6,
                    140_000, 32)
    raw = {"kind": "serve", "engine_before": zero, "engine_after": load_,
           "chunk_steps": 16, "decode_module": "jit__unknown",
           "device": {"kind": "TPU v5 lite"}, "client_records": [
               ({"times": [1.0, 2.0], "done": True}, 8192),
               ({"times": [3.0], "done": True}, 1500)]}
    if traced:
        raw["trace"] = {
            "counters": {"start": zero, "stop": load_},
            "marks": {"start": 0.0}, "window_s": 6.0,
            "modules": {"jit__unknown(1)": {"count": 95, "total_s": 19.0},
                        "jit_llm_decode_cut(2)": {"count": 10, "total_s": 1.0},
                        "jit_llm_prefill(3)": {"count": 2, "total_s": 0.2},
                        "jit_llm_prefill_part(4)": {"count": 3, "total_s": 0.3}},
            "scopes": {"attention.shared_kv": 3.0,
                       "ssm.selective_state_update": 0.2}}
    return raw


def test_the_new_readers_answer_where_the_program_counts_and_not_elsewhere():
    ctx = types.SimpleNamespace(config=CONFIG)
    raw = _raw()
    peak = flops.peaks("TPU v5 lite")
    step = reader(NEW[0]).read(ctx, raw)
    need = (2 * fp.param_count(KW) * 1600
            + 1600 * (2256 + 384) * 128 * 5120
            + 2 * 1600 * 54 * fp.state_row_bytes(KW)) / peak["hbm_bytes_per_s"]
    assert step == pytest.approx(100 * need / 20.0)
    assert 0 < step <= 100
    read = reader(NEW[1]).read(ctx, raw)
    assert read == pytest.approx(
        100 * (2256 * 95 * 16 * 128 * 5120 / peak["hbm_bytes_per_s"]) / 3.0)
    assert 0 < read <= 100
    state = reader(NEW[2]).read(ctx, raw)
    assert state == pytest.approx(
        100 * (2 * 54 * 95 * 16 * fp.state_row_bytes(KW)
               / peak["hbm_bytes_per_s"]) / 0.2)
    assert 0 < state <= 100
    mfu = reader(NEW[3]).read(ctx, raw)
    assert mfu == pytest.approx(
        100 * fp.prefill_flops(KW, [8192, 1500]) / (0.5 * peak["bf16_flops_per_s"]))
    assert reader(NEW[4]).read(ctx, raw) == pytest.approx(100 * 32 / 140_000)
    # a program without the counters (the parent): nothing, and no raise
    bare = _raw()
    for ends in (bare["engine_before"], bare["engine_after"],
                 *bare["trace"]["counters"].values()):
        ends["cache_tiles"] = {"read_full": 1, "padded": 2, "flushed": 1}
    assert [reader(n).read(ctx, bare) for n in (NEW[0], NEW[1], NEW[2], NEW[4])
            ] == [None] * 4
    # a trace without the scopes: the two scope readers find nothing
    bare = _raw()
    bare["trace"]["scopes"] = {}
    assert [reader(n).read(ctx, bare) for n in NEW[1:3]] == [None] * 2
    # another family's configuration: nothing
    other = types.SimpleNamespace(config=load("configs", "evabyte-6.5b-pp4.json"))
    assert [reader(n).read(other, raw) for n in NEW] == [None] * 5
    # untraced: only the counter's share
    assert [reader(n).read(ctx, _raw(False)) for n in NEW[:4]] == [None] * 4
    assert reader(NEW[4]).read(ctx, _raw(False)) is not None
    # the accepted readers that share the counts' names find nothing to read
    for name in ("model.moe_decode_roofline_pct", "cache.window_read_share_pct",
                 "moe.expert_load_max_over_mean", "cache.selected_read_share_pct",
                 "model.prefill_live_mfu_pct", "model.eva_decode_roofline_pct",
                 "cache.eva_read_share_pct"):
        assert reader(name).read(ctx, raw) is None, name


def test_prefill_flops_count_the_early_exit():
    one = fp.prefill_flops(KW, [2048])
    lower, every = fp.lower_params(KW), fp.param_count(KW)
    pairs = (2048 - 512) * 512 + 512 * 513 / 2
    assert one == pytest.approx(
        2.0 * lower * 2048 + 8 * fp.attended_flops(KW) * pairs
        + 2.0 * (every - lower) + 8 * fp.attended_flops(KW) * 2048)
    # every further position costs the lower half and 512 keys a window layer
    two = fp.prefill_flops(KW, [4096])
    assert two - one == pytest.approx(
        2048 * (2.0 * lower + 8 * fp.attended_flops(KW) * 512)
        + 8 * fp.attended_flops(KW) * 2048)


def test_the_rehearsal_cell_runs_on_the_cpu_and_is_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "rehearse-serve-phi4-flash-tiny", "--seed", "2200000011", "--seconds",
         "5", "--trace", "0"], capture_output=True, text=True, timeout=600,
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    counts = line["detail"]["window_counts"]
    assert counts["upper_positions"] == sum(
        row["prompts"] for row in counts["prefill"].values())
    assert counts["prefill_positions"] == sum(
        row["live_tokens"] for row in counts["prefill"].values())
