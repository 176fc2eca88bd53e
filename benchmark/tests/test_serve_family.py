"""What the K-EXAONE cell adds to the benchmark: its configuration file
against the published config, the program and the counts; the cell's sizes
against the cache; the traffic file; its entries in BENCHMARK.json; the four
readers on hand-made ``raw``s (a value where the program counts, None where
it does not, as the parent of the PR that added them does not); and the
driver's refusal of a family the program lacks."""

import importlib.util
import json
import os
import types

import pytest

from benchmark import flops, flops_k_exaone as fk, traffic_gen

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "serve-k-exaone-236b-ep8-mixed"
NEW_METRICS = ("model.moe_decode_roofline_pct", "model.prefill_live_mfu_pct",
               "moe.expert_load_max_over_mean", "cache.window_read_share_pct")


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


CONFIG = load("configs", "k-exaone-236b-a23b-ep8.json")
KW = CONFIG["model_config"]
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_configuration_keeps_the_published_widths():
    published = {
        "hidden_size": 6144, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
        "num_shared_experts": 1, "routed_scaling_factor": 2.5,
        "sliding_window": 128, "first_k_dense_replace": 1, "n_group": 1,
        "topk_group": 1, "norm_topk_prob": True, "scoring_func": "sigmoid",
        "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
        "max_position_embeddings": 262144, "model_type": "exaone_moe",
        "hidden_act": "silu", "sliding_window_pattern": "LLLG"}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["rope_parameters"] == {"rope_theta": 1000000, "rope_type": "default"}
    # the nested lists are kept whole (48 layers) and read up to the depth
    assert len(CONFIG["layer_types"]) == len(CONFIG["mlp_layer_types"]) == 48
    assert CONFIG["layer_types"][:5] == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    assert CONFIG["sliding_windows"][:5] == [128, 128, 128, 0, 128]
    # the cut: depth, experts held, vocabulary, no MTP; the published beside
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size", "num_nextn_predict_layers"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"], CONFIG["num_nextn_predict_layers"]) == (
                5, 16, 19200, 0)
    assert CONFIG["published"]["num_experts"] == 128
    assert CONFIG["published"]["vocab_size"] == 153600 == 8 * 19200
    assert "8 chips" in CONFIG["deployment"]
    assert set(CONFIG["changed"]) == set(CONFIG["reduced"])
    for key in ("qk_norm", "rotary", "selection_bias", "window", "norm_placement"):
        assert key in CONFIG["assumed"]
    # no width is reduced: the program's keywords are the published ones
    assert (KW["d_model"], KW["n_heads"], KW["head_dim"], KW["n_kv_heads"],
            KW["d_ff"], KW["d_expert"], KW["n_experts"], KW["experts_per_token"],
            KW["sliding_window"]) == (6144, 64, 128, 8, 18432, 2048, 128, 8, 128)
    assert KW["experts_held"] == [0, 16] and KW["n_layers"] == 5


def test_the_configuration_against_the_catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "K-EXAONE-236B-A23B")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"])


def test_the_program_builds_the_configuration_and_the_counts_agree():
    import jax

    from ray_tpu.serve.llm import _default_init, make_config

    cfg = make_config(CONFIG["family"], CONFIG["size"], **KW)
    assert cfg.sliding_windows == tuple(CONFIG["reference_sizes"]["sliding_windows"])
    assert cfg.experts_held == (0, 16) and cfg.n_experts == 128
    shapes = jax.eval_shape(lambda: _default_init(cfg, 0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == fk.param_count(KW) == 3_712_028_416
    assert all(str(x.dtype) == "bfloat16" for x in jax.tree.leaves(shapes))
    p = fk.parts(KW)
    assert (p["attention"], p["expert"], p["dense_ffn"]) == (
        113_258_752, 37_748_736, 339_738_624)
    # what a decode step reads whatever the routing: 2.36 GB of the 7.42
    assert 2 * fk.always_read_params(KW) == pytest.approx(2.356e9, rel=2e-3)
    ref = CONFIG["reference_sizes"]
    assert (ref["n_heads"], ref["top_k"], ref["first_expert"],
            ref["routed_scale"]) == (cfg.n_heads, cfg.experts_per_token,
                                     cfg.experts_held[0], cfg.routed_scale)


def test_the_cell_fits_its_cache_and_its_traffic():
    import jax

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import cache_positions, make_config

    cell, traffic = load("workloads", CELL + ".json"), load(
        "traffic", "mixed-lognormal-4k.json")
    e = cell["engine"]
    assert cell["kind"] == traffic["kind"] == "serve_family"
    assert traffic["prompt_len"]["max"] <= max(e["prefill_buckets"])
    assert traffic["output_len"]["max"] <= e["max_new_tokens"]
    assert (traffic["prompt_len"]["median"], traffic["output_len"]["median"]) == (512, 128)
    # rows a bucket from the token budget: 32/16/8/4/2/1
    assert [max(1, min(e["n_slots"], e["prefill_token_budget"] // b))
            for b in e["prefill_buckets"]] == [32, 16, 8, 4, 2, 1]
    length = cache_positions(max(e["prefill_buckets"]), e["max_new_tokens"],
                             e["decode_chunk_steps"])
    assert length == 37 * 128
    cfg = make_config(CONFIG["family"], CONFIG["size"], **KW)
    cache = jax.eval_shape(lambda: generate.init_cache(cfg, e["n_slots"] + 1, length))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    # one full layer of 4,736 positions and four rings of 256: 0.78 GB,
    # where five full layers would hold 3.2 GB
    assert nbytes == pytest.approx(0.7786e9, rel=1e-3)
    assert 5 * 2 * cache["k"].size * 2 == pytest.approx(3.2e9, rel=1e-2)
    assert 2 * fk.param_count(KW) + nbytes > 0.5 * 16e9  # over half the chip
    # the fixed trace: the same arrivals and lengths whatever the seed
    a = traffic_gen.serve_schedule(traffic, 1, 50.0, KW["vocab_size"])
    b = traffic_gen.serve_schedule(traffic, 3_200_000_001, 50.0, KW["vocab_size"])
    assert a["max_new"] == b["max_new"] and a["prompts"] != b["prompts"]
    assert [len(p) for p in a["prompts"]] == [len(p) for p in b["prompts"]]
    assert max(max(p) for p in b["prompts"]) < KW["vocab_size"]
    assert max(len(p) for p in a["prompts"]) <= 4096 and min(a["max_new"]) >= 16


def test_benchmark_json_lists_the_cell_and_its_metrics():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert BENCH["workloads"][-1] is entry and entry["chips"] == 1
    assert (entry["config"], entry["traffic"]) == (
        "k-exaone-236b-a23b-ep8", "mixed-lognormal-4k")
    assert 1 <= len(entry["why"]) <= 200
    config = BENCH["configs"][-1]
    assert config["file"] == "benchmark/configs/k-exaone-236b-a23b-ep8.json"
    assert (config["source"], config["reduced"]) == (CONFIG["source"], CONFIG["reduced"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for name in ("serve_tokens_per_s", "tpot_p95_ms"):
        assert e2e[name]["workloads"][-1] == CELL
    # the p95 of this cell's time to first token spreads wider over runs of
    # one tree than a new cell may (PERF.md 7.11): the cell is not judged on
    # it, so no metric that moves it lists the cell
    assert CELL not in e2e["ttft_p95_ms"]["workloads"]
    reported = {name for name, m in e2e.items()
                if CELL in m.get("workloads", [CELL])}
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] in reported, m["name"]
    assert [m["name"] for m in BENCH["per_layer"][-4:]] == list(NEW_METRICS)
    for name in NEW_METRICS:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["layer"] == "Models and kernels"
        assert m["moves"] == "tpot_p95_ms"
        assert reader(name).UNIT == m["unit"]
    assert CELL not in per_layer["model.decode_roofline_pct"]["workloads"]
    for name in ("model.decode_step_ms", "device.idle_pct.serve",
                 "engine.slots_busy_pct", "engine.compiles_in_window"):
        assert per_layer[name]["workloads"][-1] == CELL


def _raw(moe=True):
    """A serve ``raw`` as the driver leaves it, over 100 dispatches of 16
    steps; ``moe=False``: a program without the counters (the parent)."""
    before = {"cache_tiles": {"read": 0, "padded": 0}, "compiles": {"count": 7}}
    after = {"cache_tiles": {"read": 700, "padded": 119}, "compiles": {"count": 7}}
    if moe:
        zeros = [[0] * 16 for _ in range(4)]
        before = {
            "cache_tiles": {"read_full": 0, "read_window": 0, "padded": 0,
                            "layers": {"full": 1, "window": 4}},
            "prefill": {"128": {"calls": 0, "rows": 0, "padded_tokens": 0,
                                "prompts": 0, "live_tokens": 0}},
            "moe": {"prefill": None, "decode": None, "decode_steps": 0}}
        tokens = [[1000] * 15 + [4000] for _ in range(4)]  # one busy expert
        after = {
            "cache_tiles": {"read_full": 8000, "read_window": 6600,
                            "padded": 122100, "layers": {"full": 1, "window": 4}},
            "prefill": {"128": {"calls": 10, "rows": 320, "padded_tokens": 40960,
                                "prompts": 12, "live_tokens": 900}},
            "moe": {"prefill": {"tokens": zeros, "touched": [0] * 4},
                    "decode": {"tokens": tokens, "touched": [12800] * 4},
                    "decode_steps": 1600}}
    records = [({"times": [1.0 + 0.01 * i for i in range(20)]}, 700),
               ({"times": [2.0, 2.1]}, 3000), ({"times": [30.0]}, 100)]
    # the replica's reads at the two ends of the traced interval: a tenth of
    # the window's dispatches, with fewer experts touched than its average
    counters = None
    if moe:
        counters = {"start": before, "stop": {
            "cache_tiles": {"read_full": 800, "read_window": 660,
                            "padded": 12210, "layers": {"full": 1, "window": 4}},
            "moe": {"prefill": {"tokens": zeros, "touched": [0] * 4},
                    "decode": {"tokens": [[100] * 15 + [400] for _ in range(4)],
                               "touched": [960] * 4},  # 6 a layer a step
                    "decode_steps": 160}}}
    return {
        "kind": "serve", "chunk_steps": 16, "decode_module": "jit__unknown",
        "engine_before": before, "engine_after": after,
        "polls": [(10, 0)] * 5, "n_slots": 32,
        "device": {"kind": "TPU v5 lite"}, "client_records": records,
        "records": [],
        "trace": {"marks": {"start": 0.5, "stop": 31.0}, "window_s": 6.0,
                  "counters": counters,
                  "modules": {"jit__unknown(123)": {
                      "count": 30, "total_s": 3.84, "median_s": 0.120},
                      "jit_llm_prefill(77)": {
                          "count": 12, "total_s": 0.9, "median_s": 0.07}}},
    }


def test_window_counts_difference_the_engine_counters():
    counts = fk.window_counts(_raw())
    assert counts["decode_steps"] == 1600
    assert counts["full_tiles_per_step"] == 80.0       # 8000 a 100 dispatches
    assert counts["window_tiles_read_per_step"] == 66.0  # 33 rows x 2 tiles
    assert counts["touched_experts_per_step"] == 32.0  # 8 a layer, 4 layers
    assert counts["held_pairs_per_step"] == 4 * 19000 / 1600
    assert counts["prefill"]["128"]["live_tokens"] == 900
    assert fk.window_counts(_raw(moe=False)) is None


def test_the_new_readers_answer_where_the_program_counts_and_not_elsewhere():
    ctx = types.SimpleNamespace(config=CONFIG)
    raw = _raw()
    # over the TRACED interval (0.5 s to 6.5 s): the two requests decoding in
    # it, one at a time, so 1 live row; the counters between the replica's
    # two reads: 80 live tiles on the full layer (2 at most a row on each
    # ring), 24 experts touched a step where the whole window averaged 32;
    # the MEAN chunk of the interval, 128 ms, not the median
    assert fk.live_rows_between(raw["client_records"], 0.5, 6.5) == 1.0
    assert fk.traced_counts(raw)["touched_experts_per_step"] == 24.0
    share = reader("model.moe_decode_roofline_pct").read(ctx, raw)
    tiles = 80 + 4 * 1 * 2
    need = fk.decode_step_bytes(KW, 24.0, tiles)
    assert need == 2 * (fk.always_read_params(KW) + 24 * 37_748_736
                        + tiles * 2 * 8 * 128 * 128)
    assert share == pytest.approx(100 * (need / 819e9) / 0.008, rel=1e-6)
    assert 0 < share < 100
    # a trace without the replica's two reads (the parent's): nothing
    bare = {**raw, "trace": {**raw["trace"], "counters": None}}
    assert reader("model.moe_decode_roofline_pct").read(ctx, bare) is None
    # the prompts whose first token fell inside the traced interval: 700, 3000
    mfu = reader("model.prefill_live_mfu_pct").read(ctx, raw)
    want = fk.prefill_flops(KW, [700, 3000], 1.0)
    assert mfu == pytest.approx(100 * want / (0.9 * 197e12), rel=1e-6)
    assert 0 < mfu < 100
    # 4000 tokens on the busiest of 16 experts, 19000 in all
    assert reader("moe.expert_load_max_over_mean").read(ctx, raw) == pytest.approx(
        4000 * 16 / 19000)
    # (1 x 80 + 4 x 66) tiles read where five full layers would read 400
    assert reader("cache.window_read_share_pct").read(ctx, raw) == pytest.approx(
        100 * (80 + 264) / 400)
    # a program without the counters, a GPT-2 configuration, a train cell:
    # nothing to read, and nothing raises
    gpt2 = types.SimpleNamespace(config=load("configs", "gpt2-xl.json"))
    for name in NEW_METRICS:
        if name != "model.prefill_live_mfu_pct":  # it reads no counter
            assert reader(name).read(ctx, _raw(moe=False)) is None, name
        assert reader(name).read(gpt2, _raw(moe=False)) is None, name
        assert reader(name).read(gpt2, {"kind": "train"}) is None, name


def test_prefill_flops_count_the_band_not_the_square():
    one = fk.prefill_flops(KW, [4096], 1.0)
    per_pair = 4.0 * 64 * 128
    full = 4096 * 4097 / 2
    band = 128 * 129 / 2 + (4096 - 128) * 128
    matmuls = 2.0 * (fk.token_matmul_params(KW, 1.0) - fk.parts(KW)["head"]) * 4096 \
        + 2.0 * fk.parts(KW)["head"]
    assert one == pytest.approx(matmuls + per_pair * (full + 4 * band))
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_the_driver_refuses_a_family_the_program_lacks(monkeypatch):
    from benchmark.drivers import serve_family

    ctx = types.SimpleNamespace(
        config={**CONFIG, "family": "no_such_family"}, cell={}, traffic={})
    with pytest.raises(SystemExit, match="no model family 'no_such_family'"):
        serve_family.run(ctx)
    # the limits: a share of exact tokens, and a share of the tail
    ref = {"over_margin": 0, "equal": 89, "tokens": 100}
    cell = {"min_exact_share": 0.9, "max_over_margin_share": 0.01}
    assert serve_family.limits_broken(cell, ref) == ["min_exact_share"]
    assert serve_family.limits_broken(cell, {**ref, "over_margin": 1, "equal": 95}) == []
    assert serve_family.limits_broken(cell, {**ref, "over_margin": 2, "equal": 95}) == [
        "max_over_margin_share"]
    assert serve_family.limits_broken(cell, {**ref, "equal": 90}) == []
    # the cell's own: every float32 reading of 55 runs on the chip inside
    # them, the float8_e4m3fn control outside both (PERF.md section 6)
    cell = load("workloads", CELL + ".json")
    assert (cell["logit_tie_margin"], cell["max_over_margin_share"],
            cell["min_exact_share"]) == (0.25, 0.01, 0.95)
    worst_sound = {"tokens": 2563, "equal": 2510, "over_margin": 4}  # seed 3200007006
    control = {"tokens": 2676, "equal": 1874, "over_margin": 145}
    assert serve_family.limits_broken(cell, worst_sound) == []
    assert serve_family.limits_broken(cell, control) == [
        "max_over_margin_share", "min_exact_share"]


def test_scopes_come_from_the_compiled_text():
    from benchmark.drivers import serve_family

    text = '''
  %fusion.866 = bf16[33,18432]{1,0} fusion(%a, %b), kind=kOutput, calls=%fc, metadata={op_name="jit(<unknown>)/while/body/closed_call/dense_ffn/dot_general" stack_frame_id=193}, backend_config={"x":{"y":1}}
  ROOT %ragged-dot-none.3 = bf16[264,2048]{1,0} custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %custom-call.9 = f32[33,192,128]{2,1,0} custom-call(%q), metadata={op_name="jit(<unknown>)/while/body/attention.full/cond/branch_0_fun/ragged_decode_attention/pallas_call"}
  %fusion.12 = f32[33,8,8,256]{3,2,1,0} fusion(%q), kind=kLoop, metadata={op_name="jit(<unknown>)/while/body/attention.window/bkgd,bkds->bkgs/dot_general"}
  %add.3 = f32[33]{0} add(%x, %y), metadata={op_name="jit(<unknown>)/while/body/add"}
  %copy.1 = bf16[2]{0} copy(%z)
'''
    assert serve_family.scopes_of_instructions(text, CONFIG["trace_scopes"]) == {
        "fusion.866": "dense_ffn", "ragged-dot-none.3": "moe.expert_ffn",
        "custom-call.9": "attention.full", "fusion.12": "attention.window"}
