"""What the Kimi-K2.7-Code cell adds to the benchmark: its configuration file
against the published config, the program and the counts; the cell's sizes
against the latent cache; the traffic file; its entries in BENCHMARK.json; the
new roofline reader and the three it shares with K-EXAONE's cell on hand-made
``raw``s (a value where the program counts, None where it does not, as the
parent of the PR that adds the family does not); the kernel's scope found in
compiled text."""

import importlib.util
import json
import os
import types

import pytest

from benchmark import flops, flops_kimi_k2 as fk, traffic_gen

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "serve-kimi-k2.7-code-ep32-code"
NEW_METRIC = "model.latent_decode_attention_roofline_pct"
SHARED = ("serve_tokens_per_s", "tpot_p95_ms", "engine.slots_busy_pct",
          "engine.prefill_interference_pct", "model.decode_step_ms",
          "device.idle_pct.serve", "engine.compiles_in_window",
          "model.moe_decode_roofline_pct", "model.prefill_live_mfu_pct",
          "moe.expert_load_max_over_mean")


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


CONFIG = load("configs", "kimi-k2.7-code-ep32.json")
KW = CONFIG["model_config"]
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_configuration_keeps_the_published_widths():
    published = {
        "hidden_size": 7168, "num_attention_heads": 64,
        "num_key_value_heads": 64, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "intermediate_size": 18432, "moe_intermediate_size": 2048,
        "num_experts_per_tok": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 2.827, "first_k_dense_replace": 1,
        "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "rms_norm_eps": 1e-05, "rope_theta": 50000,
        "tie_word_embeddings": False, "max_position_embeddings": 262144,
        "model_type": "kimi_k2", "hidden_act": "silu",
        "num_nextn_predict_layers": 0}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    # the cut: depth, experts held, vocabulary; the published beside
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (6, 12, 20480)
    assert CONFIG["published"]["n_routed_experts"] == 384 == 32 * 12
    assert CONFIG["published"]["vocab_size"] == 163840 == 8 * 20480
    assert CONFIG["published"]["num_hidden_layers"] == 61
    assert "32 chips" in CONFIG["deployment"]
    assert "4,173,177,728 parameters" in CONFIG["deployment"]
    assert set(CONFIG["reduced"]) < set(CONFIG["changed"])
    assert "tower" in CONFIG["changed"]["modality"]
    for key in ("rotary_pairs", "selection_bias", "dtype", "weights",
                "norm_placement", "kv_b_proj"):
        assert key in CONFIG["assumed"]
    # no width is reduced: the program's keywords are the published ones
    assert (KW["d_model"], KW["n_heads"], KW["q_lora_rank"], KW["kv_lora_rank"],
            KW["qk_nope_head_dim"], KW["qk_rope_head_dim"], KW["v_head_dim"],
            KW["d_ff"], KW["d_expert"], KW["n_experts"], KW["experts_per_token"],
            KW["routed_scale"]) == (
                7168, 64, 1536, 512, 128, 64, 128, 18432, 2048, 384, 8, 2.827)
    assert (KW["rope_base"], KW["rope_factor"], KW["rope_original_positions"],
            KW["rope_beta_fast"], KW["rope_beta_slow"], KW["rope_mscale"],
            KW["rope_mscale_all_dim"]) == (50000.0, 64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert KW["experts_held"] == [0, 12] and KW["n_layers"] == 6


def test_the_configuration_against_the_catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "Kimi-K2.7-Code")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"])


def test_the_program_builds_the_configuration_and_the_counts_agree():
    import jax

    from ray_tpu.serve.llm import _default_init, make_config

    cfg = make_config(CONFIG["family"], CONFIG["size"], **KW)
    assert cfg.experts_held == (0, 12) and cfg.n_experts == 384
    assert cfg.latent_cache == (fk.latent_row(KW), KW["kv_lora_rank"]) == (576, 512)
    shapes = jax.eval_shape(lambda: _default_init(cfg, 0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == fk.param_count(KW) == 4_173_177_728
    assert all(str(x.dtype) == "bfloat16" for x in jax.tree.leaves(shapes))
    p = fk.parts(KW)
    assert (p["attention"], p["expert"], p["dense_ffn"], p["router"]) == (
        101_138_432, 44_040_192, 396_361_728, 2_752_896)
    # what a decode step reads whatever the routing: 2.77 GB of the 8.35,
    # 1.21 GB of them the six layers' latent-attention projections
    assert 2 * fk.always_read_params(KW) == pytest.approx(2.768e9, rel=1e-3)
    assert 2 * 6 * p["attention"] == pytest.approx(1.214e9, rel=1e-3)
    assert fk.attended_position_flops(KW) == 139_264
    assert fk.tile_bytes(KW) == 147_456
    ref = CONFIG["reference_sizes"]
    assert (ref["n_heads"], ref["top_k"], ref["first_expert"], ref["routed_scale"],
            ref["qk_nope_head_dim"], ref["qk_rope_head_dim"]) == (
        cfg.n_heads, cfg.experts_per_token, cfg.experts_held[0],
        cfg.routed_scale, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim)
    assert ref["rope_scaling"] == CONFIG["rope_scaling"]
    assert ref["rope_theta"] == cfg.rope_base == CONFIG["rope_theta"]


def test_the_cell_fits_its_cache_and_its_traffic():
    import jax

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import cache_positions, call_rows, make_config

    cell, traffic = load("workloads", CELL + ".json"), load(
        "traffic", "code-lognormal-8k.json")
    e = cell["engine"]
    assert (cell["config"], cell["traffic"]) == (CONFIG["name"], "code-lognormal-8k")
    assert cell["kind"] == traffic["kind"] == "serve_family"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                     "sigma": 0.8, "min": 256, "max": 8192}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 256,
                                     "sigma": 0.7, "min": 32, "max": 1024}
    assert traffic["preroll_s"] == 5
    assert traffic["prompt_len"]["max"] <= max(e["prefill_buckets"])
    assert traffic["output_len"]["max"] <= e["max_new_tokens"]
    assert e["prefill_buckets"] == [256, 512, 1024, 2048, 4096, 8192]
    assert (e["n_slots"], e["decode_chunk_steps"], e["prefill_token_budget"]) == (
        32, 16, 8192)
    assert [call_rows(b, e["n_slots"]) for b in e["prefill_buckets"]] == [1] * 6
    length = cache_positions(max(e["prefill_buckets"]), e["max_new_tokens"],
                             e["decode_chunk_steps"])
    assert length == 73 * 128 == 9344
    cfg = make_config(CONFIG["family"], CONFIG["size"], **KW)
    cache = jax.eval_shape(lambda: generate.init_cache(cfg, e["n_slots"] + 1, length))
    # ONE tensor: a 576-value row a position a layer, 2.13 GB for 33 rows
    assert set(cache) == {"c", "pos"}
    assert cache["c"].shape == (6, 33, 1, 576, 9344)
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert nbytes == pytest.approx(2.131e9, rel=1e-3)
    assert 2 * fk.param_count(KW) + nbytes > 0.6 * 16e9  # 65 % of the chip
    # the rate is a share of the swept sustained rate, both in the file
    rate = traffic["arrivals"]["rate_per_s"]
    assert f"{rate:g} req/s" in traffic["why"] and "sustain" in traffic["why"]
    # the fixed trace: the same arrivals and lengths whatever the seed
    a = traffic_gen.serve_schedule(traffic, 1, 50.0, KW["vocab_size"])
    b = traffic_gen.serve_schedule(traffic, 3_600_000_001, 50.0, KW["vocab_size"])
    assert a["max_new"] == b["max_new"] and a["prompts"] != b["prompts"]
    assert [len(p) for p in a["prompts"]] == [len(p) for p in b["prompts"]]
    assert max(max(p) for p in b["prompts"]) < KW["vocab_size"]
    assert 256 <= min(len(p) for p in a["prompts"])
    assert max(len(p) for p in a["prompts"]) <= 8192 and min(a["max_new"]) >= 32


def test_benchmark_json_lists_the_cell_and_its_metrics():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert BENCH["workloads"][-1] is entry and entry["chips"] == 1
    assert (entry["config"], entry["traffic"]) == (
        "kimi-k2.7-code-ep32", "code-lognormal-8k")
    assert 1 <= len(entry["why"]) <= 200
    config = BENCH["configs"][-1]
    assert config["file"] == "benchmark/configs/kimi-k2.7-code-ep32.json"
    assert (config["source"], config["reduced"]) == (CONFIG["source"], CONFIG["reduced"])
    assert 1 <= len(config["why"]) <= 200
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SHARED:
        assert (e2e.get(name) or per_layer[name])["workloads"][-1] == CELL, name
    # not judged on the time to first token (PERF.md 7.11), so no metric
    # that moves it lists the cell; and no window layer, no window share
    assert CELL not in e2e["ttft_p95_ms"]["workloads"]
    for m in BENCH["per_layer"]:
        if m["moves"] == "ttft_p95_ms":
            assert CELL not in m.get("workloads", []), m["name"]
    assert CELL not in per_layer["cache.window_read_share_pct"]["workloads"]
    assert CELL not in per_layer["model.decode_roofline_pct"]["workloads"]
    reported = {name for name, m in e2e.items()
                if CELL in m.get("workloads", [CELL])}
    assert reported == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] in reported, m["name"]
    new = BENCH["per_layer"][-1]
    assert new == {"name": NEW_METRIC, "unit": "%", "better": "higher",
                   "source": "device_trace", "layer": "Models and kernels",
                   "moves": "tpot_p95_ms", "workloads": [CELL]}
    assert reader(NEW_METRIC).UNIT == "%"


def _raw(latent=True):
    """A serve ``raw`` as the driver leaves it, over 100 dispatches of 16
    steps of six latent layers; ``latent=False``: a trace without the
    kernel's row (any other program)."""
    zeros = [[0] * 12 for _ in range(5)]
    layers = {"full": 6, "window": 0}
    tiles = {"full": 147_456, "window": 0}
    before = {
        "cache_tiles": {"read_full": 0, "read_window": 0, "padded": 0,
                        "layers": layers, "tile_bytes": tiles},
        "prefill": {"2048": {"calls": 0, "rows": 0, "padded_tokens": 0,
                             "prompts": 0, "live_tokens": 0}},
        "moe": {"prefill": None, "decode": None, "decode_steps": 0},
        "compiles": {"count": 9}}
    tokens = [[300] * 11 + [900] for _ in range(5)]  # one busy expert
    after = {
        "cache_tiles": {"read_full": 15000, "read_window": 0, "padded": 240900,
                        "layers": layers, "tile_bytes": tiles},
        "prefill": {"2048": {"calls": 10, "rows": 10, "padded_tokens": 20480,
                             "prompts": 10, "live_tokens": 15000}},
        "moe": {"prefill": {"tokens": zeros, "touched": [0] * 5},
                "decode": {"tokens": tokens, "touched": [3200] * 5},
                "decode_steps": 1600},
        "compiles": {"count": 9}}
    records = [({"times": [1.0 + 0.01 * i for i in range(20)]}, 2000),
               ({"times": [2.0, 2.1]}, 8000), ({"times": [30.0]}, 300)]
    # the replica's reads at the two ends of the traced interval: a tenth of
    # the window's dispatches, 160 live tiles a layer a step
    counters = {"start": before, "stop": {
        "cache_tiles": {"read_full": 1600, "read_window": 0, "padded": 24090,
                        "layers": layers, "tile_bytes": tiles},
        "moe": {"prefill": {"tokens": zeros, "touched": [0] * 5},
                "decode": {"tokens": [[30] * 11 + [90] for _ in range(5)],
                           "touched": [240] * 5},  # 1.5 a layer a step
                "decode_steps": 160}}}
    scopes = {"attention.mla_proj": 0.4, "moe.expert_ffn": 0.2}
    if latent:
        scopes["ragged_latent_decode_attention"] = 0.096
    return {
        "kind": "serve", "chunk_steps": 16, "decode_module": "jit__unknown",
        "engine_before": before, "engine_after": after,
        "polls": [(7, 0)] * 5, "n_slots": 32,
        "device": {"kind": "TPU v5 lite"}, "client_records": records,
        "records": [],
        "trace": {"marks": {"start": 0.5, "stop": 31.0}, "window_s": 6.0,
                  "counters": counters, "scopes": scopes,
                  "modules": {"jit__unknown(123)": {
                      "count": 10, "total_s": 1.12, "median_s": 0.110},
                      "jit_llm_prefill(77)": {
                          "count": 3, "total_s": 0.5, "median_s": 0.07}}},
    }


def test_the_kernels_roofline_reads_its_scope_and_the_traced_tiles():
    ctx = types.SimpleNamespace(config=CONFIG)
    raw = _raw()
    counts = fk.traced_counts(raw)
    assert (counts["full_tiles_per_step"], counts["decode_steps"]) == (160.0, 160)
    # 160 tiles a layer a step x 6 layers x 160 steps, each copied in once:
    # 147,456 bytes against 128 x 139,264 FLOPs, so the bytes bound it
    reads = 160 * 6 * 160
    assert fk.latent_attention_least(KW, reads, flops.peaks("TPU v5 lite")) == \
        pytest.approx(reads * 147_456 / 819e9)
    assert reads * 128 * 139_264 / 197e12 < reads * 147_456 / 819e9
    share = reader(NEW_METRIC).read(ctx, raw)
    assert share == pytest.approx(100 * (reads * 147_456 / 819e9) / 0.096)
    assert 0 < share < 100
    # no such row in the trace, no counters at the trace's ends, another
    # family's configuration, a GPT-2 one, a train cell: nothing, no raise
    assert reader(NEW_METRIC).read(ctx, _raw(latent=False)) is None
    bare = _raw()
    bare["trace"]["counters"] = None
    assert reader(NEW_METRIC).read(ctx, bare) is None
    exaone = types.SimpleNamespace(config=load(
        "configs", "k-exaone-236b-a23b-ep8.json"))
    assert reader(NEW_METRIC).read(exaone, _raw()) is None  # its counts: no latent tile
    gpt2 = types.SimpleNamespace(config=load("configs", "gpt2-xl.json"))
    assert reader(NEW_METRIC).read(gpt2, _raw()) is None
    assert reader(NEW_METRIC).read(gpt2, {"kind": "train", "trace": {}}) is None
    assert reader(NEW_METRIC).read(ctx, {"kind": "train"}) is None


def test_the_shared_readers_answer_for_a_configuration_with_no_window_layer():
    """``model.moe_decode_roofline_pct`` (the whole step's share, through
    this family's counts), ``model.prefill_live_mfu_pct`` and
    ``moe.expert_load_max_over_mean`` as they are, on this cell."""
    ctx = types.SimpleNamespace(config=CONFIG)
    raw = _raw()
    assert fk.live_rows_between(raw["client_records"], 0.5, 6.5) == 1.0
    share = reader("model.moe_decode_roofline_pct").read(ctx, raw)
    tiles = 6 * 160.0  # six full layers, no window layer
    need = fk.decode_step_bytes(KW, 7.5, tiles)
    assert need == 2 * (fk.always_read_params(KW) + 7.5 * 44_040_192) + tiles * 147_456
    assert share == pytest.approx(100 * (need / 819e9) / 0.007, rel=1e-6)
    assert 0 < share < 100
    mfu = reader("model.prefill_live_mfu_pct").read(ctx, raw)
    want = fk.prefill_flops(KW, [2000, 8000], 8 * 12 / 384)
    assert mfu == pytest.approx(100 * want / (0.5 * 197e12), rel=1e-6)
    assert 0 < mfu < 100
    assert reader("moe.expert_load_max_over_mean").read(ctx, raw) == pytest.approx(
        900 * 12 / 4200)


def test_prefill_flops_count_the_unabsorbed_pairs():
    one = fk.prefill_flops(KW, [8192], 0.25)
    per_pair = 4.0 * 64 * 160
    matmuls = 2.0 * (fk.token_matmul_params(KW, 0.25) - fk.parts(KW)["head"]) * 8192 \
        + 2.0 * fk.parts(KW)["head"]
    assert one == pytest.approx(matmuls + 6 * per_pair * 8192 * 8193 / 2)
    # a decode step: 2 FLOPs a matmul parameter a live row, 139,264 a position
    assert fk.decode_step_flops(KW, 2.0, 0.25, 1000.0) == pytest.approx(
        2.0 * fk.token_matmul_params(KW, 0.25) * 2.0 + 139_264 * 1000.0)


def test_the_kernels_scope_comes_from_the_compiled_text():
    from benchmark.drivers import serve_family

    text = '''
  %ragged_latent_decode_attention.3 = (f32[33,64,512]{2,1,0}, f32[33,64,128]{2,1,0}, f32[33,64,128]{2,1,0}) custom-call(%a, %b, %q, %c), custom_call_target="tpu_custom_call", metadata={op_name="jit(<unknown>)/while/body/attention.latent/cond/branch_0_fun/ragged_latent_decode_attention/pallas_call" stack_frame_id=16}
  %fusion.40 = f32[33,1,64,16]{3,2,1,0} fusion(%q), kind=kLoop, metadata={op_name="jit(<unknown>)/while/body/attention.latent/bkgd,tbkd->bkgt/dot_general"}
  %fusion.41 = bf16[33,64,1,576]{3,2,1,0} fusion(%q), kind=kOutput, metadata={op_name="jit(<unknown>)/while/body/attention.mla_proj/bhtd,hdc->bhtc/dot_general"}
  %ragged-dot-none.3 = bf16[264,2048]{1,0} custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %copy.1 = bf16[2]{0} copy(%z)
'''
    assert list(CONFIG["trace_scopes"])[0] == "ragged_latent_decode_attention"
    assert serve_family.scopes_of_instructions(text, CONFIG["trace_scopes"]) == {
        "ragged_latent_decode_attention.3": "ragged_latent_decode_attention",
        "fusion.40": "attention.latent", "fusion.41": "attention.mla_proj",
        "ragged-dot-none.3": "moe.expert_ffn"}
    ctx = types.SimpleNamespace(
        config={**CONFIG, "family": "no_such_family"}, cell={}, traffic={})
    with pytest.raises(SystemExit, match="no model family 'no_such_family'"):
        serve_family.run(ctx)
