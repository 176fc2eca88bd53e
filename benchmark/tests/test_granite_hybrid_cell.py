"""What the Granite-4.0-H-Small cell adds to the benchmark: its configuration
file against the catalog, the program and the counts; the cell's sizes against
the cache of states; the traffic file; its entries in BENCHMARK.json; the two
new readers and the whole step's roofline reader on hand-made ``raw``s (a value
where the program counts, None where it does not, as the parent of the PR that
adds the family does not); the scopes found in compiled text."""

import importlib.util
import json
import os
import types

import pytest

from benchmark import flops, flops_granite_hybrid as fg, traffic_gen

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "serve-granite-4.0-h-small-ep8-chat"
ROOFLINE = "model.ssm_state_update_roofline_pct"
SHARE = "cache.state_rows_updated_share_pct"
SHARED = ("serve_tokens_per_s", "tpot_p95_ms", "engine.slots_busy_pct",
          "engine.prefill_interference_pct", "engine.compiles_in_window",
          "engine.tpot_p95_ms", "engine.chunk_steps_per_gap",
          "engine.decode_prefill_wait_pct", "engine.decode_tick_ms",
          "engine.tick_host_ms", "replica.tpot_p95_ms", "model.decode_step_ms",
          "device.idle_pct.serve", "model.moe_decode_roofline_pct",
          "model.prefill_live_mfu_pct", "moe.expert_load_max_over_mean",
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


CONFIG = load("configs", "granite-4.0-h-small-ep8.json")
KW = CONFIG["model_config"]
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_configuration_keeps_the_published_widths():
    published = {
        "hidden_size": 4096, "mamba_n_heads": 128, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_chunk_size": 256,
        "mamba_n_groups": 1, "mamba_expand": 2, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "num_attention_heads": 32,
        "num_key_value_heads": 8, "intermediate_size": 768,
        "shared_intermediate_size": 1536, "num_experts_per_tok": 10,
        "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
        "logits_scaling": 16, "residual_multiplier": 0.22,
        "position_embedding_type": "nope", "tie_word_embeddings": True,
        "rms_norm_eps": 1e-05, "model_type": "granitemoehybrid"}
    assert {k: CONFIG[k] for k in published} == published
    assert [l for l, t in enumerate(CONFIG["layer_types"]) if t == "attention"] == [
        5, 15, 25, 35] and len(CONFIG["layer_types"]) == 40
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_local_experts",
                                 "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_local_experts"],
            CONFIG["vocab_size"]) == (20, 9, 12544)
    assert CONFIG["published"]["num_local_experts"] == 72 == 8 * 9
    assert CONFIG["published"]["vocab_size"] == 100352 == 8 * 12544
    assert CONFIG["published"]["num_hidden_layers"] == 40
    assert "16 v5e chips" in CONFIG["deployment"] and "8 chips share" in CONFIG["deployment"]
    assert "4,058,678,528 parameters" in CONFIG["deployment"]
    assert set(CONFIG["reduced"]) == set(CONFIG["changed"])
    for key in ("expert_width", "projection_order", "learned_vectors",
                "state_dtype", "weights", "norm_placement", "dtype"):
        assert key in CONFIG["assumed"]
    assert "float32" in CONFIG["assumed"]["state_dtype"]
    # no width is reduced: the program's keywords are the published ones
    assert (KW["d_model"], KW["mamba_heads"], KW["mamba_head_dim"],
            KW["mamba_state"], KW["mamba_conv"], KW["mamba_chunk"]) == (
        4096, 128, 64, 128, 4, 256)
    assert (KW["n_heads"], KW["n_kv_heads"], KW["head_dim"], KW["d_expert"],
            KW["d_shared"], KW["n_experts"], KW["experts_per_token"]) == (
        32, 8, 128, 768, 1536, 72, 10)
    assert (KW["embedding_multiplier"], KW["logits_scaling"],
            KW["residual_multiplier"], KW["attention_multiplier"]) == (
        12.0, 16.0, 0.22, 0.0078125)
    assert KW["experts_held"] == [0, 9] and KW["n_layers"] == 20
    assert KW["layer_types"] == CONFIG["layer_types"][:20]


def test_the_configuration_against_the_catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "granite-4.0-h-small")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"])


def test_the_program_builds_the_configuration_and_the_counts_agree():
    import jax

    from ray_tpu.serve.llm import _default_init, make_config

    cfg = make_config(CONFIG["family"], CONFIG["size"], **KW)
    assert cfg.experts_held == (0, 9) and cfg.n_experts == 72
    shapes = jax.eval_shape(lambda: _default_init(cfg, 0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == fg.param_count(KW) == 4_058_678_528  # 4,058.7 M
    assert all(str(x.dtype) == "bfloat16" for x in jax.tree.leaves(shapes))
    p = fg.parts(KW)
    assert (p["mamba"], p["attention"], p["expert"]) == (
        102_286_976, 41_943_040, 9_437_184)
    assert p["norms"] + p["router"] + p["shared"] == 19_177_472
    assert (fg.mamba_layers(KW), fg.attention_layers(KW)) == (18, 2)
    # what a decode step reads whatever the routing: 4.72 GB of the 8.12
    assert 2 * fg.always_read_params(KW) == pytest.approx(4.72e9, rel=1e-3)
    # a row of a Mamba layer: 4,194,304 B of float32 state + 50,688 of inputs
    assert fg.state_row_bytes(KW) == 4_244_992 and fg.state_values(KW) == 1 << 20
    # 12 live rows, all 180 held experts touched: the issue's ~12 ms
    need = fg.decode_step_bytes(KW, fg.Touched(180.0, 12.0), 2 * 12 * 4.0)
    assert need == pytest.approx(4.72e9 + 3.40e9 + 1.83e9 + 96 * 524288, rel=2e-3)
    assert need / 819e9 == pytest.approx(12.2e-3, rel=0.02)
    # without the state it is what the other two families count
    assert need - fg.decode_step_bytes(KW, 180.0, 96.0) == 2 * 12 * 18 * 4_244_992
    ref = CONFIG["reference_sizes"]
    assert (ref["n_heads"], ref["n_kv_heads"], ref["mamba_heads"],
            ref["mamba_state"], ref["top_k"], ref["first_expert"]) == (
        cfg.n_heads, cfg.n_kv_heads, cfg.mamba_heads, cfg.mamba_state,
        cfg.experts_per_token, cfg.experts_held[0])
    assert tuple(ref["layer_types"]) == cfg.layer_types


def test_the_cell_fits_its_cache_and_its_traffic():
    import jax

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import cache_positions, call_rows, make_config

    cell, traffic = load("workloads", CELL + ".json"), load(
        "traffic", "chat-lognormal-2k.json")
    e = cell["engine"]
    assert (cell["config"], cell["traffic"]) == (CONFIG["name"], "chat-lognormal-2k")
    assert cell["kind"] == traffic["kind"] == "serve_family"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 256,
                                     "sigma": 1.0, "min": 32, "max": 2048}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 128,
                                     "sigma": 0.7, "min": 16, "max": 512}
    assert traffic["preroll_s"] == 5
    assert e["prefill_buckets"] == [64, 128, 256, 512, 1024, 2048]
    assert (e["n_slots"], e["decode_chunk_steps"], e["prefill_token_budget"],
            e["max_new_tokens"]) == (48, 16, 2048, 512)
    assert [call_rows(b, e["n_slots"]) for b in e["prefill_buckets"]] == [
        4, 2, 1, 1, 1, 1]
    length = cache_positions(max(e["prefill_buckets"]), e["max_new_tokens"],
                             e["decode_chunk_steps"])
    assert length == 21 * 128 == 2688
    cfg = make_config(CONFIG["family"], CONFIG["size"], **KW)
    cache = jax.eval_shape(lambda: generate.init_cache(cfg, e["n_slots"] + 1, length))
    assert set(cache) == {"k", "v", "pos", "ssm", "conv"}
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert nbytes == pytest.approx(4.82e9, rel=2e-3)
    assert 0.80 < (2 * fg.param_count(KW) + nbytes) / 15.75e9 < 0.84  # resident
    rate = traffic["arrivals"]["rate_per_s"]
    assert f"{rate:g} req/s" in traffic["why"] and "sustain" in traffic["why"]
    a = traffic_gen.serve_schedule(traffic, 1, 50.0, KW["vocab_size"])
    b = traffic_gen.serve_schedule(traffic, 4_200_000_001, 50.0, KW["vocab_size"])
    assert a["max_new"] == b["max_new"] and a["prompts"] != b["prompts"]
    assert [len(p) for p in a["prompts"]] == [len(p) for p in b["prompts"]]
    assert max(max(p) for p in b["prompts"]) < KW["vocab_size"]
    assert 32 <= min(len(p) for p in a["prompts"])
    assert max(len(p) for p in a["prompts"]) <= 2048 and min(a["max_new"]) >= 16


def test_benchmark_json_lists_the_cell_and_its_metrics():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and 1 <= len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"]) == (
        "granite-4.0-h-small-ep8", "chat-lognormal-2k")
    assert len(BENCH["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert config["file"] == "benchmark/configs/granite-4.0-h-small-ep8.json"
    assert (config["source"], config["reduced"]) == (CONFIG["source"], CONFIG["reduced"])
    assert 1 <= len(config["why"]) <= 200
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SHARED:
        assert CELL in (e2e.get(name) or per_layer[name])["workloads"], name
    assert CELL not in e2e["ttft_p95_ms"]["workloads"]
    for m in BENCH["per_layer"]:
        if m["moves"] == "ttft_p95_ms":
            assert CELL not in m.get("workloads", []), m["name"]
    reported = {name for name, m in e2e.items()
                if CELL in m.get("workloads", [CELL])}
    assert reported == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] in reported, m["name"]
    for name, better, source in ((ROOFLINE, "higher", "device_trace"),
                                 (SHARE, "lower", "program_counter")):
        assert per_layer[name] == {
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": "Models and kernels", "moves": "tpot_p95_ms",
            "workloads": [CELL]}
        assert reader(name).UNIT == "%"


def _raw(scope=True, state=True):
    """A serve ``raw`` as the driver leaves it: a window of 1,000 steps in 80
    dispatches with 12 rows live, a traced interval of a tenth of it with 6
    whole chunks; ``scope=False``: a trace without the state's row,
    ``state=False``: a program without the counters (the parent)."""
    layers = {"full": 2, "window": 0, "state": 18}
    tiles = {"full": 524_288, "window": 0}
    zeros = [[0] * 9 for _ in range(20)]

    def stats(steps, dispatches, rows):
        out = {
            "cache_tiles": {"read_full": dispatches * rows * 4, "read_window": 0,
                            "padded": dispatches * 49 * 21, "flushed": dispatches * rows,
                            "layers": layers, "tile_bytes": tiles},
            "prefill": {"256": {"calls": dispatches, "rows": dispatches,
                                "padded_tokens": 256 * dispatches,
                                "prompts": dispatches, "live_tokens": 200 * dispatches}},
            "moe": {"prefill": {"tokens": zeros, "touched": [0] * 20, "rows": [0] * 20},
                    "decode": {"tokens": [[steps * rows * 10 // 72] * 9] * 20,
                               "touched": [steps * 8] * 20,
                               "rows": [steps * rows] * 20},
                    "decode_steps": steps, "decode_dispatches": dispatches},
            "compiles": {"count": 9}}
        if state:
            out["state"] = {"rows_updated": steps * rows, "rows_live": steps * rows,
                            "steps": steps, "dispatches": dispatches,
                            "layers": 18, "row_bytes": 4_244_992}
        else:
            for phase in ("prefill", "decode"):
                out["moe"][phase].pop("rows")
            out["moe"].pop("decode_dispatches")
        return out

    records = [({"times": [0.6 + 0.02 * i for i in range(300)]}, 200)] * 12
    scopes = {"ssm.in_proj": 0.5, "moe.expert_ffn": 0.4}
    if scope:
        scopes["ssm.state_update"] = 0.35
    return {
        "kind": "serve", "chunk_steps": 16, "decode_module": "jit__unknown",
        "engine_before": stats(0, 0, 12), "engine_after": stats(1000, 80, 12),
        "polls": [(12, 0)] * 5, "n_slots": 48,
        "device": {"kind": "TPU v5 lite"}, "client_records": records,
        "records": [],
        "trace": {"marks": {"start": 0.5, "stop": 7.0}, "window_s": 6.0,
                  "counters": {"start": stats(0, 0, 12), "stop": stats(100, 8, 12)},
                  "scopes": scopes,
                  "modules": {"jit__unknown(123)": {
                      "count": 6, "total_s": 1.8, "median_s": 0.30},
                      "jit_llm_decode_cut(9)": {
                          "count": 2, "total_s": 0.08, "median_s": 0.04},
                      "jit_llm_prefill(77)": {
                          "count": 8, "total_s": 0.5, "median_s": 0.06}}},
    }


def test_the_states_roofline_reads_its_scope_and_the_live_rows():
    ctx = types.SimpleNamespace(config=CONFIG)
    raw = _raw()
    counts = fg.traced_counts(raw)
    # steps AND dispatches from the counters: 100 steps in 8 dispatches,
    # not 100 / 16; 12 rows took every step; 48 tiles a layer a dispatch
    assert (counts["decode_steps"], counts["dispatches"]) == (100, 8)
    assert counts["state_rows_per_step"] == 12.0
    assert counts["full_tiles_per_step"] == 48.0
    assert counts["touched_experts_per_step"].state_rows == 12.0
    assert float(counts["touched_experts_per_step"]) == 160.0
    json.dumps(counts)  # the driver keeps it in the line's detail
    # 12 rows x 16 steps x 6 whole chunks, 18 layers of 4 MB read and written
    row_steps = 12 * 16 * 6
    least = fg.state_update_least(KW, row_steps, flops.peaks("TPU v5 lite"))
    assert least == pytest.approx(row_steps * 18 * 8_388_608 / 819e9)
    share = reader(ROOFLINE).read(ctx, raw)
    assert share == pytest.approx(100 * least / 0.35) and 0 < share < 100
    # the counter's share: the kernel touches the live rows only; a masked
    # update over all 49 rows reads 49 / 12
    assert reader(SHARE).read(ctx, raw) == pytest.approx(100.0)
    masked = _raw()
    masked["engine_after"]["state"]["rows_updated"] = 1000 * 49
    assert reader(SHARE).read(ctx, masked) == pytest.approx(100 * 49 / 12)
    # no such row, no counters (the parent of the PR that adds them), other
    # families' configurations, a train cell: nothing, and no raise
    assert reader(ROOFLINE).read(ctx, _raw(scope=False)) is None
    parent = _raw(state=False)
    assert reader(SHARE).read(ctx, parent) is None
    assert fg.traced_counts(parent)["state_rows_per_step"] == 0.0
    assert reader(ROOFLINE).read(ctx, parent) is None
    bare = _raw()
    bare["trace"]["counters"] = None
    assert reader(ROOFLINE).read(ctx, bare) is None
    for other in ("kimi-k2.7-code-ep32.json", "k-exaone-236b-a23b-ep8.json",
                  "gpt2-xl.json"):
        there = types.SimpleNamespace(config=load("configs", other))
        assert reader(ROOFLINE).read(there, {**_raw(), "trace": {
            **_raw()["trace"], "scopes": {"moe.router": 0.1}}}) is None
    assert reader(ROOFLINE).read(ctx, {"kind": "train"}) is None
    assert reader(SHARE).read(ctx, {"kind": "train"}) is None


def test_the_whole_steps_roofline_counts_the_live_rows_state():
    """``model.moe_decode_roofline_pct`` as it is, through this family's
    counts: the least bytes hold the state of the live rows read and written
    (the counts hand them over on the touched experts: ``Touched``)."""
    ctx = types.SimpleNamespace(config=CONFIG)
    raw = _raw()
    share = reader("model.moe_decode_roofline_pct").read(ctx, raw)
    live_tiles = 2 * 48.0
    need = fg.decode_step_bytes(KW, fg.Touched(160.0, 12.0), live_tiles)
    step_s = 1.8 / 6 / 16
    assert share == pytest.approx(100 * need / 819e9 / step_s)
    without = fg.decode_step_bytes(KW, 160.0, live_tiles)
    assert need - without == pytest.approx(1.83e9, rel=3e-3)
    assert 0 < share < 100
    # the twelve prompts whose first token landed inside the traced interval
    mfu = reader("model.prefill_live_mfu_pct").read(ctx, raw)
    need = fg.prefill_flops(KW, [200] * 12, 10 * 9 / 72)
    assert mfu == pytest.approx(100 * need / (0.5 * 197e12)) and 0 < mfu < 100
    load_ratio = reader("moe.expert_load_max_over_mean").read(ctx, raw)
    assert load_ratio == pytest.approx(1.0)


def test_the_scopes_come_from_the_compiled_text():
    from benchmark.drivers import serve_family

    text = '''
  %ssm_state_update.3 = (f32[18,49,128,64,128]{4,3,2,1,0}, f32[49,64,128]{2,1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(<unknown>)/while/body/closed_call/while/body/closed_call/ssm.state_update/cond/branch_0_fun/jit(state_update_kernel)/ssm_state_update"}
  %fusion.808 = bf16[49,1,16768]{2,0,1} fusion(%x, %w), kind=kOutput, calls=%fc, metadata={op_name="jit(<unknown>)/while/body/closed_call/while/body/closed_call/ssm.in_proj/dot_general"}
  %fusion.9 = f32[49,8192]{1,0} fusion(%y), kind=kLoop, metadata={op_name="jit(<unknown>)/while/body/closed_call/while/body/closed_call/ssm.out_proj/mul"}
  ROOT %ragged-dot-none.7 = bf16[496,768]{1,0} custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %custom-call.9 = f32[49,96,128]{2,1,0} custom-call(%q), metadata={op_name="jit(<unknown>)/while/body/closed_call/attention.full/cond/branch_0_fun/ragged_decode_attention/pallas_call"}
  %add.3 = f32[49]{0} add(%x, %y), metadata={op_name="jit(<unknown>)/while/body/add"}
'''
    assert serve_family.scopes_of_instructions(text, CONFIG["trace_scopes"]) == {
        "ssm_state_update.3": "ssm.state_update", "fusion.808": "ssm.in_proj",
        "fusion.9": "ssm.out_proj", "ragged-dot-none.7": "moe.expert_ffn",
        "custom-call.9": "attention.full"}
