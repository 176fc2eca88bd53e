"""What the dots3-note-prev cell adds to the benchmark: its configuration file
against the catalog, the program and the counts; the cell's sizes against the
three cached tensors; the traffic file; its entries in BENCHMARK.json; the three
new readers on hand-made ``raw``s (a value where the program counts, None
where it does not, as the parent of the PR that adds the family does not)."""

import importlib.util
import json
import os
import types

import pytest

from benchmark import flops, flops_dots3_note as fd, traffic_gen

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "serve-dots3-note-prev-ep32-docs"
NEW = ("model.index_select_roofline_pct",
       "model.sparse_latent_decode_roofline_pct",
       "cache.selected_read_share_pct")
SHARED = ("serve_tokens_per_s", "tpot_p95_ms", "engine.slots_busy_pct",
          "engine.prefill_interference_pct", "model.decode_step_ms",
          "device.idle_pct.serve", "engine.compiles_in_window",
          "model.moe_decode_roofline_pct", "model.prefill_live_mfu_pct",
          "moe.expert_load_max_over_mean", "engine.tpot_p95_ms",
          "replica.tpot_p95_ms", "cache.flush_write_share_pct")


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


CONFIG = load("configs", "dots3-note-prev-ep32.json")
KW = CONFIG["model_config"]
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_configuration_against_the_catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "dots3-note-prev")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}


def test_the_configuration_keeps_the_published_widths():
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (5, 8, 19008)
    assert CONFIG["published"]["n_routed_experts"] == 256 == 32 * 8
    assert CONFIG["published"]["vocab_size"] == 152064 == 8 * 19008
    assert CONFIG["published"]["num_hidden_layers"] == 46
    assert "32 chips" in CONFIG["deployment"]
    assert "1,822.2 M parameters" in CONFIG["deployment"]
    assert set(CONFIG["reduced"]) < set(CONFIG["changed"])
    for key in ("lora_rescale", "attention_gate", "window", "indexer",
                "norm_placement", "rotary_pairs", "selection_bias", "dtype",
                "weights", "n_group"):
        assert key in CONFIG["assumed"]
    for key in ("lora_rescale", "attention_gate", "window"):
        assert "ASSUMED" in CONFIG["assumed"][key]
    # no width is reduced: the program's keywords are the published ones
    assert (KW["d_model"], KW["n_heads"], KW["q_lora_rank"], KW["kv_lora_rank"],
            KW["qk_nope_head_dim"], KW["qk_rope_head_dim"], KW["v_head_dim"]) == (
        5120, 128, 1024, 512, 128, 64, 128)
    assert (KW["swa_n_heads"], KW["swa_q_lora_rank"], KW["swa_kv_lora_rank"],
            KW["swa_qk_nope_head_dim"], KW["swa_qk_rope_head_dim"],
            KW["swa_v_head_dim"], KW["sliding_window"]) == (
        64, 1024, 1024, 192, 64, 128, 513)
    assert (KW["index_n_heads"], KW["index_head_dim"], KW["index_topk"]) == (
        64, 128, 2048)
    assert (KW["d_ff"], KW["d_expert"], KW["n_experts"], KW["experts_per_token"],
            KW["routed_scale"], KW["rope_base"], KW["swa_rope_base"]) == (
        13824, 1536, 256, 8, 1.0, 80000000.0, 50000.0)
    assert KW["experts_held"] == [0, 8] and KW["n_layers"] == 5
    assert KW["layer_types"] == CONFIG["layer_types"][:5] == [
        "full_attention", "full_attention", "sliding_attention",
        "sliding_attention", "sliding_attention"]


def test_the_program_builds_the_configuration_and_the_counts_agree():
    import jax

    from ray_tpu.serve.llm import _default_init, make_config

    cfg = make_config(CONFIG["family"], CONFIG["size"], **KW)
    assert cfg.experts_held == (0, 8) and cfg.n_experts == 256
    assert cfg.latent_cache == (576, 512)
    assert cfg.window_latent_cache == (1088, 1024)
    assert cfg.index_cache == (128, 2048)
    shapes = jax.eval_shape(lambda: _default_init(cfg, 0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == fd.param_count(KW) == 1_822_230_016
    assert all(str(x.dtype) == "bfloat16" for x in jax.tree.leaves(shapes))
    p = fd.parts(KW)
    assert round(p["full_attention"] / 1e6, 2) == 134.69
    assert round(p["window_attention"] / 1e6, 2) == 90.85
    assert round(p["indexer"] / 1e6, 2) == 9.37
    assert (p["expert"], p["dense_ffn"]) == (23_592_960, 212_336_640)
    assert (fd.full_row_bytes(KW), fd.index_key_bytes(KW),
            fd.window_row_bytes(KW)) == (1152, 256, 2176)
    assert fd.attended_position_flops(KW) == 278_528
    assert fd.scored_position_flops(KW) == 16_384
    # the reference's sizes say what the program's keywords say
    sizes = CONFIG["reference_sizes"]
    assert sizes["index_topk"] == cfg.index_topk
    assert sizes["sliding_window"] == cfg.sliding_window
    assert sizes["layer_types"] == list(cfg.layer_types)
    assert (sizes["top_k"], sizes["first_expert"]) == (8, 0)


def test_the_cell_fits_its_engine_and_its_traffic():
    cell = load("workloads", CELL + ".json")
    traffic = traffic_gen.load(cell["traffic"])
    e = cell["engine"]
    assert cell["kind"] == traffic["kind"] == "serve_family"
    assert cell["chips"] == 1 and cell["config"] == CONFIG["name"]
    assert e["prefill_buckets"] == [2048, 4096, 8192, 16384]
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                     "sigma": 0.7, "min": 2048, "max": 16384}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 256,
                                     "sigma": 0.7, "min": 32, "max": 1024}
    assert traffic["prompt_len"]["max"] <= max(e["prefill_buckets"])
    assert traffic["output_len"]["max"] <= e["max_new_tokens"]
    # every context exceeds index_topk: every full-layer query selects
    assert traffic["prompt_len"]["min"] >= KW["index_topk"]
    assert e["decode_chunk_steps"] <= KW["sliding_window"] + 1
    # 33 rows x 17,536 positions x 2 layers x (1,152 + 256) bytes, and rings
    positions = -(-(16384 + e["max_new_tokens"] + e["decode_chunk_steps"]) // 128) * 128
    slab = (e["n_slots"] + 1) * positions * fd.full_layers(KW) * (1152 + 256)
    rings = (e["n_slots"] + 1) * 2 * 513 * fd.window_layers(KW) * 2176
    assert positions == 17536 and slab == 1_629_585_408 and rings == 221_025_024
    assert cell["logit_tie_margin"] > 0 and 0 < cell["min_exact_share"] < 1
    for key in ("sizes", "rate", "correctness", "noise", "trace"):
        assert key in cell["assumed"], key


def test_the_benchmark_names_the_cell_and_its_metrics():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL]["config"] == CONFIG["name"] and cells[CELL]["chips"] == 1
    assert len(cells[CELL]["why"]) <= 200
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in SHARED:
        assert CELL in metrics[name]["workloads"], name
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "tpot_p95_ms" and metrics[name]["unit"] == "%"
        assert os.path.exists(os.path.join(HERE, "layer_metrics", name + ".py"))
    assert CELL not in metrics["ttft_p95_ms"]["workloads"]
    assert CELL not in metrics["model.latent_decode_attention_roofline_pct"]["workloads"]


def stats(steps, dispatches, scored, selected, read):
    """An engine's ``perf_stats()`` as the readers see it, two full layers."""
    half = lambda n: [n // 2, n - n // 2]  # noqa: E731
    return {"moe": {"decode_steps": steps, "decode_dispatches": dispatches,
                    "decode": {"tokens": [[steps] * 8] * 4, "touched": [steps] * 4,
                               "dsa_scored": half(scored),
                               "dsa_selected": half(selected),
                               "dsa_read": half(read)},
                    "prefill": {"tokens": [[0] * 8] * 4}},
            "cache_tiles": {"read_full": 10 * dispatches, "read_window": 0,
                            "padded": 100 * dispatches, "flushed": dispatches,
                            "layers": {"full": 2, "window": 3}},
            "prefill": {}}


def raw_with(before, after, *, scopes=None, whole=10):
    trace = None
    if scopes is not None:
        trace = {"scopes": scopes, "counters": {"start": before, "stop": after},
                 "modules": {"jit__unknown(1)": {"count": whole, "total_s": 1.0},
                             "jit_llm_decode_cut(2)": {"count": 3, "total_s": 0.1}},
                 "marks": {"start": 0.0}, "window_s": 6.0}
    return {"kind": "serve", "engine_before": before, "engine_after": after,
            "chunk_steps": 16, "decode_module": "jit__unknown",
            "device": {"kind": "TPU v5 lite"}, "trace": trace}


CTX = types.SimpleNamespace(config=CONFIG)


def test_the_read_share_is_rows_read_over_rows_chosen():
    before = stats(0, 0, 0, 0, 0)
    after = stats(160, 12, 2_000_000, 600_000, 1_800_000)
    read = reader("cache.selected_read_share_pct").read
    assert read(CTX, raw_with(before, after)) == pytest.approx(300.0)
    # a program without the counter (or a family that selects nothing)
    bare = stats(160, 12, 0, 0, 0)
    for k in ("dsa_scored", "dsa_selected", "dsa_read"):
        bare["moe"]["decode"].pop(k)
    assert read(CTX, raw_with(before, bare)) is None
    assert read(types.SimpleNamespace(config={}), raw_with(before, after)) is None


def test_the_two_rooflines_hold_counted_rows_against_the_scopes_seconds():
    before = stats(0, 0, 0, 0, 0)
    # 160 steps: 12,288 rows scored, 4,096 chosen, 12,800 read a step
    after = stats(160, 12, 160 * 12_288, 160 * 4_096, 160 * 12_800)
    peak = flops.peaks("TPU v5 lite")
    scopes = {"attention.index_score": 0.010, "attention.index_select": 0.006,
              "attention.latent_sparse": 0.002,
              "ragged_latent_decode_attention": 0.018}
    raw = raw_with(before, after, scopes=scopes, whole=10)
    steps = 10 * 16  # the whole chunks' steps alone
    got = reader("model.index_select_roofline_pct").read(CTX, raw)
    rows = 12_288 * steps
    least = max(rows * 256 / peak["hbm_bytes_per_s"],
                rows * 16_384 / peak["bf16_flops_per_s"])
    assert got == pytest.approx(100 * least / 0.016) and 0 < got < 100
    got = reader("model.sparse_latent_decode_roofline_pct").read(CTX, raw)
    rows = 12_800 * steps
    least = max(rows * 1152 / peak["hbm_bytes_per_s"],
                rows * 278_528 / peak["bf16_flops_per_s"])
    assert got == pytest.approx(100 * least / 0.020) and 0 < got < 100
    # no such scope in the trace (the parent's program), or no trace: nothing
    for name in NEW[:2]:
        assert reader(name).read(CTX, raw_with(before, after, scopes={})) is None
        assert reader(name).read(CTX, raw_with(before, after)) is None


def test_the_step_roofline_counts_chosen_rows_and_scored_keys():
    """What ``model.moe_decode_roofline_pct`` hands the counts module: the
    touched experts carry the selection's rows, and the cache's least bytes
    are an index key a scored row, a latent row a CHOSEN one, and a window of
    ring entries a live row a sliding layer."""
    before = stats(0, 0, 0, 0, 0)
    after = stats(160, 12, 160 * 2 * 6_000 * 3, 160 * 2 * 2_048 * 3, 160 * 2 * 6_144 * 3)
    counts = fd.counts_between(before, after, 16)
    touched = counts["touched_experts_per_step"]
    assert float(touched) == 4.0 and touched.rows_scored == 36_000
    cache = fd.cache_bytes(KW, touched.rows_scored, touched.rows_selected)
    assert cache == 36_000 * 256 + 12_288 * 1152 + 3 * (3 * 513) * 2176
    assert fd.decode_step_bytes(KW, touched, 1e9) == (
        2 * (fd.always_read_params(KW) + 4 * 23_592_960) + cache)
    # T (T + 1) / 2 scored pairs, min(t + 1, 2048) attended a full layer
    one = fd.prefill_flops(KW, [4096], 0.25) - fd.prefill_flops(KW, [4095], 0.25)
    per_token = 2.0 * (fd.token_matmul_params(KW, 0.25) - fd.parts(KW)["head"])
    assert one == pytest.approx(
        per_token + 2 * (16_384 * 4096 + 2 * 128 * 320 * 2048)
        + 3 * 2 * 64 * 384 * 513)
