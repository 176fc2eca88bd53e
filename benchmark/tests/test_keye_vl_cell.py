"""What the Keye-VL cell adds to the benchmark: its configuration file against
the catalog, the program and the counts; the cell's sizes against the cache's
tensors; the traffic file and the schedule the driver makes of it; its entries
in BENCHMARK.json; the four new readers on hand-made ``raw``s (a value where
the program counts, None where it does not, as the parent of the PR that adds
the family does not)."""

import importlib.util
import json
import os
import types

import pytest

from benchmark import flops, flops_keye_vl as fk

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "serve-keye-vl-2.0-30b-a3b-pp8-video"
NEW = ("model.vision_tower_mfu_pct", "engine.vision_encode_share_pct",
       "model.sparse_gqa_decode_roofline_pct", "moe.experts_touched_share_pct")
SHARED = ("serve_tokens_per_s", "tpot_p95_ms", "engine.slots_busy_pct",
          "engine.prefill_interference_pct", "model.decode_step_ms",
          "device.idle_pct.serve", "engine.compiles_in_window",
          "model.moe_decode_roofline_pct", "model.prefill_live_mfu_pct",
          "moe.expert_load_max_over_mean", "engine.tpot_p95_ms",
          "replica.tpot_p95_ms", "cache.flush_write_share_pct",
          "model.index_select_roofline_pct", "cache.selected_read_share_pct")


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


CONFIG = load("configs", "keye-vl-2.0-30b-a3b-pp8.json")
KW = CONFIG["model_config"]
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CTX = types.SimpleNamespace(config=CONFIG)


def test_the_configuration_against_the_catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers"}


def test_the_configuration_keeps_the_published_widths():
    assert CONFIG["num_hidden_layers"] == 6 == KW["n_layers"]
    assert CONFIG["published"]["num_hidden_layers"] == 48 == 8 * 6
    assert "EIGHT chips as eight pipeline stages" in CONFIG["deployment"]
    assert set(CONFIG["reduced"]) <= set(CONFIG["changed"])
    for key in ("norm_placement", "qk_norm", "mrope", "positions", "indexer",
                "experts", "tower", "merger", "placeholder", "dtype", "weights"):
        assert key in CONFIG["assumed"], key
    assert (KW["d_model"], KW["n_heads"], KW["n_kv_heads"], KW["head_dim"],
            KW["vocab_size"]) == (2048, 32, 4, 128, 151936)
    assert (KW["n_experts"], KW["experts_per_token"], KW["d_expert"],
            KW["experts_held"]) == (128, 8, 768, [0, 128])
    assert (KW["index_n_heads"], KW["index_head_dim"], KW["index_topk"]) == (
        16, 64, 2048) == tuple(CONFIG["sa_config"][k] for k in (
            "indexer_num_heads", "indexer_head_dim", "topk"))
    assert KW["mrope_section"] == CONFIG["rope_scaling"]["mrope_section"]
    assert (KW["vision_layers"], KW["vision_d_model"], KW["vision_heads"],
            KW["vision_d_ff"], KW["vision_patch"]) == (27, 1152, 16, 4304, 14)
    assert KW["video_token_id"] == 151656 < KW["vocab_size"]


def test_the_program_builds_the_configuration_and_the_counts_agree():
    import jax

    from ray_tpu.serve.llm import _default_init, make_config

    cfg = make_config(CONFIG["family"], CONFIG["size"], **KW)
    assert cfg.experts_held == (0, 128) and cfg.index_cache == (64, 2048)
    shapes = jax.eval_shape(lambda: _default_init(cfg, 0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == fk.param_count(KW) == 4_818_289_520
    assert sum(x.size for x in jax.tree.leaves(shapes["vision"])) == (
        fk.vision_param_count(KW)) == 443_667_056
    assert all(str(x.dtype) == "bfloat16" for x in jax.tree.leaves(shapes))
    p = fk.parts(KW)
    assert (round(p["attention"] / 1e6, 2), round(p["indexer"] / 1e6, 2)) == (
        18.88, 2.26)
    assert (p["expert"], p["router"]) == (4_718_592, 262_144)
    assert (fk.kv_row_bytes(KW), fk.index_key_bytes(KW)) == (2048, 128)
    assert fk.attended_position_flops(KW) == 16_384
    assert fk.scored_position_flops(KW) == 2_048
    # a frame of 16 x 16 patches: 222.9 GFLOP, 8.2 of them its attention
    assert fk.vision_patch_flops(KW, (16, 16)) * 256 == pytest.approx(222.9e9, rel=1e-3)
    sizes = CONFIG["reference_sizes"]
    assert (sizes["index_topk"], sizes["top_k"], sizes["head_dim"]) == (2048, 8, 128)
    assert sizes["mrope_section"] == list(cfg.mrope_section)
    assert sizes["video_token_id"] == cfg.video_token_id


def test_the_cell_fits_its_engine_and_its_traffic():
    from benchmark.drivers.serve_vision import video_schedule

    cell = load("workloads", CELL + ".json")
    traffic = load("traffic", cell["traffic"] + ".json")
    e = cell["engine"]
    assert cell["kind"] == traffic["kind"] == "serve_vision"
    assert cell["chips"] == 1 and cell["config"] == CONFIG["name"]
    assert e["prefill_buckets"] == [2048, 4096, 8192, 16384] and e["n_slots"] >= 8
    assert traffic["frames"] == {"dist": "lognormal", "median": 80,
                                 "sigma": 0.7, "min": 32, "max": 240}
    assert traffic["question_len"]["max"] == 512 and traffic["system_len"] == 16
    assert traffic["output_len"]["max"] <= e["max_new_tokens"]
    assert traffic["frame_grid"] == [16, 16]
    longest = 16 + traffic["frames"]["max"] * 64 + traffic["question_len"]["max"]
    assert longest == 15888 <= max(e["prefill_buckets"])
    # every context passes topk: the shortest video alone is 2,048 rows
    assert traffic["frames"]["min"] * 64 >= KW["index_topk"]
    positions = -(-(16384 + e["max_new_tokens"] + e["decode_chunk_steps"]) // 128) * 128
    cache = (e["n_slots"] + 1) * positions * KW["n_layers"] * (2048 + 128)
    assert positions == 17536 and cache == 2_976_350_208
    # the schedule: one fixed trace, the seed's ids (never the placeholder
    # outside the video's run) and the same frames for every seed
    a = video_schedule(traffic, CONFIG, 7, 10.0)
    b = video_schedule(traffic, CONFIG, 8, 10.0)
    assert a["due"] == b["due"] and a["frames"] == b["frames"]
    assert a["max_new"] == b["max_new"] and a["prompts"] != b["prompts"]
    for prompt, frames in zip(a["prompts"], a["frames"]):
        held = [i for i, t in enumerate(prompt) if t == KW["video_token_id"]]
        assert held == list(range(16, 16 + frames * 64))
        assert 32 <= frames <= 240 and max(prompt) < KW["vocab_size"]
    assert min(a["due"]) < 0 <= max(a["due"]) < 10.0
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
    assert CELL not in metrics["model.sparse_latent_decode_roofline_pct"]["workloads"]


def stats(steps, dispatches, scored, selected, read, touched=36, patches=0,
          tower_s=0.0, decode_s=0.0):
    """An engine's ``perf_stats()`` as the readers see it, six layers."""
    each = lambda n: [n // 6] * 6  # noqa: E731
    return {"moe": {"decode_steps": steps, "decode_dispatches": dispatches,
                    "decode": {"tokens": [[steps] * 128] * 6,
                               "touched": [steps * touched] * 6,
                               "dsa_scored": each(scored),
                               "dsa_selected": each(selected),
                               "dsa_read": each(read)},
                    "prefill": {"tokens": [[0] * 128] * 6}},
            "cache_tiles": {"read_full": 10 * dispatches, "read_window": 0,
                            "padded": 100 * dispatches, "flushed": dispatches,
                            "layers": {"full": 6, "window": 0},
                            **{"vision_" + k: 0 for k in fk.VISION_COUNTERS},
                            "vision_patches": patches},
            "prefill": {},
            "tick_s": {"decode_only": decode_s, "interleaved": decode_s,
                       "prefill_only": 0.0},
            "vision_ticks": {"tower_s": tower_s, "tower_calls_timed": 0,
                             "tower_interference_s": tower_s / 2}}


def raw_with(before, after, *, scopes=None, whole=10, tower_s=None):
    trace = None
    if scopes is not None:
        modules = {"jit__unknown(1)": {"count": whole, "total_s": 1.0},
                   "jit_llm_decode_cut(2)": {"count": 3, "total_s": 0.1}}
        if tower_s:
            modules["jit_llm_vision_encode(3)"] = {"count": 4, "total_s": tower_s}
        trace = {"scopes": scopes, "counters": {"start": before, "stop": after},
                 "modules": modules, "marks": {"start": 0.0}, "window_s": 6.0}
    return {"kind": "serve", "engine_before": before, "engine_after": after,
            "chunk_steps": 16, "decode_module": "jit__unknown",
            "frame_grid": [16, 16], "device": {"kind": "TPU v5 lite"},
            "trace": trace}


def bare(after):
    """The same reads from a program without the tower's counters (a parent)."""
    out = json.loads(json.dumps(after))
    out.pop("vision_ticks")
    out["cache_tiles"] = {k: v for k, v in out["cache_tiles"].items()
                          if not k.startswith("vision_")}
    return out


def test_the_tower_mfu_holds_counted_patches_against_the_programs_seconds():
    before, after = stats(0, 0, 0, 0, 0), stats(160, 12, 6, 6, 6, patches=16_384)
    read = reader("model.vision_tower_mfu_pct").read
    got = read(CTX, raw_with(before, after, scopes={}, tower_s=0.1))
    want = 100 * 16_384 * fk.vision_patch_flops(KW, (16, 16)) / (0.1 * 197e12)
    assert got == pytest.approx(want) and 0 < got < 100
    assert read(CTX, raw_with(before, after, scopes={})) is None   # no such program
    assert read(CTX, raw_with(before, after)) is None              # no trace
    assert read(CTX, raw_with(bare(before), bare(after), scopes={}, tower_s=0.1)) is None


def test_the_tower_share_of_decoding_periods():
    before = stats(0, 0, 0, 0, 0, tower_s=1.0, decode_s=5.0)
    after = stats(160, 12, 6, 6, 6, tower_s=3.0, decode_s=10.0)
    read = reader("engine.vision_encode_share_pct").read
    assert read(CTX, raw_with(before, after)) == pytest.approx(100 * 1.0 / 10.0)
    assert read(CTX, raw_with(bare(before), bare(after))) is None


def test_the_sparse_read_roofline_and_the_touched_share():
    before = stats(0, 0, 0, 0, 0)
    # 160 steps, a step: 36,000 rows scored, 12,288 chosen, 38,400 read
    after = stats(160, 12, 160 * 36_000, 160 * 12_288, 160 * 38_400, touched=36)
    peak = flops.peaks("TPU v5 lite")
    raw = raw_with(before, after, scopes={"attention.gqa_sparse": 0.020}, whole=10)
    got = reader("model.sparse_gqa_decode_roofline_pct").read(CTX, raw)
    rows = 38_400 * 10 * 16
    least = max(rows * 2048 / peak["hbm_bytes_per_s"],
                rows * 16_384 / peak["bf16_flops_per_s"])
    assert got == pytest.approx(100 * least / 0.020) and 0 < got < 100
    assert reader("model.sparse_gqa_decode_roofline_pct").read(
        CTX, raw_with(before, after, scopes={})) is None
    touched = reader("moe.experts_touched_share_pct").read(CTX, raw_with(before, after))
    assert touched == pytest.approx(100 * 6 * 36 / (6 * 128))
    # a chip's share of an expert-parallel layer is another question
    shared = types.SimpleNamespace(config={**CONFIG, "model_config": {
        **KW, "experts_held": [0, 16]}})
    assert reader("moe.experts_touched_share_pct").read(
        shared, raw_with(before, after)) is None
    # what model.moe_decode_roofline_pct hands the counts module
    counts = fk.counts_between(before, after, 16)
    touched = counts["touched_experts_per_step"]
    assert float(touched) == 6 * 36 and touched.rows_scored == 36_000
    assert fk.decode_step_bytes(KW, touched, 1e9) == (
        2 * (fk.always_read_params(KW) + 216 * 4_718_592)
        + 36_000 * 128 + 12_288 * 2048)
    assert counts["vision"]["patches"] == 0
