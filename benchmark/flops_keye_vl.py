"""Operations and least bytes of the Keye-VL family's tower call, prompt part
and decode step (a vision tower in front, grouped-query attention under a
learned selection over a slab of K and V, every expert of a layer held), from
the configuration (``model_config``: the program's keywords) and the run's
counters.  Pure host-side Python, no jax.  The interface of
``flops_dots3_note.py``, whose counter arithmetic (``counts_between``: steps,
dispatches, tiles, experts and the selection's rows from the engine's
``perf_stats()``) is this family's too; what is added rides the same dict:
``vision`` (the tower's counters between the two reads).

As in ``flops.py``: what the ALGORITHM needs, never what the program happens
to do.  Padded frames, rows of idle slots, dead cache positions, experts no
live token chose and cached positions the selection did not choose are not
credited.

- a decode step SCORES every cached position of a live row a layer (one
  64-value index key: 128 bytes in bf16) and READS the k and v of the rows it
  selected (``min(context, index_topk)`` of 4 heads x 128 x 2 tensors: 2,048
  bytes);
- a prompt's text path: every real token through the layers; a layer scores ``T
  (T + 1) / 2`` pairs with its index and attends the ``min(t + 1, index_topk)``
  positions a row selects; the TOWER is counted apart (:func:`vision_flops`),
  as its program is timed apart (``model.vision_tower_mfu_pct``);
- a patch of the tower: the matmuls of 27 blocks, the patch embedding and a
  quarter of the merger (4 patches a row), and the attention among a frame's
  patches.
"""

from __future__ import annotations

from benchmark.flops_dots3_note import Touched, counts_between as _counts_between
from benchmark.flops_k_exaone import TILE, _delta, live_rows_between  # noqa: F401

VISION_COUNTERS = ("frames", "patches", "padded_patches", "visual_tokens",
                   "calls", "requests")


def sparse_layers(cfg: dict) -> int:
    return cfg["n_layers"]


def kv_row_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """A cached position's k and v of one layer (2,048 as published)."""
    return 2 * cfg["n_kv_heads"] * cfg["head_dim"] * bytes_per_value


def index_key_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """A cached position's index key (128 as published)."""
    return cfg["index_head_dim"] * bytes_per_value


def parts(cfg: dict) -> dict:
    """Parameters of each part, from the sizes alone."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return {
        # W_q, W_k, W_v, W_o, the two head norms and the layer's two norms
        "attention": (2 * cfg["n_heads"] + 2 * cfg["n_kv_heads"]) * hd * d
                     + 2 * hd + 2 * d,
        # W_qI, W_kI and its LayerNorm, W_w
        "indexer": d * hi * di + d * di + 2 * di + d * hi,
        "expert": 3 * d * cfg["d_expert"],
        "router": d * cfg["n_experts"],
        "head": d * cfg["vocab_size"] + d,
        "embedding": cfg["vocab_size"] * d,
    }


def vision_parts(cfg: dict) -> dict:
    """The tower's and the merger's parameters."""
    dv, f = cfg["vision_d_model"], cfg["vision_d_ff"]
    values = 3 * cfg["vision_patch"] ** 2
    merged = 4 * dv
    return {
        "patch": values * dv + dv + cfg["vision_table"] ** 2 * dv,
        "block": 4 * dv * dv + 4 * dv + 2 * dv * f + f + dv + 4 * dv,
        "merger": 4 * dv + merged * merged + merged + merged * cfg["d_model"]
                  + cfg["d_model"],
    }


def vision_param_count(cfg: dict) -> int:
    p = vision_parts(cfg)
    return p["patch"] + cfg["vision_layers"] * p["block"] + p["merger"]


def always_read_params(cfg: dict) -> int:
    """What every decode step reads whatever the routing and the selection:
    the attention's and the indexer's projections, the routers, the head."""
    p = parts(cfg)
    return (cfg["n_layers"] * (p["attention"] + p["indexer"] + p["router"])
            + p["head"])


def param_count(cfg: dict) -> int:
    """Parameters this chip holds: the stage's layers with every expert, the
    embedding and the head, the tower and the merger."""
    p = parts(cfg)
    return (always_read_params(cfg) + p["embedding"]
            + cfg["n_layers"] * cfg["experts_held"][1] * p["expert"]
            + vision_param_count(cfg))


def token_matmul_params(cfg: dict, held_pairs: float) -> float:
    return always_read_params(cfg) + cfg["n_layers"] * held_pairs * parts(cfg)["expert"]


def attended_position_flops(cfg: dict) -> int:
    """One cached position attended by every query head: a score and a value
    sum over 128 values a head (32 x 128 x 2 x 2 = 16,384)."""
    return 4 * cfg["n_heads"] * cfg["head_dim"]


def scored_position_flops(cfg: dict) -> int:
    """One index score: 16 heads x 64 values, a product and a sum (2,048)."""
    return 2 * cfg["index_n_heads"] * cfg["index_head_dim"]


def cache_bytes(cfg: dict, rows_scored: float, rows_selected: float) -> float:
    """Least cache bytes of decode steps whose layers scored and chose that
    many rows (both summed over the layers)."""
    return rows_scored * index_key_bytes(cfg) + rows_selected * kv_row_bytes(cfg)


def decode_step_flops(cfg: dict, live_rows: float, held_pairs: float,
                      attended_positions: float) -> float:
    return (2.0 * token_matmul_params(cfg, held_pairs) * live_rows
            + attended_position_flops(cfg) * attended_positions)


def decode_step_bytes(cfg: dict, touched_experts: float, live_tiles: float,
                      bytes_per_value: int = 2) -> float:
    """Least bytes one decode step reads: the always-read weights, the
    DISTINCT experts some live token chose, and of the cache what the
    selection's counts say (they ride ``touched_experts``:
    ``flops_dots3_note.Touched``); ``live_tiles`` is not used."""
    return (bytes_per_value * (always_read_params(cfg)
                               + float(touched_experts) * parts(cfg)["expert"])
            + cache_bytes(cfg, getattr(touched_experts, "rows_scored", 0.0),
                          getattr(touched_experts, "rows_selected", 0.0)))


def prefill_flops(cfg: dict, prompt_lens, held_pairs: float) -> float:
    """FLOPs the LIVE prompt tokens need on the TEXT path (the tower apart:
    module docstring)."""
    p = parts(cfg)
    per_token = 2.0 * (token_matmul_params(cfg, held_pairs) - p["head"])
    bounded = lambda t, k: (  # noqa: E731 — sum over rows of min(row + 1, k)
        t * (t + 1) / 2.0 if t <= k else k * (k + 1) / 2.0 + (t - k) * k)
    total = 0.0
    for t in prompt_lens:
        total += (per_token * t + 2.0 * p["head"] + cfg["n_layers"] * (
            scored_position_flops(cfg) * t * (t + 1) / 2.0
            + attended_position_flops(cfg) * bounded(t, cfg["index_topk"])))
    return total


def vision_patch_flops(cfg: dict, grid) -> float:
    """FLOPs one patch of a frame of ``grid = (rows, columns)`` patches needs:
    twice the blocks' matmul parameters, the patch embedding, a quarter of the
    merger, and the attention among the frame's patches (a score and a value
    sum against every patch of the frame, a block)."""
    p = vision_parts(cfg)
    dv, f = cfg["vision_d_model"], cfg["vision_d_ff"]
    values = 3 * cfg["vision_patch"] ** 2
    merged = 4 * dv
    matmul = (values * dv + cfg["vision_layers"] * (4 * dv * dv + 2 * dv * f)
              + (merged * merged + merged * cfg["d_model"]) / 4.0)
    del p
    return 2.0 * matmul + cfg["vision_layers"] * 4.0 * dv * grid[0] * grid[1]


def vision_flops(cfg: dict, patches: float, grid) -> float:
    """FLOPs the tower needs for ``patches`` REAL patches (a padded frame's
    are not credited)."""
    return patches * vision_patch_flops(cfg, grid)


def index_select_least(cfg: dict, rows_scored: float, peak: dict) -> float:
    return max(rows_scored * index_key_bytes(cfg) / peak["hbm_bytes_per_s"],
               rows_scored * scored_position_flops(cfg) / peak["bf16_flops_per_s"])


def sparse_gqa_read_least(cfg: dict, rows_read: float, peak: dict) -> float:
    """Least seconds reading ``rows_read`` cached positions' k and v of a layer
    and attending them could take (the kernel's own roofline over what it
    READ, not over what was selected)."""
    return max(rows_read * kv_row_bytes(cfg) / peak["hbm_bytes_per_s"],
               rows_read * attended_position_flops(cfg) / peak["bf16_flops_per_s"])


def counts_between(before: dict, after: dict, chunk_steps: int):
    """``flops_dots3_note.counts_between`` and, beside it, what the tower's
    counters moved by (``cache_tiles.vision_*``: the numbers of
    ``perf_stats()["vision"]``, carried where a traced replica reads)."""
    out = _counts_between(before, after, chunk_steps)
    moved = {k: _delta(before, after, "cache_tiles", "vision_" + k)
             for k in VISION_COUNTERS}
    if out is not None and all(v is not None for v in moved.values()):
        out["vision"] = moved
    return out


def window_counts(raw: dict):
    return counts_between(raw.get("engine_before"), raw.get("engine_after"),
                          raw["chunk_steps"])


def traced_counts(raw: dict):
    ends = (raw.get("trace") or {}).get("counters")
    if not ends:
        return None
    return counts_between(ends["start"], ends["stop"], raw["chunk_steps"])


__all__ = ["Touched", "TILE", "live_rows_between", "counts_between",
           "window_counts", "traced_counts"]
