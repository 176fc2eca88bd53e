"""Operations and least bytes of the dots3-note family's decode step and
prefill (two kinds of latent attention, a learned index over the full layers'
cached positions, held experts), from the configuration (``model_config``: the
program's keywords) and the run's counters.  Pure host-side Python, no jax.
The interface of ``flops_k_exaone.py``, whose readers of the client's records
are this family's too.

As in ``flops.py``: what the ALGORITHM needs, never what the program happens
to do.  Padded rows, rows of idle slots, dead cache positions, experts no live
token chose and LATENT ROWS THE SELECTION DID NOT CHOOSE are not credited, so
waste (a read of every live tile under a mask) shows as a low share.

What differs from the other expert families' counts:

- a full layer's decode step has to SCORE every cached position of a live row
  (one 128-value index key, 256 bytes in bf16), and to READ only the rows it
  selected (``min(context, index_topk)`` of 1,152 bytes);
- a sliding layer reads ``min(context, window)`` rows of 2,176 bytes a live
  row, whatever the ring holds;
- steps and DISPATCHES both come from the counters (a dispatch is a whole or
  a cut chunk since PR 40: PERF.md 7.12), never ``steps / 16``.
"""

from __future__ import annotations

from benchmark.flops_k_exaone import (  # noqa: F401 — the interface
    TILE,
    _delta,
    live_rows_between,
)

FULL, WINDOW = "full_attention", "sliding_attention"


def kinds(cfg: dict) -> list:
    """Per layer, its kind (``layer_types``, or the family's default: two
    full layers, then three sliding and a full one, repeated)."""
    given = list(cfg.get("layer_types") or [])[:cfg["n_layers"]]
    return given or [FULL if l == 0 or l % 4 == 1 else WINDOW
                     for l in range(cfg["n_layers"])]


def full_layers(cfg: dict) -> int:
    return kinds(cfg).count(FULL)


def window_layers(cfg: dict) -> int:
    return kinds(cfg).count(WINDOW)


def sparse_layers(cfg: dict) -> int:
    return cfg["n_layers"] - cfg.get("first_dense_layers", 1)


def _latent(cfg: dict, pre: str) -> dict:
    get = lambda name: cfg[pre + name]  # noqa: E731
    return {"heads": get("n_heads"), "rq": get("q_lora_rank"),
            "rkv": get("kv_lora_rank"), "nope": get("qk_nope_head_dim"),
            "pe": get("qk_rope_head_dim"), "dv": get("v_head_dim")}


def full_row_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """A cached position of a full layer's attention (1,152 as published)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_value


def index_key_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """A cached position's index key (256 as published)."""
    return cfg["index_head_dim"] * bytes_per_value


def window_row_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """A ring entry of a sliding layer (2,176 as published)."""
    return (cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]) * bytes_per_value


def parts(cfg: dict) -> dict:
    """Parameters of each part, from the sizes alone."""
    d = cfg["d_model"]

    def attention(z):
        # W_dq and its norm, W_uq, W_dkv and its norm, W_uk | W_uv, the gate,
        # W_o, and the layer's two norms
        return (d * z["rq"] + z["rq"] + z["rq"] * z["heads"] * (z["nope"] + z["pe"])
                + d * (z["rkv"] + z["pe"]) + z["rkv"]
                + z["rkv"] * z["heads"] * (z["nope"] + z["dv"])
                + d * z["heads"] + z["heads"] * z["dv"] * d + 2 * d)

    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return {
        "full_attention": attention(_latent(cfg, "")),
        "window_attention": attention(_latent(cfg, "swa_")),
        # W_qI, W_kI and its LayerNorm, W_w
        "indexer": cfg["q_lora_rank"] * hi * di + d * di + 2 * di + d * hi,
        "dense_ffn": 3 * d * cfg["d_ff"],
        "expert": 3 * d * cfg["d_expert"],
        "shared": 3 * d * cfg["d_expert"] * cfg["n_shared_experts"],
        "router": d * cfg["n_experts"] + cfg["n_experts"],
        "head": d * cfg["vocab_size"] + d,
        "embedding": cfg["vocab_size"] * d,
    }


def always_read_params(cfg: dict) -> int:
    """What every decode step reads whatever the routing and the selection:
    both attentions' projections, the indexer's, the dense layer, routers,
    shared experts, the head."""
    p, n_sparse = parts(cfg), sparse_layers(cfg)
    return (full_layers(cfg) * (p["full_attention"] + p["indexer"])
            + window_layers(cfg) * p["window_attention"]
            + (cfg["n_layers"] - n_sparse) * p["dense_ffn"]
            + n_sparse * (p["router"] + p["shared"]) + p["head"])


def param_count(cfg: dict) -> int:
    """Parameters this chip holds (``experts_held[1]`` experts a sparse layer)."""
    p = parts(cfg)
    return (always_read_params(cfg) + p["embedding"]
            + sparse_layers(cfg) * cfg["experts_held"][1] * p["expert"])


def token_matmul_params(cfg: dict, held_pairs: float) -> float:
    return always_read_params(cfg) + sparse_layers(cfg) * held_pairs * parts(cfg)["expert"]


def attended_position_flops(cfg: dict) -> int:
    """The absorbed decode form against one cached row of a full layer: every
    head's score over the row and value sum over its latent (128 x (576 +
    512) x 2 = 278,528 as published)."""
    z = _latent(cfg, "")
    return 2 * z["heads"] * (2 * z["rkv"] + z["pe"])


def scored_position_flops(cfg: dict) -> int:
    """One index score: 64 heads x 128 values, a product and a sum (16,384)."""
    return 2 * cfg["index_n_heads"] * cfg["index_head_dim"]


class Touched(float):
    """The touched experts a step, carrying the rows the step's selection
    scored and chose (summed over the full layers).  The accepted reader
    (``layer_metrics/model.moe_decode_roofline_pct.py``) hands
    :func:`decode_step_bytes` the touched experts and a count of live TILES
    of one width; this family's rows have three widths and only the selected
    ones are needed, so what the cache costs rides on the first argument (as
    ``flops_granite_hybrid.Touched`` carries the state's rows)."""

    rows_scored = rows_selected = 0.0

    def __new__(cls, value, rows_scored, rows_selected):
        out = super().__new__(cls, value)
        out.rows_scored, out.rows_selected = float(rows_scored), float(rows_selected)
        return out


def cache_bytes(cfg: dict, rows_scored: float, rows_selected: float) -> float:
    """Least cache bytes of decode steps whose full layers scored and chose
    that many rows (both summed over the full layers): an index key a scored
    row, a latent row a chosen one, and a sliding layer's ``min(context,
    window)`` rows a live row (a live row's context is what ONE full layer
    scored for it; the live rows are the chosen rows over ``index_topk``
    where contexts exceed it, which this family's cell makes them)."""
    n_full = max(full_layers(cfg), 1)
    contexts = rows_scored / n_full
    live_rows = rows_selected / n_full / cfg["index_topk"]
    windowed = min(contexts, live_rows * cfg["sliding_window"])
    return (rows_scored * index_key_bytes(cfg) + rows_selected * full_row_bytes(cfg)
            + window_layers(cfg) * windowed * window_row_bytes(cfg))


def decode_step_flops(cfg: dict, live_rows: float, held_pairs: float,
                      attended_positions: float) -> float:
    """``2 x`` the matmul parameters a live row, and the absorbed attention a
    position attended as the accepted reader counts them (live tiles), at the
    full layer's cost: an upper estimate of a term that is a fortieth of the
    bytes' time, so it never binds."""
    return (2.0 * token_matmul_params(cfg, held_pairs) * live_rows
            + attended_position_flops(cfg) * attended_positions)


def decode_step_bytes(cfg: dict, touched_experts: float, live_tiles: float,
                      bytes_per_value: int = 2) -> float:
    """Least bytes one decode step reads: the always-read weights, the
    DISTINCT held experts some live token chose, and of the cache what the
    selection's counts say (:func:`cache_bytes` of ``touched_experts``'s rows:
    the index keys scored, the rows selected, the sliding layers' windows);
    ``live_tiles`` is not used."""
    return (bytes_per_value * (always_read_params(cfg)
                               + float(touched_experts) * parts(cfg)["expert"])
            + cache_bytes(cfg, getattr(touched_experts, "rows_scored", 0.0),
                          getattr(touched_experts, "rows_selected", 0.0)))


def prefill_flops(cfg: dict, prompt_lens, held_pairs: float) -> float:
    """FLOPs the LIVE prompt tokens need: every real token through the layers
    (the head for the last token of a prompt only); a full layer scores ``T (T
    + 1) / 2`` pairs with its index and attends, un-absorbed, the ``min(t + 1,
    index_topk)`` positions a row selects; a sliding layer ``min(t + 1,
    window)``."""
    p = parts(cfg)
    per_token = 2.0 * (token_matmul_params(cfg, held_pairs) - p["head"])
    pair = lambda z: 2.0 * z["heads"] * (z["nope"] + z["pe"] + z["dv"])  # noqa: E731
    full, window = pair(_latent(cfg, "")), pair(_latent(cfg, "swa_"))
    bounded = lambda t, k: (  # noqa: E731 — sum over rows of min(row + 1, k)
        t * (t + 1) / 2.0 if t <= k else k * (k + 1) / 2.0 + (t - k) * k)
    total = 0.0
    for t in prompt_lens:
        total += (per_token * t + 2.0 * p["head"]
                  + full_layers(cfg) * (
                      scored_position_flops(cfg) * t * (t + 1) / 2.0
                      + full * bounded(t, cfg["index_topk"]))
                  + window_layers(cfg) * window * bounded(t, cfg["sliding_window"]))
    return total


def index_select_least(cfg: dict, rows_scored: float, peak: dict) -> float:
    """Least seconds scoring ``rows_scored`` cached positions could take (one
    index key read a position; the selection itself moves nothing more): the
    larger of the bytes over the HBM peak and the FLOPs over the bf16 peak."""
    return max(rows_scored * index_key_bytes(cfg) / peak["hbm_bytes_per_s"],
               rows_scored * scored_position_flops(cfg) / peak["bf16_flops_per_s"])


def sparse_read_least(cfg: dict, rows_read: float, peak: dict) -> float:
    """Least seconds reading ``rows_read`` latent rows of a full layer and
    attending them could take (the kernel's own roofline over what it READ,
    not over what was selected)."""
    return max(rows_read * full_row_bytes(cfg) / peak["hbm_bytes_per_s"],
               rows_read * attended_position_flops(cfg) / peak["bf16_flops_per_s"])


def counts_between(before: dict, after: dict, chunk_steps: int):
    """What the engine's counters say of the load between two
    ``perf_stats()`` reads, per decode step or prefill where that is the
    natural unit; None where the program has no such counters or no chunk was
    drained between the reads.  Steps from ``moe.decode_steps``, dispatches
    from ``moe.decode_dispatches``; the selection's rows from
    ``moe.decode.dsa_*`` (one value a full layer, the device's own count: the
    same numbers as ``perf_stats()["dsa"]``, which the traced replica does
    not read)."""
    steps = _delta(before, after, "moe", "decode_steps")
    tiles = _delta(before, after, "cache_tiles", "read_full")
    if not steps or tiles is None:
        return None
    dispatches = (_delta(before, after, "moe", "decode_dispatches")
                  or steps / chunk_steps)
    layers = after["cache_tiles"].get("layers") or {"full": 0, "window": 0}
    decode_tokens = _delta(before, after, "moe", "decode", "tokens")  # [layer][expert]
    touched = _delta(before, after, "moe", "decode", "touched")       # [layer]
    out = {
        "decode_steps": steps,
        "dispatches": dispatches,
        "layers": layers,
        "full_tiles_per_step": tiles / dispatches,
        "window_tiles_read_per_step":
            (_delta(before, after, "cache_tiles", "read_window") or 0) / dispatches,
        "padded_tiles_per_step":
            _delta(before, after, "cache_tiles", "padded") / dispatches,
        "held_pairs_per_step": sum(map(sum, decode_tokens)) / steps,
        "expert_tokens_decode": decode_tokens,
        "expert_tokens_prefill": _delta(before, after, "moe", "prefill", "tokens"),
    }
    rows = {k: _delta(before, after, "moe", "decode", "dsa_" + k)
            for k in ("scored", "selected", "read")}
    out["touched_experts_per_step"] = sum(touched) / steps
    if all(v is not None for v in rows.values()):
        # summed over the full layers
        out["dsa"] = {"rows_" + k: float(sum(v)) for k, v in rows.items()}
        for k, v in out["dsa"].items():
            out[f"dsa_{k}_per_step"] = v / steps
        out["touched_experts_per_step"] = Touched(
            out["touched_experts_per_step"],
            out["dsa_rows_scored_per_step"], out["dsa_rows_selected_per_step"])
    earlier = (before or {}).get("prefill") or {}
    out["prefill"] = {
        b: {k: v - earlier.get(b, {}).get(k, 0) for k, v in row.items()}
        for b, row in (after.get("prefill") or {}).items()}
    return out


def window_counts(raw: dict):
    """:func:`counts_between` the driver's two reads: pre-roll and window."""
    return counts_between(raw.get("engine_before"), raw.get("engine_after"),
                          raw["chunk_steps"])


def traced_counts(raw: dict):
    """:func:`counts_between` the replica's reads at the two ends of the
    traced interval (``raw["trace"]["counters"]``)."""
    ends = (raw.get("trace") or {}).get("counters")
    if not ends:
        return None
    return counts_between(ends["start"], ends["stop"], raw["chunk_steps"])
