"""Operations and least bytes of EvaByte's decode step and prefill (an exact
window beside pooled summaries of the windows before it: ``ray_tpu.ops.eva``),
from the configuration (``model_config``: the program's keywords) and the
run's counters.  Pure host-side Python, no jax.

As in ``flops.py``: what the ALGORITHM needs, never what the program happens
to do.  Padded rows, rows of idle slots and dead cache places are not
credited.  A decode step needs, a live row a layer, the LIVE places of its
window (``pos % window``) and its ``(pos // window) x window / chunk`` summary
rows, 16,384 bytes each in bf16 as published (K and V, 32 heads of 128); the
program reads them in tiles of 128, and the counters here count the tiles
READ (``perf_stats()["cache_tiles"]["eva_*"]``, counted on the host at
dispatch), so a share of a roofline holds the bytes that moved against the
time they took and cannot pass 100 by crediting work nobody did.

The counters ride in ``cache_tiles`` because the traced replica of
``drivers/serve_family.py`` reads three keys of ``perf_stats()`` at the
trace's two ends (``moe``, ``cache_tiles``, ``prefill``) and this family has
no experts.  :func:`traced_counts`, the name the expert families' whole-step
reader calls, answers None here (that reader would go on to ask for held
pairs); this family's readers call :func:`eva_traced_counts`.
"""

from __future__ import annotations

from benchmark.flops_k_exaone import (  # noqa: F401 — the interface
    TILE,
    _delta,
    live_rows_between,
)

COUNTERS = ("window_tiles", "summary_tiles", "tile_steps", "row_steps",
            "read_positions", "attendable_positions", "rollovers",
            "chunks_pooled", "prefill_windows_pooled", "window_cuts", "steps",
            "dispatches")


def head_dim(cfg: dict) -> int:
    return cfg["d_model"] // cfg["n_heads"]


def layer_params(cfg: dict) -> int:
    """One layer: W_q and W_o, W_k and W_v, the SwiGLU, two norms, the two
    pooling vectors a head (202,391,552 as published)."""
    d, hd = cfg["d_model"], head_dim(cfg)
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    return (2 * d * q + 2 * d * kv + 3 * d * cfg["d_ff"] + 2 * d
            + 2 * cfg["n_kv_heads"] * hd)


def param_count(cfg: dict) -> int:
    """Parameters this chip holds: its layers, the embedding, the eight-way
    head, the final norm (1,630,932,992 for 8 layers)."""
    d, v = cfg["d_model"], cfg["vocab_size"]
    return (cfg["n_layers"] * layer_params(cfg) + v * d
            + d * cfg["n_pred_heads"] * v + d)


def step_params(cfg: dict) -> int:
    """What every decode step has to read: the layers, the final norm and the
    ONE prediction head the served path samples (the other seven are held and
    not read; an embedding row a live slot is nothing)."""
    d = cfg["d_model"]
    return cfg["n_layers"] * layer_params(cfg) + d + d * cfg["vocab_size"]


def row_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """A window place or a summary row of one layer: K and V of every KV head
    (16,384 as published)."""
    return 2 * cfg["n_kv_heads"] * head_dim(cfg) * bytes_per_value


def attended_flops(cfg: dict) -> int:
    """One query against one key and value of one layer, every head: a score
    and a weighted sum."""
    return 4 * cfg["n_heads"] * head_dim(cfg)


def cache_bytes(cfg: dict, tile_steps: float) -> float:
    """Bytes of ``tile_steps`` tiles of 128 places or rows (a layer each),
    over every layer."""
    return cfg["n_layers"] * tile_steps * TILE * row_bytes(cfg)


def decode_flops(cfg: dict, row_steps: float, tile_steps: float) -> float:
    """``2 x`` the parameters a step reads a live row a step, and the
    attention over the places and rows in the tiles read."""
    return (2.0 * step_params(cfg) * row_steps
            + cfg["n_layers"] * attended_flops(cfg) * TILE * tile_steps)


def decode_bytes(cfg: dict, steps: float, tile_steps: float,
                 bytes_per_value: int = 2) -> float:
    """Least bytes ``steps`` decode steps read: the weights once a step and
    the live tiles of window and summaries."""
    return bytes_per_value * step_params(cfg) * steps + cache_bytes(cfg, tile_steps)


def attention_read_least(cfg: dict, tile_steps: float, peak: dict) -> float:
    """Least seconds reading ``tile_steps`` tiles and attending them could
    take: the bytes over the HBM peak, or the FLOPs where they bind."""
    return max(cache_bytes(cfg, tile_steps) / peak["hbm_bytes_per_s"],
               cfg["n_layers"] * attended_flops(cfg) * TILE * tile_steps
               / peak["bf16_flops_per_s"])


def prefill_flops(cfg: dict, prompt_lens) -> float:
    """FLOPs the LIVE prompt bytes need: every real byte through the layers'
    matrices; position ``i``'s scores over its window up to itself (``i %
    window + 1``: the block-diagonal causal part) and over the summaries of
    every earlier window (``i // window`` windows of ``window / chunk`` rows);
    the pooling of every window a prompt fills (a score and two weighted sums
    a position a head); one row of head-0 logits a prompt."""
    w, rows = cfg["window_size"], cfg["window_size"] // cfg["chunk_size"]
    layers, d = cfg["n_layers"], cfg["d_model"]
    per_byte = 2.0 * layers * layer_params(cfg)
    pooled = 6.0 * cfg["n_kv_heads"] * head_dim(cfg)
    total = 0.0
    for t in prompt_lens:
        full, rest = divmod(t, w)
        pairs = (full * w * (w + 1) / 2.0 + rest * (rest + 1) / 2.0  # exact
                 + rows * (w * full * (full - 1) / 2.0 + rest * full))  # summaries
        total += (per_byte * t + 2.0 * d * cfg["vocab_size"]
                  + layers * (attended_flops(cfg) * pairs + pooled * full * w))
    return total


def counts_between(before: dict, after: dict):
    """What the engine's ``eva_*`` counters say of the load between two
    ``perf_stats()`` reads (each the difference of a cumulative count), or
    None where the program has no such counters (the parent of the PR that
    adds the family) or no chunk was dispatched between the reads."""
    out = {k: _delta(before, after, "cache_tiles", "eva_" + k) for k in COUNTERS}
    if any(v is None for v in out.values()) or not out["steps"]:
        return None
    earlier = (before or {}).get("prefill") or {}
    out["prefill"] = {
        b: {k: v - earlier.get(b, {}).get(k, 0) for k, v in row.items()}
        for b, row in (after.get("prefill") or {}).items()}
    out["tiles_per_step"] = out["tile_steps"] / out["steps"]
    out["live_rows_per_step"] = out["row_steps"] / out["steps"]
    return out


# what three accepted readers index in ``window_counts`` BEFORE they ask
# whether the family has it (``cache.window_read_share_pct``: no tiles a step
# -> None; ``moe.expert_load_max_over_mean``: no expert rows -> None): a family
# without window layers or experts says so in their own terms
NOT_THIS_FAMILY = {"full_tiles_per_step": 0, "expert_tokens_decode": [],
                   "expert_tokens_prefill": []}


def window_counts(raw: dict):
    """:func:`counts_between` the driver's two reads: pre-roll and window (the
    line's ``detail.window_counts``)."""
    counts = counts_between(raw.get("engine_before"), raw.get("engine_after"))
    return counts and {**counts, **NOT_THIS_FAMILY}


def eva_traced_counts(raw: dict):
    """:func:`counts_between` the replica's reads at the two ends of the
    traced interval (``raw["trace"]["counters"]``)."""
    ends = (raw.get("trace") or {}).get("counters")
    if not ends:
        return None
    return counts_between(ends["start"], ends["stop"])


def traced_counts(raw: dict):
    """None, always: the name the expert families' whole-step reader
    (``layer_metrics/model.moe_decode_roofline_pct.py``) calls, which would
    go on to ask for held experts (module docstring)."""
    return None
