"""Operations and least bytes of Phi-4-mini-flash's decode step and prefill (a
decoder-hybrid-decoder: Mamba-1 and window attention below, ONE full layer's K
and V read by the cross-attention layers above, gated memory units:
``ray_tpu.models.phi4_flash``), from the configuration (``model_config``: the
program's keywords) and the run's counters.  Pure host-side Python, no jax.

As in ``flops.py``: what the ALGORITHM needs, never what the program happens
to do.  A decode step needs the weights once, the live tiles of the ONE slab
once a layer that reads it (its owner and every cross layer: eight as
published), the live tiles of a window layer's ring, and a live row's state
in and out a Mamba layer.  The program reads slab and rings in tiles of 128
positions, and the counters here count the tiles READ
(``perf_stats()["cache_tiles"]["yoco_*"]``, counted on the host at dispatch),
so a share of a roofline holds the bytes that moved against the time they
took.  A prompt needs the lower half and the slab's K and V at every position
and the layers above for its LAST position alone.

The counters ride in ``cache_tiles`` because the traced replica of
``drivers/serve_family.py`` reads three keys of ``perf_stats()`` at the
trace's two ends (``moe``, ``cache_tiles``, ``prefill``) and this family has
no experts.  :func:`traced_counts`, the name the expert families' whole-step
reader calls, answers None here; this family's readers call
:func:`yoco_traced_counts`.
"""

from __future__ import annotations

from benchmark.flops_k_exaone import (  # noqa: F401 — the interface
    TILE,
    _delta,
    live_rows_between,
)

COUNTERS = ("slab_tile_steps", "ring_tile_steps", "state_row_steps",
            "row_steps", "steps", "dispatches", "prefill_positions",
            "upper_positions")
def kinds(cfg: dict) -> list:
    half = cfg["n_layers"] // 2
    return [("mamba" if l % 2 == 0 else "window") if l <= half
            else "full" if l == half + 1
            else ("gmu" if l % 2 == 0 else "cross")
            for l in range(cfg["n_layers"])]


def sizes(cfg: dict) -> dict:
    d = cfg["d_model"]
    return {"d": d, "f": cfg["d_ff"], "di": cfg.get("mamba_expand", 2) * d,
            "n": cfg.get("mamba_state", 16), "conv": cfg.get("mamba_conv", 4),
            "rank": cfg.get("mamba_dt_rank") or -(-d // 16),
            "dh": d // cfg["n_heads"], "kv": cfg["n_kv_heads"] * d // cfg["n_heads"]}


def layer_params(cfg: dict, kind: str) -> int:
    """One layer of ``kind``: its mixer, the SwiGLU, two LayerNorms (119.90 M
    a Mamba layer, 98.32 M a window or full layer, 91.77 M a cross layer,
    104.87 M a gated memory unit, as published)."""
    s = sizes(cfg)
    d, f, di, n, r, dh, kv = (s[k] for k in ("d", "f", "di", "n", "rank", "dh", "kv"))
    mixer = {
        "mamba": (d * 2 * di + di * s["conv"] + di + di * (r + 2 * n) + r * di
                  + di + n * di + di + di * d),
        "gmu": 2 * d * di,
        "cross": 2 * (d * d + d) + 4 * dh + 2 * dh,
    }
    mixer["window"] = mixer["full"] = mixer["cross"] + 2 * (d * kv + kv)
    return mixer[kind] + 3 * d * f + 4 * d


def param_count(cfg: dict) -> int:
    """Every parameter: the layers, the tied embedding, the final norm
    (3,852,562,944 as published: the card's 3.8 B)."""
    return (sum(layer_params(cfg, k) for k in kinds(cfg))
            + cfg["vocab_size"] * cfg["d_model"] + 2 * cfg["d_model"])


def step_params(cfg: dict) -> int:
    """What every decode step has to read: all of it (the tied embedding IS
    the head)."""
    return param_count(cfg)


def slab_readers(cfg: dict) -> int:
    return 1 + kinds(cfg).count("cross")


def position_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """A cached position of the slab, or of ONE window layer's ring: K and V
    of every KV head (5,120 bytes as published)."""
    return 2 * sizes(cfg)["kv"] * bytes_per_value


def state_row_bytes(cfg: dict) -> int:
    """A row's state of ONE Mamba layer: float32 ``[d_inner, N]`` (327,680
    bytes as published), and its convolution's last inputs."""
    s = sizes(cfg)
    return 4 * s["di"] * s["n"] + 2 * (s["conv"] - 1) * s["di"]


def attended_flops(cfg: dict) -> int:
    """One query against one cached position of one layer, every head: a
    score over ``dh`` and a weighted sum over the pair's ``2 dh`` values."""
    return 2 * cfg["n_heads"] * 3 * sizes(cfg)["dh"]


def slab_bytes(cfg: dict, slab_tile_steps: float) -> float:
    return slab_tile_steps * TILE * position_bytes(cfg)


def state_bytes(cfg: dict, state_row_steps: float) -> float:
    """A live row's state read and written, a layer a step."""
    return 2.0 * state_row_steps * state_row_bytes(cfg)


def decode_bytes(cfg: dict, counts: dict) -> float:
    """Least bytes the counted decode steps read and write: the weights once a
    step, the slab tiles times their readers, the ring tiles, the states in
    and out."""
    return (2.0 * step_params(cfg) * counts["steps"]
            + (counts["slab_tile_steps"] + counts["ring_tile_steps"]) * TILE
            * position_bytes(cfg) + state_bytes(cfg, counts["state_row_steps"]))


def decode_flops(cfg: dict, counts: dict) -> float:
    return (2.0 * step_params(cfg) * counts["row_steps"]
            + attended_flops(cfg) * TILE
            * (counts["slab_tile_steps"] + counts["ring_tile_steps"]))


def shared_kv_least(cfg: dict, slab_tile_steps: float, peak: dict) -> float:
    """Least seconds reading the slab's tiles (times their readers) and
    attending them could take."""
    return max(slab_bytes(cfg, slab_tile_steps) / peak["hbm_bytes_per_s"],
               attended_flops(cfg) * TILE * slab_tile_steps
               / peak["bf16_flops_per_s"])


def state_update_least(cfg: dict, state_row_steps: float, peak: dict) -> float:
    return state_bytes(cfg, state_row_steps) / peak["hbm_bytes_per_s"]


def lower_params(cfg: dict) -> int:
    """What EVERY prompt position runs through: the layers below the slab and
    the slab's K and V projection."""
    ks = kinds(cfg)
    owner = ks.index("full")
    s = sizes(cfg)
    return (sum(layer_params(cfg, k) for k in ks[:owner])
            + 2 * (s["d"] * s["kv"] + s["kv"]))


def prefill_flops(cfg: dict, prompt_lens) -> float:
    """FLOPs the LIVE prompt tokens need: every position through the lower
    half and the slab's K and V (:func:`lower_params`; a window layer's scores
    over at most ``sliding_window`` keys; the scans' updates at 4 operations
    each and an exponential are NOT matmul FLOPs and are left out), and each
    prompt's LAST position through everything above: the rest of the owner,
    the upper layers, the slab read by every reader, the head."""
    ks = kinds(cfg)
    w = cfg["sliding_window"]
    upper = param_count(cfg) - lower_params(cfg)
    total = 0.0
    for t in prompt_lens:
        full, rest = max(t - w, 0), min(t, w)
        pairs = full * w + rest * (rest + 1) / 2.0
        total += (2.0 * lower_params(cfg) * t
                  + ks.count("window") * attended_flops(cfg) * pairs
                  + 2.0 * upper + slab_readers(cfg) * attended_flops(cfg) * t)
    return total


def part_flops(cfg: dict, tokens: int = 2048) -> float:
    """The matmul FLOPs of one prompt's part that ends no prompt."""
    return 2.0 * lower_params(cfg) * tokens


def counts_between(before: dict, after: dict):
    """What the engine's ``yoco_*`` counters say of the load between two
    ``perf_stats()`` reads (each the difference of a cumulative count), or
    None where the program has no such counters (the parent of the PR that
    adds the family) or no chunk was dispatched between the reads."""
    out = {k: _delta(before, after, "cache_tiles", "yoco_" + k) for k in COUNTERS}
    if any(v is None for v in out.values()) or not out["steps"]:
        return None
    earlier = (before or {}).get("prefill") or {}
    out["prefill"] = {
        b: {k: v - earlier.get(b, {}).get(k, 0) for k, v in row.items()}
        for b, row in (after.get("prefill") or {}).items()}
    out["slab_tiles_per_step"] = out["slab_tile_steps"] / out["steps"]
    out["ring_tiles_per_step"] = out["ring_tile_steps"] / out["steps"]
    out["state_rows_per_step"] = out["state_row_steps"] / out["steps"]
    out["live_rows_per_step"] = out["row_steps"] / out["steps"]
    return out


# what three accepted readers index in ``window_counts`` BEFORE they ask
# whether the family has it (``flops_evabyte.NOT_THIS_FAMILY``: the same)
NOT_THIS_FAMILY = {"full_tiles_per_step": 0, "expert_tokens_decode": [],
                   "expert_tokens_prefill": []}


def window_counts(raw: dict):
    """:func:`counts_between` the driver's two reads: pre-roll and window (the
    line's ``detail.window_counts``)."""
    counts = counts_between(raw.get("engine_before"), raw.get("engine_after"))
    return counts and {**counts, **NOT_THIS_FAMILY}


def yoco_traced_counts(raw: dict):
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
