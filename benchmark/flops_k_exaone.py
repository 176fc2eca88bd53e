"""Operations and least bytes of the EXAONE-MoE family's decode step and
prefill, from the configuration (``model_config``: the program's keywords)
and the run's counters.  Pure host-side Python, no jax.

As in ``flops.py``: what the ALGORITHM needs, never what the program happens
to do.  Padded rows, rows of idle slots, dead cache positions and experts no
live token chose are not credited, so waste shows as a low share.
"""

from __future__ import annotations

TILE = 128  # cache positions a tile (ray_tpu.ops.attention.DECODE_TILE)


def windows(cfg: dict) -> list:
    """Per layer: 0 a full layer, else the window."""
    kinds = cfg["layer_types"][:cfg["n_layers"]]
    return [cfg["sliding_window"] if k == "sliding_attention" else 0
            for k in kinds]


def sparse_layers(cfg: dict) -> int:
    return cfg["mlp_layer_types"][:cfg["n_layers"]].count("sparse")


def parts(cfg: dict) -> dict:
    """Parameters of each part, from the sizes alone."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    return {
        "attention": (cfg["n_heads"] + 2 * cfg["n_kv_heads"]) * hd * d
                     + cfg["n_heads"] * hd * d + 2 * d + 2 * hd,
        "dense_ffn": 3 * d * cfg["d_ff"],
        "expert": 3 * d * cfg["d_expert"],
        "shared": 3 * d * cfg["d_expert"] * cfg["n_shared_experts"],
        "router": d * cfg["n_experts"] + cfg["n_experts"],
        "head": d * cfg["vocab_size"] + d,
        "embedding": cfg["vocab_size"] * d,
    }


def param_count(cfg: dict) -> int:
    """Parameters this chip holds (``experts_held[1]`` experts a sparse layer)."""
    p, n_sparse = parts(cfg), sparse_layers(cfg)
    return (cfg["n_layers"] * p["attention"]
            + (cfg["n_layers"] - n_sparse) * p["dense_ffn"]
            + n_sparse * (p["router"] + p["shared"]
                          + cfg["experts_held"][1] * p["expert"])
            + p["head"] + p["embedding"])


def always_read_params(cfg: dict) -> int:
    """What every decode step reads whatever the routing: attention, the dense
    layers, routers, shared experts, the head (an embedding ROW a token is
    not worth counting)."""
    p, n_sparse = parts(cfg), sparse_layers(cfg)
    return (cfg["n_layers"] * p["attention"]
            + (cfg["n_layers"] - n_sparse) * p["dense_ffn"]
            + n_sparse * (p["router"] + p["shared"]) + p["head"])


def token_matmul_params(cfg: dict, held_pairs: float) -> float:
    """Parameters one token's matmuls touch: the always-read ones and
    ``held_pairs`` held experts a sparse layer (about ``top_k * held /
    n_experts``; a run's counters say how many)."""
    return always_read_params(cfg) + sparse_layers(cfg) * held_pairs * parts(cfg)["expert"]


def decode_step_flops(cfg: dict, live_rows: float, held_pairs: float,
                      attended_positions: float) -> float:
    """``2 x`` the matmul parameters a live row, ``4 * heads * head_dim`` a
    position attended (scores and values), summed over layers in
    ``attended_positions``."""
    return (2.0 * token_matmul_params(cfg, held_pairs) * live_rows
            + 4.0 * cfg["n_heads"] * cfg["head_dim"] * attended_positions)


def decode_step_bytes(cfg: dict, touched_experts: float, live_tiles: float,
                      bytes_per_value: int = 2) -> float:
    """Least bytes one decode step reads: the always-read weights, the
    DISTINCT held experts some live token chose (summed over the sparse
    layers in ``touched_experts``), and the live cache tiles (summed over
    layers in ``live_tiles``: a full layer a slot's tiles below its position,
    a window layer at most its window's tiles)."""
    tile = 2 * cfg["n_kv_heads"] * cfg["head_dim"] * TILE  # k and v
    return bytes_per_value * (
        always_read_params(cfg) + touched_experts * parts(cfg)["expert"]
        + live_tiles * tile)


def prefill_flops(cfg: dict, prompt_lens, held_pairs: float) -> float:
    """FLOPs the LIVE prompt tokens need: every real token through the layers
    (the head for the last token of a prompt only), attention over what each
    position may attend: a full layer ``T (T + 1) / 2`` pairs a prompt, a
    window layer at most the window a position."""
    p = parts(cfg)
    per_token = 2.0 * (token_matmul_params(cfg, held_pairs) - p["head"])
    per_pair = 4.0 * cfg["n_heads"] * cfg["head_dim"]
    total = 0.0
    for t in prompt_lens:
        total += per_token * t + 2.0 * p["head"]
        for w in windows(cfg):
            pairs = t * (t + 1) / 2.0
            if w and t > w:
                pairs = w * (w + 1) / 2.0 + (t - w) * w
            total += per_pair * pairs
    return total


def _delta(before: dict, after: dict, *path):
    """``after - before`` of a cumulative counter of ``perf_stats()`` (a
    number, or nested lists of numbers), or None where the program has none."""
    def at(node):
        for key in path:
            node = node.get(key) if isinstance(node, dict) else None
            if node is None:
                return None
        return node

    def sub(a, b):
        if isinstance(a, list):
            return [sub(x, y) for x, y in zip(a, b)] if b is not None else a
        return a - (b or 0)

    later = at(after or {})
    return None if later is None else sub(later, at(before or {}))


def counts_between(before: dict, after: dict, chunk_steps: int):
    """What the engine's counters say of the load between two
    ``perf_stats()`` reads, per decode step or prefill where that is the
    natural unit; None where the program has no such counters (the parent of
    the PR that adds them) or no chunk was drained between the reads."""
    steps = _delta(before, after, "moe", "decode_steps")
    tiles = _delta(before, after, "cache_tiles", "read_full")
    if not steps or tiles is None:
        return None
    dispatches = steps / chunk_steps
    layers = after["cache_tiles"].get("layers") or {"full": 0, "window": 0}
    decode_tokens = _delta(before, after, "moe", "decode", "tokens")  # [layer][expert]
    touched = _delta(before, after, "moe", "decode", "touched")       # [layer]
    out = {
        "decode_steps": steps,
        "layers": layers,
        # a full layer's live tiles a step (counted once a chunk, at its start)
        "full_tiles_per_step": tiles / dispatches,
        "window_tiles_read_per_step":
            _delta(before, after, "cache_tiles", "read_window") / dispatches,
        "padded_tiles_per_step":
            _delta(before, after, "cache_tiles", "padded") / dispatches,
        # summed over the sparse layers, a step
        "held_pairs_per_step": sum(map(sum, decode_tokens)) / steps,
        "touched_experts_per_step": sum(touched) / steps,
        "expert_tokens_decode": decode_tokens,
        "expert_tokens_prefill": _delta(before, after, "moe", "prefill", "tokens"),
    }
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
    traced interval (``raw["trace"]["counters"]``: taken after the profiler
    started and before it was stopped), so that a share of the traced step
    time counts the experts and tiles of the same steps."""
    ends = (raw.get("trace") or {}).get("counters")
    if not ends:
        return None
    return counts_between(ends["start"], ends["stop"], raw["chunk_steps"])


def live_rows_between(records, start: float, stop: float, every: float = 0.05):
    """Mean number of requests decoding at an instant of ``[start, stop]``
    that has any, from the client's records (``(record, prompt length)``
    pairs; a request decodes from its first token to its last)."""
    total = samples = 0
    t = start
    while t < stop:
        n = sum(1 for r, _ in records
                if r["times"] and r["times"][0] <= t <= r["times"][-1])
        if n:
            total, samples = total + n, samples + 1
        t += every
    return total / samples if samples else 0.0
