"""Operations of the SmallThinker family as it is TRAINED, from its sizes
alone (``cfg``: the configuration file's ``model_config``, the program's own
keywords).  Pure host-side Python, no jax.  As in ``flops.py`` every function
counts what the ALGORITHM needs: no credit for recomputation under remat, for
a trip's rows past its groups, or for the cells of the score matrix outside a
layer's band."""

from __future__ import annotations


def layer_params(cfg: dict) -> dict:
    """One layer's parameters by part (every layer is alike)."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    expert = 3 * d * cfg["d_expert"]
    return {"attention": d * q + 2 * d * kv + q * d, "norms": 2 * d,
            "router": d * cfg["n_experts"], "expert": expert,
            "experts": cfg["n_experts"] * expert}


def param_count(cfg: dict) -> int:
    """Every parameter held: the layers, embedding, untied head, final norm."""
    p = layer_params(cfg)
    layer = p["attention"] + p["norms"] + p["router"] + p["experts"]
    return (cfg["n_layers"] * layer
            + 2 * cfg["vocab_size"] * cfg["d_model"] + cfg["d_model"])


def active_params(cfg: dict) -> int:
    """Parameters a token's matmuls use: a layer's attention, router and
    ``experts_per_token`` experts, and the head (the embedding is a lookup)."""
    p = layer_params(cfg)
    return (cfg["n_layers"] * (p["attention"] + p["router"]
                               + cfg["experts_per_token"] * p["expert"])
            + cfg["vocab_size"] * cfg["d_model"])


def layouts(cfg: dict) -> list:
    """Per layer ``(rotary, window)``, as the program reads its two layouts
    (left out: 0, 1, 1, 1 repeated)."""
    n = cfg["n_layers"]
    default = [int(l % 4 != 0) for l in range(n)]
    rope = list(cfg.get("rope_layout") or default)[:n]
    band = list(cfg.get("sliding_window_layout") or default)[:n]
    return [(r, cfg["sliding_window"] * w) for r, w in zip(rope, band)]


def mean_attended_keys(seq_len: int, window: int) -> float:
    """Keys a query attends, averaged over the positions of a sequence:
    causal, ``i - window < j <= i`` under a window (0: none)."""
    if not window or window >= seq_len:
        return (seq_len + 1) / 2.0
    head = window * (window + 1) / 2.0           # positions 0..window-1
    return (head + (seq_len - window) * window) / seq_len


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Scores and values, forward and backward (3 x 4 x heads x head_dim x
    keys attended), each layer under its own band."""
    width = cfg["n_heads"] * cfg["head_dim"]
    return sum(12.0 * width * mean_attended_keys(seq_len, window)
               for _, window in layouts(cfg))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs a trained token: ``6 x`` ACTIVE parameters plus attention."""
    return 6.0 * active_params(cfg) + attention_flops_per_token(cfg, seq_len)


def expert_flops_per_pair(cfg: dict) -> float:
    """The grouped matmuls' work for one routed (token, expert) pair, forward
    and backward: ``6 x`` one expert's three matrices, whatever implements it."""
    return 6.0 * layer_params(cfg)["expert"]


def chip_load_max_over_mean(pairs, chips: int) -> float:
    """``pairs [L][E]`` routed pairs a layer and expert, experts in ``chips``
    contiguous blocks: the fullest chip's pairs over the mean chip's, of the
    worst layer (the straggler an exchange of tokens would wait for)."""
    worst = 0.0
    for row in pairs:
        held = len(row) // chips
        load = [float(sum(row[c * held:(c + 1) * held])) for c in range(chips)]
        if sum(load):
            worst = max(worst, max(load) * chips / sum(load))
    return worst
