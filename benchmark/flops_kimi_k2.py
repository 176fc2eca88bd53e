"""Operations and least bytes of the Kimi-K2 family's decode step and prefill
(multi-head latent attention, held experts), from the configuration
(``model_config``: the program's keywords) and the run's counters.  Pure
host-side Python, no jax.  The interface of ``flops_k_exaone.py``, whose
readers of the engine's counters are this family's too.

As in ``flops.py``: what the ALGORITHM needs, never what the program happens
to do.  Padded rows, rows of idle slots, dead cache positions and experts no
live token chose are not credited, so waste shows as a low share.
"""

from __future__ import annotations

from benchmark.flops_k_exaone import (  # noqa: F401 — the interface
    TILE,
    counts_between,
    live_rows_between,
    traced_counts,
    window_counts,
)


def latent_row(cfg: dict) -> int:
    """Values a cached position holds a layer: the normed latent and the
    rotated shared key (576 as published)."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def sparse_layers(cfg: dict) -> int:
    return cfg["n_layers"] - cfg.get("first_dense_layers", 1)


def parts(cfg: dict) -> dict:
    """Parameters of each part, from the sizes alone."""
    d, h = cfg["d_model"], cfg["n_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, pe, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    return {
        # W_dq and its norm, W_uq, W_dkv and its norm, W_uk | W_uv, W_o, and
        # the layer's two norms
        "attention": (d * rq + rq + rq * h * (nope + pe) + d * (rkv + pe) + rkv
                      + rkv * h * (nope + dv) + h * dv * d + 2 * d),
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
    """What every decode step reads whatever the routing: the latent
    attention's projections, the dense layer, routers, shared experts, the
    head (an embedding ROW a token is not worth counting)."""
    p, n_sparse = parts(cfg), sparse_layers(cfg)
    return (cfg["n_layers"] * p["attention"]
            + (cfg["n_layers"] - n_sparse) * p["dense_ffn"]
            + n_sparse * (p["router"] + p["shared"]) + p["head"])


def token_matmul_params(cfg: dict, held_pairs: float) -> float:
    """Parameters one token's matmuls touch: the always-read ones (``W_uk``
    and ``W_uv`` once each in either form of the attention) and
    ``held_pairs`` held experts a sparse layer."""
    return always_read_params(cfg) + sparse_layers(cfg) * held_pairs * parts(cfg)["expert"]


def attended_position_flops(cfg: dict) -> int:
    """The absorbed decode form: every head's ``kv_lora_rank + rope`` wide
    score and ``kv_lora_rank`` wide value sum against a position's one
    latent row (64 x (576 + 512) x 2 = 139,264 as published)."""
    return 2 * cfg["n_heads"] * (latent_row(cfg) + cfg["kv_lora_rank"])


def tile_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """One 128-position tile of a layer's latent cache (147,456 bytes as
    published, in bf16): read once for scores and values."""
    return latent_row(cfg) * TILE * bytes_per_value


def decode_step_flops(cfg: dict, live_rows: float, held_pairs: float,
                      attended_positions: float) -> float:
    """``2 x`` the matmul parameters a live row, and the absorbed attention
    a position attended, summed over layers in ``attended_positions``."""
    return (2.0 * token_matmul_params(cfg, held_pairs) * live_rows
            + attended_position_flops(cfg) * attended_positions)


def decode_step_bytes(cfg: dict, touched_experts: float, live_tiles: float,
                      bytes_per_value: int = 2) -> float:
    """Least bytes one decode step reads: the always-read weights, the
    DISTINCT held experts some live token chose (summed over the sparse
    layers in ``touched_experts``), and the live latent tiles (summed over
    layers in ``live_tiles``)."""
    return (bytes_per_value * (always_read_params(cfg)
                               + touched_experts * parts(cfg)["expert"])
            + live_tiles * tile_bytes(cfg, bytes_per_value))


def prefill_flops(cfg: dict, prompt_lens, held_pairs: float) -> float:
    """FLOPs the LIVE prompt tokens need: every real token through the layers
    (the head for the last token of a prompt only), and the UN-absorbed
    attention, ``T (T + 1) / 2`` pairs a prompt a layer at ``4 x heads x
    160`` (192-wide scores, 128-wide values)."""
    p = parts(cfg)
    per_token = 2.0 * (token_matmul_params(cfg, held_pairs) - p["head"])
    per_pair = 2.0 * cfg["n_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    total = 0.0
    for t in prompt_lens:
        total += (per_token * t + 2.0 * p["head"]
                  + cfg["n_layers"] * per_pair * t * (t + 1) / 2.0)
    return total


def latent_attention_least(cfg: dict, tile_reads: float, peak: dict) -> float:
    """Least seconds ``tile_reads`` tiles of latent decode attention could
    take (the kernel's own roofline): the larger of their FLOPs over the bf16
    peak and their bytes over the HBM peak."""
    return max(
        tile_reads * TILE * attended_position_flops(cfg) / peak["bf16_flops_per_s"],
        tile_reads * tile_bytes(cfg) / peak["hbm_bytes_per_s"])
