"""Operations and least bytes of the Granite-hybrid family's decode step and
prefill (Mamba-2 layers with a per-request state, a few attention layers,
held experts and a shared MLP on every layer, a tied head), from the
configuration (``model_config``: the program's keywords) and the run's
counters.  Pure host-side Python, no jax.  The interface of
``flops_k_exaone.py``, whose readers of the client's records are this
family's too.

As in ``flops.py``: what the ALGORITHM needs, never what the program happens
to do.  Padded rows, rows of idle slots, dead cache positions, experts no live
token chose and the STATE OF ROWS THAT TOOK NO STEP are not credited, so waste
(a masked update over every row) shows as a low share.

What differs from the other two expert families' counts:

- a decode step reads AND writes the state of every live row of every Mamba
  layer (4,194,304 bytes of float32 state and 50,688 of convolution inputs a
  row a layer as published), whatever the row's position;
- steps and DISPATCHES both come from the counters (a dispatch is a whole or
  a cut chunk since PR 40: PERF.md 7.12), never ``steps / 16``: the cache
  tiles are counted once a dispatch and are divided by the dispatches.
"""

from __future__ import annotations

from benchmark.flops_k_exaone import (  # noqa: F401 — the interface
    TILE,
    _delta,
    live_rows_between,
)


def kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["n_layers"]])


def mamba_layers(cfg: dict) -> int:
    return kinds(cfg).count("mamba")


def attention_layers(cfg: dict) -> int:
    return kinds(cfg).count("attention")


def sparse_layers(cfg: dict) -> int:
    """Every layer routes."""
    return cfg["n_layers"]


def d_inner(cfg: dict) -> int:
    return cfg["mamba_heads"] * cfg["mamba_head_dim"]


def conv_width(cfg: dict) -> int:
    return d_inner(cfg) + 2 * cfg["mamba_state"]


def parts(cfg: dict) -> dict:
    """Parameters of each part, from the sizes alone."""
    d, di, c = cfg["d_model"], d_inner(cfg), conv_width(cfg)
    heads, hd = cfg["mamba_heads"], cfg["head_dim"]
    return {
        # W_in, the convolution and its bias, dt_bias | A_log | D, the gated
        # norm's scale, W_out
        "mamba": (d * (di + c + heads) + c * cfg["mamba_conv"] + c + 3 * heads
                  + di + di * d),
        "attention": (cfg["n_heads"] + 2 * cfg["n_kv_heads"]) * hd * d
                     + cfg["n_heads"] * hd * d,
        "norms": 2 * d,
        "router": d * cfg["n_experts"],
        "shared": 3 * d * cfg["d_shared"],
        "expert": 3 * d * cfg["d_expert"],
        # the head IS the embedding's rows held here, and the final norm
        "head": cfg["vocab_size"] * d + d,
    }


def param_count(cfg: dict) -> int:
    """Parameters this chip holds (``experts_held[1]`` experts a layer)."""
    p = parts(cfg)
    return (mamba_layers(cfg) * p["mamba"] + attention_layers(cfg) * p["attention"]
            + cfg["n_layers"] * (p["norms"] + p["router"] + p["shared"]
                                 + cfg["experts_held"][1] * p["expert"])
            + p["head"])


def always_read_params(cfg: dict) -> int:
    """What every decode step reads whatever the routing and whoever is
    live: both mixers' weights, norms, routers, shared MLPs, the tied head
    (an embedding ROW a token is not worth counting)."""
    p = parts(cfg)
    return (mamba_layers(cfg) * p["mamba"] + attention_layers(cfg) * p["attention"]
            + cfg["n_layers"] * (p["norms"] + p["router"] + p["shared"])
            + p["head"])


def token_matmul_params(cfg: dict, held_pairs: float) -> float:
    """Parameters one token's matmuls touch: the always-read ones and
    ``held_pairs`` held experts a layer."""
    return always_read_params(cfg) + cfg["n_layers"] * held_pairs * parts(cfg)["expert"]


def state_values(cfg: dict) -> int:
    """Values of recurrent state a row holds a Mamba layer (1,048,576)."""
    return cfg["mamba_heads"] * cfg["mamba_head_dim"] * cfg["mamba_state"]


def state_row_bytes(cfg: dict, with_inputs: bool = True) -> int:
    """Bytes a row holds a Mamba layer: the float32 state and, ``with_inputs``,
    the convolution's last ``d_conv - 1`` inputs in bf16 (4,244,992)."""
    tail = (cfg["mamba_conv"] - 1) * conv_width(cfg) * 2 if with_inputs else 0
    return 4 * state_values(cfg) + tail


# a state value a step: the decay's product, the outer product, their sum,
# the product with C and its sum
STATE_FLOPS = 6


def tile_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """One 128-position tile of an attention layer's K and V."""
    return 2 * cfg["n_kv_heads"] * cfg["head_dim"] * TILE * bytes_per_value


class Touched(float):
    """The touched experts a step, carrying the live rows of state a step.
    The accepted reader (``layer_metrics/model.moe_decode_roofline_pct.py``)
    hands :func:`decode_step_bytes` the touched experts and the live tiles and
    nothing else, so what else the step has to move rides on the first."""

    state_rows = 0.0

    def __new__(cls, value, state_rows):
        out = super().__new__(cls, value)
        out.state_rows = float(state_rows)
        return out


def decode_step_flops(cfg: dict, live_rows: float, held_pairs: float,
                      attended_positions: float) -> float:
    """``2 x`` the matmul parameters a live row, ``4 * heads * head_dim`` a
    position attended (summed over the attention layers in
    ``attended_positions``), ``STATE_FLOPS`` a state value a live row a
    Mamba layer."""
    return (2.0 * token_matmul_params(cfg, held_pairs) * live_rows
            + 4.0 * cfg["n_heads"] * cfg["head_dim"] * attended_positions
            + STATE_FLOPS * state_values(cfg) * mamba_layers(cfg) * live_rows)


def decode_step_bytes(cfg: dict, touched_experts: float, live_tiles: float,
                      bytes_per_value: int = 2) -> float:
    """Least bytes one decode step moves: the always-read weights, the
    DISTINCT held experts some live token chose (summed over the layers in
    ``touched_experts``), the live K/V tiles (summed over the attention
    layers in ``live_tiles``), and the state of the live rows (``touched_experts
    .state_rows``: :class:`Touched`) of every Mamba layer READ AND WRITTEN."""
    state_rows = getattr(touched_experts, "state_rows", 0.0)
    return (bytes_per_value * (always_read_params(cfg)
                               + float(touched_experts) * parts(cfg)["expert"])
            + live_tiles * tile_bytes(cfg, bytes_per_value)
            + 2.0 * state_rows * mamba_layers(cfg) * state_row_bytes(cfg))


def prefill_flops(cfg: dict, prompt_lens, held_pairs: float) -> float:
    """FLOPs the LIVE prompt tokens need: every real token through the layers
    (the head for the last token of a prompt only), an attention layer ``T (T
    + 1) / 2`` pairs a prompt, a Mamba layer the recurrence's
    ``STATE_FLOPS`` a state value a token (what the chunked form adds to
    that is the program's choice, not the algorithm's need)."""
    p = parts(cfg)
    per_token = (2.0 * (token_matmul_params(cfg, held_pairs) - p["head"])
                 + STATE_FLOPS * state_values(cfg) * mamba_layers(cfg))
    per_pair = 4.0 * cfg["n_heads"] * cfg["head_dim"] * attention_layers(cfg)
    return sum(per_token * t + 2.0 * p["head"] + per_pair * t * (t + 1) / 2.0
               for t in prompt_lens)


def state_update_least(cfg: dict, row_steps: float, peak: dict) -> float:
    """Least seconds the state's step could take for ``row_steps`` live rows
    x steps (a row a step is every Mamba layer's state once): the larger of
    its FLOPs over the bf16 peak and the float32 state read and written over
    the HBM peak (8,388,608 bytes a row a layer: bytes-bound by 60 x)."""
    per_row = mamba_layers(cfg) * state_values(cfg)
    return max(row_steps * per_row * STATE_FLOPS / peak["bf16_flops_per_s"],
               row_steps * per_row * 2 * 4 / peak["hbm_bytes_per_s"])


def counts_between(before: dict, after: dict, chunk_steps: int):
    """What the engine's counters say of the load between two
    ``perf_stats()`` reads, per decode step or prefill where that is the
    natural unit; None where the program has no such counters or no chunk was
    drained between the reads.  Steps from ``moe.decode_steps``, dispatches
    from ``moe.decode_dispatches`` (a program that does not count them:
    ``steps / chunk_steps``)."""
    steps = _delta(before, after, "moe", "decode_steps")
    tiles = _delta(before, after, "cache_tiles", "read_full")
    if not steps or tiles is None:
        return None
    dispatches = (_delta(before, after, "moe", "decode_dispatches")
                  or steps / chunk_steps)
    layers = after["cache_tiles"].get("layers") or {"full": 0, "window": 0}
    decode_tokens = _delta(before, after, "moe", "decode", "tokens")  # [layer][expert]
    touched = _delta(before, after, "moe", "decode", "touched")       # [layer]
    rows = _delta(before, after, "moe", "decode", "rows") or [0]      # [layer]
    out = {
        "decode_steps": steps,
        "dispatches": dispatches,
        "layers": layers,
        # an attention layer's live tiles a step (counted once a dispatch)
        "full_tiles_per_step": tiles / dispatches,
        "window_tiles_read_per_step": 0.0,
        "padded_tiles_per_step":
            _delta(before, after, "cache_tiles", "padded") / dispatches,
        # summed over the layers, a step
        "held_pairs_per_step": sum(map(sum, decode_tokens)) / steps,
        # the rows that took a step (counted on the device, a layer's)
        "state_rows_per_step": rows[0] / steps,
        "expert_tokens_decode": decode_tokens,
        "expert_tokens_prefill": _delta(before, after, "moe", "prefill", "tokens"),
    }
    out["touched_experts_per_step"] = Touched(
        sum(touched) / steps, out["state_rows_per_step"])
    # the engine's own count of the state's rows (window reads only: the
    # traced replica reads three other keys)
    state = _delta(before, after, "state", "rows_live")
    if state:
        out["state"] = {k: _delta(before, after, "state", k) for k in (
            "rows_updated", "rows_live", "steps", "dispatches")}
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
