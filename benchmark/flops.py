"""The yardstick's arithmetic: peaks, and the operations and bytes a model
needs.  Pure host-side Python, no jax.

Copied in spirit from ``ray_tpu/util/flops.py`` (sound, see PERF.md section 3)
so that a later PR may change the program but not the ruler; the HBM peak is
added.  Every function counts what the ALGORITHM needs — recomputation under
remat, padded rows and dead cache positions are never credited, so waste
shows as a low share instead of hiding in the denominator.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The peak table's row for a ``device_kind`` string.  Unknown kinds
    raise: a share of somebody else's peak is a made-up number."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    kind = (device_kind or "").lower()
    for key, row in table.items():
        if not key.startswith("_") and key in kind:
            return row
    raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")


def gpt2_param_count(cfg: dict) -> int:
    """Parameters of a GPT-2 with tied embeddings, from its sizes alone
    (``cfg`` holds the GPT2Config keywords)."""
    d, f, layers = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    block = (2 * d            # ln1
             + d * 3 * d + 3 * d   # qkv
             + d * d + d      # attention output
             + 2 * d          # ln2
             + d * f + f + f * d + d)  # mlp
    return (cfg["vocab_size"] * d + cfg["max_seq_len"] * d
            + layers * block + 2 * d)


def train_flops_per_token(n_params: int, n_layers: int, d_model: int,
                          seq_len: int) -> float:
    """Model FLOPs per trained token: ``6N`` for the matmuls forward and
    backward, ``12*L*d*T`` for attention scores and values.  No credit for
    recomputation."""
    return 6.0 * n_params + 12.0 * n_layers * d_model * seq_len


def decode_step_bytes(cfg: dict, live_positions: float,
                      bytes_per_value: int = 2) -> float:
    """Bytes one decode step has to read: every weight once in the serving
    type, plus the keys and values of the LIVE cache positions (summed over
    the batch's active sequences).  The padded, allocated cache is not
    counted — reading it is the waste this share exposes."""
    weights = gpt2_param_count(cfg) * bytes_per_value
    kv_per_position = 2 * cfg["n_layers"] * cfg["d_model"] * bytes_per_value
    return weights + kv_per_position * live_positions


def decode_step_flops(cfg: dict, n_active: float, live_positions: float) -> float:
    """FLOPs one decode step needs: ``2N`` per active sequence for the
    matmuls, ``4*L*d`` per live cache position for scores and values."""
    return (2.0 * gpt2_param_count(cfg) * n_active
            + 4.0 * cfg["n_layers"] * cfg["d_model"] * live_positions)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """``(least seconds, which bound)``: the larger of operations over peak
    FLOP/s and bytes over peak bytes/s."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
