"""Analytical FLOPs / roofline model shared by bench and the live profiler.

One home for the MFU arithmetic: ``bench.py`` computed
``flops_per_token``/``peak_flops`` privately and once per run, which made
a LIVE per-step MFU impossible to compare against the end-of-run number
(any drift between two copies of the formula would make an "MFU
regressed" doctor rule meaningless).  Everything here is pure host-side
arithmetic — no jax import unless the XLA cross-check is asked for.

Conventions (unchanged from bench.py's originals):

- ``transformer_flops_per_token`` counts MODEL FLOPs only — ``6N``
  matmul fwd+bwd plus the ``12·L·D·T`` attention term; remat
  recomputation is never credited.
- ``peak_flops`` is the bf16 peak of the chip generation, keyed by
  substring of ``device.device_kind``.  A kind that is not in the table
  is an error: an MFU against somebody else's peak (a CPU run divided by
  the v5e number) is a made-up ratio, not a diagnostic.
"""

from __future__ import annotations

from typing import Any, Optional

# bf16 peak FLOP/s per chip, by generation (Google Cloud TPU documentation,
# the per-generation "System architecture" pages: v4 275, v5e 197, v5p 459,
# v6e 918 TFLOP/s).  MFU denominators.
PEAK_FLOPS_BF16 = {
    "v5 lite": 197e12, "v5litepod": 197e12, "v5e": 197e12,
    "v4": 275e12, "v5p": 459e12, "v6 lite": 918e12, "v6e": 918e12,
}


def peak_flops(device_kind: str) -> float:
    """bf16 peak FLOP/s for a device kind string (``jax.devices()[0]
    .device_kind``).  Raises ``KeyError`` for a kind the table does not
    hold — callers that only want a live diagnostic catch it and report
    no MFU (``StepProfiler``); a benchmark lets it fail."""
    kind = (device_kind or "").lower()
    for k, v in PEAK_FLOPS_BF16.items():
        if k in kind:
            return v
    raise KeyError(
        f"no bf16 peak for device kind {device_kind!r}: add it to "
        f"PEAK_FLOPS_BF16 with its source, or pass peak= explicitly")


def transformer_flops_per_token(n_params: int, n_layers: int,
                                d_model: int, seq_len: int) -> float:
    """Training FLOPs per token for a decoder transformer: ``6N`` matmul
    (fwd 2N + bwd 4N) + ``12·L·D·T`` attention score/value math, fwd+bwd
    folded into the constants.  Model FLOPs only (no remat credit)."""
    return 6.0 * n_params + 12.0 * n_layers * d_model * seq_len


def model_flops_per_token(cfg: Any, n_params: int) -> float:
    """``transformer_flops_per_token`` off a model config (anything with
    ``n_layers``/``d_model``/``max_seq_len`` — gpt2/llama/bert configs
    qualify).  ``n_params`` comes from the caller (``models.*.num_params``
    over an ``eval_shape`` pytree is free) so this agrees EXACTLY with
    the bench formula rather than re-estimating the count analytically."""
    return transformer_flops_per_token(
        int(n_params), int(cfg.n_layers), int(cfg.d_model),
        int(cfg.max_seq_len))


def decode_flops_per_token(n_params: int) -> float:
    """Inference decode FLOPs per generated token: the ``2N`` forward
    matmul cost (attention-over-cache is bandwidth-, not FLOP-bound at
    decode shapes, so the matmul term is the roofline numerator)."""
    return 2.0 * n_params


def mfu(tokens_per_sec: float, flops_per_token: float,
        device_kind: str = "", peak: Optional[float] = None) -> float:
    """Model FLOPs utilization: achieved model FLOP/s over the chip's
    bf16 peak.  ``peak`` overrides the device-kind lookup (tests, or a
    synthetic denominator asked for by name); without it an unknown
    ``device_kind`` raises (``peak_flops``)."""
    denom = peak if peak else peak_flops(device_kind)
    return tokens_per_sec * flops_per_token / denom


def xla_cost_analysis_flops(jitted_fn, *args, **kwargs) -> Optional[float]:
    """XLA's own FLOP count for one call of a jitted function, via
    ``lower(...).compile().cost_analysis()`` — the cross-check that keeps
    the analytical model honest (the two should agree within the remat /
    non-matmul-op noise).  Returns None where the function cannot be
    lowered or the backend reports no FLOPs (never raises: this is a
    diagnostic, and a backend quirk must not take down a bench or doctor
    run)."""
    try:
        ca = jitted_fn.lower(*args, **kwargs).compile().cost_analysis()
    except Exception:
        return None
    f = (ca or {}).get("flops")
    return float(f) if f else None
