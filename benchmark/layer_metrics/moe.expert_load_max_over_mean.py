"""Models and kernels, the expert layers: how unevenly the router loaded the
experts this chip holds.  Per sparse layer, the tokens the busiest held
expert got over the mean a held expert got (decode steps and prefills of the
load between the driver's two ``perf_stats()`` reads; the engine's
``perf_stats()["moe"]`` counter), averaged over the layers.  1 is even; the
grouped matmul's time follows the sum, a deployment's exchange the maximum.
None for a configuration that names no ``counts_module``, and where the
program has no such counter."""

import importlib

UNIT = "ratio"


def read(ctx, raw):
    module = ctx.config.get("counts_module")
    if raw.get("kind") != "serve" or not module:
        return None
    fk = importlib.import_module(module)
    counts = fk.window_counts(raw)
    if not counts:
        return None
    decode = counts["expert_tokens_decode"]
    prefill = counts["expert_tokens_prefill"] or [[0] * len(row) for row in decode]
    ratios = []
    for per_step, per_prompt in zip(decode, prefill):
        tokens = [d + p for d, p in zip(per_step, per_prompt)]
        if sum(tokens):
            ratios.append(max(tokens) * len(tokens) / sum(tokens))
    return sum(ratios) / len(ratios) if ratios else None
