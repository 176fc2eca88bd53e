"""Models and kernels, the expert layers of a TRAINED sparse model: the grouped
matmuls' share of the chip's bf16 peak.  6 x one expert's three matrices x the
(token, expert) pairs actually routed (the step's counter, ``routed_pairs``,
summed over layers and experts: the same work whatever implements it) over
the device time under ``scope:moe.expert_ffn`` (forward, recomputation and
backward; summed over the chips and the step's runs in the trace) and one
chip's peak.  None where the program counts no routed pairs or the trace
holds no such scope."""

import importlib

UNIT = "%"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    trace = raw.get("trace") or {}
    seconds = (trace.get("scopes") or {}).get("moe.expert_ffn")
    pairs = raw.get("routed_pairs_per_step")
    if raw.get("kind") != "train" or not cfg or not module or not seconds \
            or not pairs or not trace.get("step_runs") or not trace.get("devices"):
        return None
    from benchmark import flops

    fk = importlib.import_module(module)
    # a run of the step on each chip: the steps in the trace
    steps = trace["step_runs"] / len(trace["devices"])
    work = fk.expert_flops_per_pair(cfg) * sum(map(sum, pairs)) * steps
    peak = flops.peaks(raw["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * work / seconds / peak
