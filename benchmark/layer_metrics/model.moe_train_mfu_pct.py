"""Models and kernels, a sparse model TRAINED: the share of the WHOLE step.
Model FLOPs a trained token (6 x ACTIVE parameters + attention with each
layer's own band; no credit for recomputation: the configuration's
``counts_module``) x tokens a step, over the device time of the step program in
the trace (median; ``raw["step_module"]``, the family's own name for its jitted
step) and the chips' bf16 peak.  Answers only for a configuration that names a
``counts_module`` and a driver that names the step's program; else None."""

import importlib

UNIT = "%"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    if raw.get("kind") != "train" or not cfg or not module or not raw.get("step_module"):
        return None
    from benchmark import flops
    from benchmark.trace_reduce import program_seconds

    step_s = program_seconds(raw.get("trace"), raw["step_module"])
    if step_s is None:
        return None
    fk = importlib.import_module(module)
    cfg = {**cfg, **ctx.cell.get("model_config", {})}
    peak = flops.peaks(raw["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * fk.train_flops_per_token(cfg, raw["seq"]) * raw["batch"] * raw[
        "seq"] / step_s / (raw["device"]["count"] * peak)
