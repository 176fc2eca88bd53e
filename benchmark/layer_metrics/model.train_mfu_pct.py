"""Models and kernels: model FLOPs of one step (6N + 12*L*d*T per token,
recomputation not counted) over the device time of the train-step program
in the trace (median) and the chips' bf16 peak."""

UNIT = "%"


def read(ctx, raw):
    if raw["kind"] != "train":
        return None
    from benchmark import flops
    from benchmark.trace_reduce import program_seconds

    step_s = program_seconds(raw.get("trace"), "train_step")
    if step_s is None:
        return None
    cfg = {**ctx.config["gpt2_config"], **ctx.cell.get("gpt2_config", {})}
    per_token = flops.train_flops_per_token(
        raw["n_params"], cfg["n_layers"], cfg["d_model"], raw["seq"])
    peak = flops.peaks(raw["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * per_token * raw["batch"] * raw["seq"] / step_s / (
        raw["device"]["count"] * peak)
