"""Models and kernels: the least time one decode step could take on this
chip — the larger of FLOPs over peak and bytes over HBM bandwidth, counting
the bf16 weights and the LIVE cache positions only — over the measured step.
Live positions come from the client's records over the traced interval: a
request is live from its first token to its last, at prompt + tokens so far.
Decode at these batch sizes is memory-bound, so the share says how much of
what is read had to be read."""

import bisect

UNIT = "%"


def read(ctx, raw):
    if raw["kind"] != "serve":
        return None
    from benchmark import flops
    from benchmark.trace_reduce import program_seconds

    chunk_s = program_seconds(raw.get("trace"), raw["decode_module"])
    if chunk_s is None:
        return None
    step_s = chunk_s / raw["chunk_steps"]
    marks = raw["trace"]["marks"]
    live_n, live_pos, samples = 0.0, 0.0, 0
    t = marks["start"]
    while t < marks["stop"]:
        n, pos = 0, 0
        for r, prompt_len in raw["records"]:
            times = r["times"]
            if times and times[0] <= t <= times[-1]:
                n += 1
                pos += prompt_len + bisect.bisect_right(times, t)
        if n:
            live_n, live_pos, samples = live_n + n, live_pos + pos, samples + 1
        t += 0.05
    if not samples:
        return None
    cfg = ctx.config["gpt2_config"]
    least, _bound = flops.roofline_seconds(
        flops.decode_step_flops(cfg, live_n / samples, live_pos / samples),
        flops.decode_step_bytes(cfg, live_pos / samples),
        flops.peaks(raw["device"]["kind"]))
    return 100.0 * least / step_s
