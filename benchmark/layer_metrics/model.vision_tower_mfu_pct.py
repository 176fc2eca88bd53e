"""Models and kernels, a family with a vision tower in front of its text path:
the FLOPs the REAL patches of the traced interval needed
(``vision_flops`` of the configuration's ``counts_module``: the tower's and the
merger's matmuls and the attention among a frame's patches; a frame a call was
padded with is not credited) over the device time of the tower's program
(``jit_llm_vision_encode``) in the traced interval and the chip's bf16 peak.
The patches are the engine's own count (``perf_stats()["vision"]["patches"]``,
carried in ``cache_tiles`` where the traced replica reads its counters at the
trace's two ends).  None where the trace holds no such program or the program
has no such counter (a parent of the PR that added the tower)."""

import importlib

UNIT = "%"

VISION_MODULE = "llm_vision_encode"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    trace = raw.get("trace") or {}
    busy = sum(m["total_s"] for name, m in (trace.get("modules") or {}).items()
               if VISION_MODULE in name)
    if raw.get("kind") != "serve" or not cfg or not module or not busy:
        return None
    fk = importlib.import_module(module)
    counts = fk.traced_counts(raw) if hasattr(fk, "vision_flops") else None
    grid = raw.get("frame_grid")
    if not counts or "vision" not in counts or not grid:
        return None
    from benchmark import flops

    need = fk.vision_flops(cfg, counts["vision"]["patches"], grid)
    peak = flops.peaks(raw["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * need / (busy * peak)
