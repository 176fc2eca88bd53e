"""Models and kernels, a family whose upper layers read ONE lower layer's K and
V (Phi-4-mini-flash: SambaY): the least time the decode steps of the TRACED
interval could take on this chip over the device time BOTH decode programs
took there (the whole chunk and the cut one).  Least time
(``flops_phi4_flash``): the bf16 weights once a step, the live tiles of the
shared slab ONCE A LAYER THAT READS IT (eight as published), the live tiles
of the window layers' rings, and the live rows' states read and written a
Mamba layer, over the HBM peak, or the FLOPs where they bind.  Steps, tiles
and rows are the engine's ``perf_stats()["cache_tiles"]["yoco_*"]`` counters
read by the replica at the trace's two ends.  None where the trace has no
decode program or the program no such counters (the parent of the PR that adds
the family)."""

import importlib

UNIT = "%"

CUT_MODULE = "jit_llm_decode_cut"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    if raw.get("kind") != "serve" or not cfg or not module or not raw.get("trace"):
        return None
    fk = importlib.import_module(module)
    if not hasattr(fk, "yoco_traced_counts"):
        return None
    counts = fk.yoco_traced_counts(raw)
    busy = sum(m["total_s"] for name, m in raw["trace"].get("modules", {}).items()
               if raw["decode_module"] in name or CUT_MODULE in name)
    if not counts or not busy:
        return None
    from benchmark import flops

    least, _bound = flops.roofline_seconds(
        fk.decode_flops(cfg, counts), fk.decode_bytes(cfg, counts),
        flops.peaks(raw["device"]["kind"]))
    return 100.0 * least / busy
