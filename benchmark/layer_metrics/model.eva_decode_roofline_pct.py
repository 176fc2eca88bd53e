"""Models and kernels, a family whose cache compacts itself (EvaByte): the
least time the decode steps of the TRACED interval could take on this chip
over the device time BOTH decode programs took there (the whole chunk and the
cut one: a chunk is cut wherever a slot's window ends, so the cut program is
a real share of the steps).  Least time (``flops_evabyte``): the bf16 weights
a step reads, once a step, and the live tiles of the exact window and of the
summaries the steps read, a layer each, over the HBM peak, or the FLOPs where
they bind.  Steps, tiles and live rows are the engine's
``perf_stats()["cache_tiles"]["eva_*"]`` counters read by the replica at the
trace's two ends.  None where the trace has no decode program or the program
no such counters (the parent of the PR that adds the family)."""

import importlib

UNIT = "%"

CUT_MODULE = "jit_llm_decode_cut"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    if raw.get("kind") != "serve" or not cfg or not module or not raw.get("trace"):
        return None
    fk = importlib.import_module(module)
    if not hasattr(fk, "eva_traced_counts"):
        return None
    counts = fk.eva_traced_counts(raw)
    busy = sum(m["total_s"] for name, m in raw["trace"].get("modules", {}).items()
               if raw["decode_module"] in name or CUT_MODULE in name)
    if not counts or not busy:
        return None
    from benchmark import flops

    least, _bound = flops.roofline_seconds(
        fk.decode_flops(cfg, counts["row_steps"], counts["tile_steps"]),
        fk.decode_bytes(cfg, counts["steps"], counts["tile_steps"]),
        flops.peaks(raw["device"]["kind"]))
    return 100.0 * least / busy
