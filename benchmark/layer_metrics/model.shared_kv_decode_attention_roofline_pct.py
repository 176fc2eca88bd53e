"""Models and kernels, a family whose upper layers read ONE lower layer's K and
V (Phi-4-mini-flash): the least time reading the shared slab's live tiles,
once a layer that reads them, could take on this chip (their bytes over the
HBM peak, or the scores' and values' FLOPs where they bind:
``shared_kv_least`` of ``flops_phi4_flash``) over the device time of the
``scope:attention.shared_kv`` row of the traced run: the ragged kernel's calls
under that ``jax.named_scope`` (the owner's and the readers') and the merge
with the chunk's own columns.  The scope's seconds are summed inside the runs
of the whole chunk's program alone, so the interval's tiles a step (the
engine's ``yoco_slab_tile_steps`` over ``yoco_steps``, read at the trace's two
ends) are scaled to the whole chunks' steps.  None where the trace has no such
row or the program no such counters."""

import importlib

UNIT = "%"

SCOPE = "attention.shared_kv"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    trace = raw.get("trace") or {}
    busy = (trace.get("scopes") or {}).get(SCOPE)
    if raw.get("kind") != "serve" or not cfg or not module or not busy:
        return None
    fk = importlib.import_module(module)
    if not hasattr(fk, "yoco_traced_counts"):
        return None
    counts = fk.yoco_traced_counts(raw)
    if not counts:
        return None
    from benchmark import flops

    whole = sum(m["count"] for name, m in trace.get("modules", {}).items()
                if raw["decode_module"] in name)
    tile_steps = counts["slab_tiles_per_step"] * whole * raw["chunk_steps"]
    if not tile_steps:
        return None
    least = fk.shared_kv_least(
        cfg, tile_steps, flops.peaks(raw["device"]["kind"]))
    return 100.0 * least / busy
