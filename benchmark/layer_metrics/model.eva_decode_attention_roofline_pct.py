"""Models and kernels, a family whose cache compacts itself (EvaByte): the
least time reading the window and summary tiles the decode steps READ could
take on this chip (16,384 bytes a place or row a layer in bf16 as published,
over the HBM peak, or the scores' FLOPs where they bind:
``attention_read_least`` of ``flops_evabyte``) over the device time the read
took, both over the WHOLE decode chunks of the traced interval.  Work: the
tiles read a step (``perf_stats()["cache_tiles"]["eva_tile_steps"]`` over
``eva_steps``, read by the replica at the trace's two ends) times the whole
chunks' steps.  Device time: the ``scope:attention.eva_window``,
``scope:attention.eva_summary`` and ``scope:attention.eva_merge`` rows of the
traced run (the ragged kernel, called twice a layer, is traced under the first
two) and the kernel's own row where a trace keeps it apart; the roll-over's
pooling (``scope:attention.eva_pool``) reads no tile of a step and is left
out.  None where the trace has no such rows or the program no such counter."""

import importlib

UNIT = "%"

SCOPES = ("attention.eva_window", "attention.eva_summary", "attention.eva_merge",
          "ragged_decode_attention")


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    trace = raw.get("trace") or {}
    busy = sum((trace.get("scopes") or {}).get(s, 0.0) for s in SCOPES)
    if raw.get("kind") != "serve" or not cfg or not module or not busy:
        return None
    fk = importlib.import_module(module)
    if not hasattr(fk, "eva_traced_counts"):
        return None
    counts = fk.eva_traced_counts(raw)
    if not counts:
        return None
    from benchmark import flops

    whole = sum(m["count"] for name, m in trace.get("modules", {}).items()
                if raw["decode_module"] in name)
    tile_steps = counts["tiles_per_step"] * whole * raw["chunk_steps"]
    if not tile_steps:
        return None
    least = fk.attention_read_least(
        cfg, tile_steps, flops.peaks(raw["device"]["kind"]))
    return 100.0 * least / busy
