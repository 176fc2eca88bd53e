"""Models and kernels, a family whose full layers SELECT the cached positions
a query reads: the least time reading and attending the latent rows the decode
steps READ could take on this chip over the device time their attention took,
both over the whole decode chunks of the TRACED interval.  Work: the rows
READ, not the rows selected (``perf_stats()["moe"]["decode"]["dsa_read"]``
a step, read by the replica at the trace's two ends; 1,152 bytes a row in bf16
as published, or the absorbed attention's FLOPs where they bind:
``sparse_read_least`` of the configuration's ``counts_module``), so a dense
read under a mask reads its true share of the kernel's roofline and nothing
over 100; how much of what was read had been chosen is
``cache.selected_read_share_pct``.  Device time: the
``scope:attention.latent_sparse`` row of the traced run and the row of the
kernel inside it that keeps its own name
(``scope:ragged_latent_decode_attention``), summed inside the whole chunk's
program alone.  None where the trace has no such rows or the program no such
counter."""

import importlib

UNIT = "%"

SCOPES = ("attention.latent_sparse", "ragged_latent_decode_attention")


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    trace = raw.get("trace") or {}
    busy = sum((trace.get("scopes") or {}).get(s, 0.0) for s in SCOPES)
    if raw.get("kind") != "serve" or not cfg or not module or not busy:
        return None
    fk = importlib.import_module(module)
    counts = fk.traced_counts(raw)
    if not counts or "dsa" not in counts or not hasattr(fk, "sparse_read_least"):
        return None
    from benchmark import flops

    whole = sum(m["count"] for name, m in trace.get("modules", {}).items()
                if raw["decode_module"] in name)
    rows = counts["dsa_rows_read_per_step"] * whole * raw["chunk_steps"]
    if not rows:
        return None
    least = fk.sparse_read_least(cfg, rows, flops.peaks(raw["device"]["kind"]))
    return 100.0 * least / busy
