"""Models and kernels, a family whose layers SELECT the cached positions a
query reads over a slab of K and V per head: the least time reading and
attending the cached rows the decode steps READ could take on this chip over
the device time their attention took, both over the whole decode chunks of the
TRACED interval.  Work: the rows READ, not the rows selected
(``perf_stats()["moe"]["decode"]["dsa_read"]`` a step, read by the replica at
the trace's two ends; k and v of 4 heads of 128: 2,048 bytes a row in bf16 as
published, or the attention's FLOPs where they bind: ``sparse_gqa_read_least``
of the configuration's ``counts_module``), so a read of every live tile under a
mask reads its true share of the kernel's roofline and nothing over 100; how
much of what was read had been chosen is ``cache.selected_read_share_pct``.
Device time: the ``scope:attention.gqa_sparse`` row of the traced run (the
kernel under it, which keeps a name of its own, is one of the scope's marks in
the configuration's ``trace_scopes``), summed inside the whole chunk's program
alone.  None where the trace has no such row or the program no such counter."""

import importlib

UNIT = "%"

SCOPE = "attention.gqa_sparse"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    trace = raw.get("trace") or {}
    busy = (trace.get("scopes") or {}).get(SCOPE, 0.0)
    if raw.get("kind") != "serve" or not cfg or not module or not busy:
        return None
    fk = importlib.import_module(module)
    counts = fk.traced_counts(raw)
    if not counts or "dsa" not in counts or not hasattr(fk, "sparse_gqa_read_least"):
        return None
    from benchmark import flops

    whole = sum(m["count"] for name, m in trace.get("modules", {}).items()
                if raw["decode_module"] in name)
    rows = counts["dsa_rows_read_per_step"] * whole * raw["chunk_steps"]
    if not rows:
        return None
    least = fk.sparse_gqa_read_least(cfg, rows, flops.peaks(raw["device"]["kind"]))
    return 100.0 * least / busy
