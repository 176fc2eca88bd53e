"""Models and kernels, a family whose full layers SELECT the cached positions
a query reads: the least time the decode steps' index scoring could take on
this chip over the device time the scoring AND the selection took, both over
the whole decode chunks of the TRACED interval.  Work: one index key read a
cached position of a live row's context a full layer (256 bytes in bf16 as
published), or its FLOPs where they bind (``index_select_least`` of the
configuration's ``counts_module``); the rows scored as the program counted
them on the device (``perf_stats()["moe"]["decode"]["dsa_scored"]``, read by
the replica at the trace's two ends: the numbers of ``perf_stats()["dsa"]``)
a step, times the steps of the interval's whole chunks.  Device time: the
``scope:attention.index_score`` and ``scope:attention.index_select`` rows of
the traced run (the configuration's ``trace_scopes``), which are summed inside
the whole chunk's program alone (a cut chunk is another program, and its
steps are left out of both sides).  Scores over the whole padded slab read
low and never over 100.  None where the trace has no such rows or the
program no such counter."""

import importlib

UNIT = "%"

SCOPES = ("attention.index_score", "attention.index_select")


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    trace = raw.get("trace") or {}
    busy = sum((trace.get("scopes") or {}).get(s, 0.0) for s in SCOPES)
    if raw.get("kind") != "serve" or not cfg or not module or not busy:
        return None
    fk = importlib.import_module(module)
    counts = fk.traced_counts(raw)
    if not counts or "dsa" not in counts or not hasattr(fk, "index_select_least"):
        return None
    from benchmark import flops

    whole = sum(m["count"] for name, m in trace.get("modules", {}).items()
                if raw["decode_module"] in name)
    rows = counts["dsa_rows_scored_per_step"] * whole * raw["chunk_steps"]
    if not rows:
        return None
    least = fk.index_select_least(cfg, rows, flops.peaks(raw["device"]["kind"]))
    return 100.0 * least / busy
