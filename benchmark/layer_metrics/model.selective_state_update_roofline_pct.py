"""Models and kernels, a family with Mamba-1 layers (Phi-4-mini-flash): the
least time the states' decode step could take on this chip (the LIVE rows'
states of every Mamba layer read and written once a step, float32, and their
convolutions' last inputs: ``state_update_least`` of ``flops_phi4_flash``,
bytes-bound at the HBM peak) over the device time of the
``scope:ssm.selective_state_update`` row of the traced run (the
``jax.named_scope`` around the update, whatever implements it, so an update
over every row reads low and never over 100).  The scope's seconds are summed
inside the runs of the whole chunk's program alone, so the interval's rows a
step (the engine's ``yoco_state_row_steps`` over ``yoco_steps``, read at the
trace's two ends) are scaled to the whole chunks' steps.  None where the trace
has no such row or the program no such counters."""

import importlib

UNIT = "%"

SCOPE = "ssm.selective_state_update"


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
    row_steps = counts["state_rows_per_step"] * whole * raw["chunk_steps"]
    if not row_steps:
        return None
    least = fk.state_update_least(
        cfg, row_steps, flops.peaks(raw["device"]["kind"]))
    return 100.0 * least / busy
