"""Models and kernels, a family whose layers carry a per-request state: the
least time the state's decode step could take on this chip over the device
time it took, both over the whole decode chunks of the TRACED interval.
Work: the state of the LIVE rows of every recurrent layer read and written
once a step (float32; ``state_update_least`` of the configuration's
``counts_module``: bytes-bound at the HBM peak): the rows that took a step as
the program counted them on the device (``perf_stats()["moe"]["decode"]
["rows"]`` over ``decode_steps``, read by the replica at the trace's two
ends) times the steps of the interval's whole chunks.  Device time: the
``scope:ssm.state_update`` row of the traced run (the configuration's
``trace_scopes``: the ``jax.named_scope`` around the update, whatever
implements it, so an update over every row reads low and never over 100).
None where the trace has no such row (a program without the scope) or the
configuration's counts know no state."""

import importlib

UNIT = "%"

SCOPE = "ssm.state_update"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    trace = raw.get("trace") or {}
    busy = (trace.get("scopes") or {}).get(SCOPE)
    if raw.get("kind") != "serve" or not cfg or not module or not busy:
        return None
    fk = importlib.import_module(module)
    counts = fk.traced_counts(raw)
    if not counts or not hasattr(fk, "state_update_least"):
        return None
    from benchmark import flops

    # the scope's seconds are summed inside the runs of the whole chunk's
    # program alone (``decode_module``); a cut chunk is another program
    whole = sum(m["count"] for name, m in trace.get("modules", {}).items()
                if raw["decode_module"] in name)
    row_steps = counts["state_rows_per_step"] * whole * raw["chunk_steps"]
    if not row_steps:
        return None
    least = fk.state_update_least(
        cfg, row_steps, flops.peaks(raw["device"]["kind"]))
    return 100.0 * least / busy
