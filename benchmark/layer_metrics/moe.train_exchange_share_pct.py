"""Parallel, the experts' exchange in a TRAINED step: device time under
``scope:moe.exchange`` (the collectives around the expert layer, forward and
backward, a chip: the experts' matrices gathered to a chip's tokens, their
gradients reduce-scattered home) as a share of the chip's busy time in the
traced steps.
``collective.exposed_pct`` beside it says how much of ALL collective time no
compute hides.  None where the trace holds no such scope."""

UNIT = "%"


def read(ctx, raw):
    trace = raw.get("trace") or {}
    seconds = (trace.get("scopes") or {}).get("moe.exchange")
    if raw.get("kind") != "train" or seconds is None or not trace.get("busy_s") \
            or not trace.get("devices"):
        return None
    return 100.0 * seconds / len(trace["devices"]) / trace["busy_s"]
