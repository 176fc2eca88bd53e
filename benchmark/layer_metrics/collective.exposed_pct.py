"""Parallel: time in collective operations during which no compute runs on
that device, as a share of the device's busy time in the traced steps.  Only
a cell with a mesh has collectives."""

UNIT = "%"


def read(ctx, raw):
    trace = raw.get("trace")
    if not trace or not ctx.cell.get("mesh") or not trace.get("busy_s"):
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["busy_s"]
