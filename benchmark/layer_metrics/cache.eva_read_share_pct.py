"""Models and kernels, a family whose cache compacts itself (EvaByte): the
window places and summary rows the decode steps READ (whole tiles of 128, and
the chunk's own columns) over those their queries MAY attend (a slot's live
places ``pos % window``, its ``(pos // window) x window / chunk`` summary rows
and the chunk's columns up to the step's own), from the engine's
``perf_stats()["cache_tiles"]["eva_read_positions"]`` over
``["eva_attendable_positions"]`` between the driver's two reads.  100: a step
reads exactly what it attends; the padding of the last tile of each of the two
sources is what lies above it.  None where the program has no such counter."""

import importlib

UNIT = "%"


def read(ctx, raw):
    module = ctx.config.get("counts_module")
    if raw.get("kind") != "serve" or not module:
        return None
    counts = importlib.import_module(module).window_counts(raw) or {}
    if not counts.get("attendable_positions") or "read_positions" not in counts:
        return None
    return 100.0 * counts["read_positions"] / counts["attendable_positions"]
