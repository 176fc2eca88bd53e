"""Models and kernels, a cache of two kinds of layer: the cache tiles the
decode steps read (a full layer a live slot's tiles below its position; a
window layer every row's ring) over the tiles the same steps would read if
every layer were a full one, from the engine's ``perf_stats()["cache_tiles"]``
counter over the load between the driver's two reads (counted by the
configuration's ``counts_module``).  None for a configuration that names no
such module, and where the program's counter has no kinds."""

import importlib

UNIT = "%"


def read(ctx, raw):
    module = ctx.config.get("counts_module")
    if raw.get("kind") != "serve" or not module:
        return None
    fk = importlib.import_module(module)
    counts = fk.window_counts(raw)
    if not counts or not counts["full_tiles_per_step"]:
        return None
    n_full, n_window = counts["layers"]["full"], counts["layers"]["window"]
    read_tiles = (n_full * counts["full_tiles_per_step"]
                  + n_window * counts["window_tiles_read_per_step"])
    return 100.0 * read_tiles / (
        (n_full + n_window) * counts["full_tiles_per_step"])
