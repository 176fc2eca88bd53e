"""Models and kernels, a family whose full layers SELECT the cached positions
a query reads: the latent rows the decode steps' attention READ over the rows
their selection CHOSE, from the program's own count over the load between the
driver's two reads (``perf_stats()["moe"]["decode"]["dsa_read"]`` over
``["dsa_selected"]``, the numbers of ``perf_stats()["dsa"]``).  100: the
attention reads what it chose (a gathered read); a read of every live tile
under a mask reads ``context rounded up to tiles / min(context, index_topk)``
x 100 (300 at a context of 6,144).  The yardstick of the later gathered read.
None for a configuration that names no ``counts_module`` and where the program
has no such counter."""

import importlib

UNIT = "%"


def read(ctx, raw):
    module = ctx.config.get("counts_module")
    if raw.get("kind") != "serve" or not module:
        return None
    counts = importlib.import_module(module).window_counts(raw)
    rows = (counts or {}).get("dsa")
    if not rows or not rows["rows_selected"]:
        return None
    return 100.0 * rows["rows_read"] / rows["rows_selected"]
