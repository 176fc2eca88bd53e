"""Models and kernels, an expert model served as one chip's share: the least
time one WHOLE decode step could take on this chip over the measured step,
both over the TRACED interval.  Least time: the larger of FLOPs over the bf16
peak and least bytes over the HBM peak (the configuration's
``counts_module``); least bytes: the weights every step reads (attention,
dense layers, routers, shared experts, head), only the DISTINCT held experts
some live token chose, and the live cache tiles (a full layer a slot's tiles
below its position, a window layer at most two a live slot).  Experts and
tiles are the engine's ``perf_stats()`` counters read by the replica at the
two ends of the traced interval (``raw["trace"]["counters"]``), live rows the
client's records between the same two instants, and the step is the MEAN
decode chunk of that interval over its steps (the mean, like the counts, so
that a busier stretch inside the interval cannot lift the share over 100;
``model.decode_step_ms`` is the median).  Answers only for a configuration
that names a ``counts_module`` and a program with the counters; else None."""

import importlib

UNIT = "%"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    if raw.get("kind") != "serve" or not cfg or not module or not raw.get("trace"):
        return None
    from benchmark import flops

    fk = importlib.import_module(module)
    chunks = [m for name, m in raw["trace"].get("modules", {}).items()
              if raw["decode_module"] in name]
    counts = fk.traced_counts(raw)
    if not chunks or not counts:
        return None
    chunk = max(chunks, key=lambda m: m["count"])
    start = raw["trace"]["marks"]["start"]
    live_rows = fk.live_rows_between(
        raw.get("client_records") or [], start, start + raw["trace"]["window_s"])
    if live_rows <= 0:
        return None
    n_full, n_window = counts["layers"]["full"], counts["layers"]["window"]
    full_tiles = counts["full_tiles_per_step"]
    window_tiles = live_rows * min(full_tiles / live_rows, 2.0)
    live_tiles = n_full * full_tiles + n_window * window_tiles
    n_sparse = fk.sparse_layers(cfg)
    need_flops = fk.decode_step_flops(
        cfg, live_rows, counts["held_pairs_per_step"] / n_sparse / live_rows,
        fk.TILE * live_tiles)
    need_bytes = fk.decode_step_bytes(
        cfg, counts["touched_experts_per_step"], live_tiles)
    least, _bound = flops.roofline_seconds(
        need_flops, need_bytes, flops.peaks(raw["device"]["kind"]))
    step_s = chunk["total_s"] / chunk["count"] / raw["chunk_steps"]
    return 100.0 * least / step_s
