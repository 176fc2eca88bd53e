"""Models and kernels, a family that caches one latent row a position: the
least time the latent decode-attention kernel's work could take on this chip
over the device time it took, both over the TRACED interval.  Work: the live
128-position tiles the decode chunks of the interval read (the engine's
``perf_stats()["cache_tiles"]["read_full"]``, counted once a chunk a layer,
times the chunk's steps and the layers; read by the replica at the trace's
two ends), each copied in once: ``128 x attended_position_flops`` FLOPs and
``tile_bytes`` bytes a tile (the configuration's ``counts_module``).  Least
time: the larger of FLOPs over the bf16 peak and bytes over the HBM peak.
Device time: the ``scope:ragged_latent_decode_attention`` row of the traced
run (the configuration's ``trace_scopes`` marks the kernel by its own name).
None where the trace has no such row (a program without the kernel) or the
configuration's counts know no latent tile."""

import importlib

UNIT = "%"

KERNEL = "ragged_latent_decode_attention"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    trace = raw.get("trace") or {}
    busy = (trace.get("scopes") or {}).get(KERNEL)
    if raw.get("kind") != "serve" or not cfg or not module or not busy:
        return None
    fk = importlib.import_module(module)
    counts = fk.traced_counts(raw)
    if not counts or not hasattr(fk, "latent_attention_least"):
        return None
    from benchmark import flops

    tile_reads = (counts["full_tiles_per_step"] * counts["layers"]["full"]
                  * counts["decode_steps"])
    least = fk.latent_attention_least(
        cfg, tile_reads, flops.peaks(raw["device"]["kind"]))
    return 100.0 * least / busy
