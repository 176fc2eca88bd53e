"""Models and kernels, a stage that holds EVERY expert of its layers: the
distinct experts a decode step touched over the experts the stage holds
(``perf_stats()["moe"]["decode"]["touched"]`` summed over the layers and the
window's decode steps, over layers x held experts x steps; the difference of
the driver's two reads).  A decode step reads the weights of the experts it
touches and no others, so this share of the experts' bytes is what a step
pays: a small-batch stage touches a quarter, a deployment that batches
hundreds of rows all of them.  Answers only where the configuration holds all
of a layer's experts (a chip's share of an expert-parallel layer is another
question: ``model.moe_decode_roofline_pct``); None else, and where the program
has no such counter."""

import importlib

UNIT = "%"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    if raw.get("kind") != "serve" or not cfg or not module:
        return None
    held = cfg.get("experts_held")
    if not held or held[1] != cfg.get("n_experts"):
        return None
    fk = importlib.import_module(module)
    counts = fk.window_counts(raw)
    if not counts or not hasattr(fk, "sparse_layers"):
        return None
    return 100.0 * float(counts["touched_experts_per_step"]) / (
        fk.sparse_layers(cfg) * held[1])
