"""Models and kernels, a family whose prefill stops half way up
(Phi-4-mini-flash): of the prompt positions the window's prefill calls took,
the share that was run through the layers ABOVE the shared slab (the rows
that ended a prompt: one a prompt; the engine's ``yoco_upper_positions`` over
``yoco_prefill_positions``, counted on the host at dispatch, differenced over
the window).  100 would mean no early exit: every position through every
layer.  None where the program has no such counters."""

import importlib

UNIT = "%"


def read(ctx, raw):
    module = ctx.config.get("counts_module")
    if raw.get("kind") != "serve" or not module:
        return None
    fk = importlib.import_module(module)
    if not hasattr(fk, "yoco_traced_counts"):
        return None
    counts = fk.counts_between(raw.get("engine_before"), raw.get("engine_after"))
    if not counts or not counts["prefill_positions"]:
        return None
    return 100.0 * counts["upper_positions"] / counts["prefill_positions"]
