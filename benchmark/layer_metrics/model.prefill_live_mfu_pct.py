"""Models and kernels, a family served through ``drivers/serve_family.py``:
FLOPs the LIVE prompt tokens needed (``prefill_flops`` of the configuration's
``counts_module``: every real token through the layers, attention over what
each position may attend, one row of logits a prompt) over the device time of the prefill programs in
the traced interval and the chip's bf16 peak.  A prefill call is a fixed
number of rows padded to a bucket whatever it admits, so this share is mostly
what that padding costs.  The prompts are those of the requests whose first
token reached the client inside the traced interval (the trace's own span).
Answers only for a configuration that names a ``counts_module``; else None."""

import importlib

UNIT = "%"

PREFILL_MODULE = "jit_llm_prefill"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    if raw.get("kind") != "serve" or not cfg or not module or not raw.get("trace"):
        return None
    from benchmark import flops

    fk = importlib.import_module(module)
    busy = sum(m["total_s"] for name, m in raw["trace"].get("modules", {}).items()
               if PREFILL_MODULE in name)
    # the interval the trace covers: from the mark at its start for as long
    # as its events span (the mark at its stop is taken after the profiler
    # has written its file, seconds later)
    start = raw["trace"]["marks"]["start"]
    stop = start + raw["trace"]["window_s"]
    prompts = [n for r, n in raw.get("client_records") or []
               if r["times"] and start <= r["times"][0] <= stop]
    if not busy or not prompts or "experts_held" not in cfg:
        return None
    held_pairs = cfg["experts_per_token"] * cfg["experts_held"][1] / cfg["n_experts"]
    need = fk.prefill_flops(cfg, prompts, held_pairs)
    peak = flops.peaks(raw["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * need / (busy * peak)
