"""Models and kernels, a family whose prefill stops half way up
(Phi-4-mini-flash): FLOPs the LIVE prompt tokens needed (``prefill_flops`` of
``flops_phi4_flash``: every real position through the layers BELOW the shared
slab and the slab's K and V projection, a window layer's scores over its 512
keys, and each prompt's LAST position through everything above, the slab's
eight reads and the head) over the device time of the prefill programs in the
traced interval (the bucket's and the part's, found by the name they share) and
the chip's bf16 peak.  The Mamba scans' element updates run on the vector unit
and are no matmul FLOPs: they count in the time and not in the work.  The
prompts are those of the requests whose first token reached the client inside
the traced interval.  None for a configuration whose ``counts_module`` counts
no such FLOPs."""

import importlib

UNIT = "%"

PREFILL_MODULE = "jit_llm_prefill"


def read(ctx, raw):
    cfg, module = ctx.config.get("model_config"), ctx.config.get("counts_module")
    if raw.get("kind") != "serve" or not cfg or not module or not raw.get("trace"):
        return None
    fk = importlib.import_module(module)
    if not hasattr(fk, "yoco_traced_counts"):
        return None
    from benchmark import flops

    busy = sum(m["total_s"] for name, m in raw["trace"].get("modules", {}).items()
               if PREFILL_MODULE in name)
    start = raw["trace"]["marks"]["start"]
    stop = start + raw["trace"]["window_s"]
    prompts = [n for r, n in raw.get("client_records") or []
               if r["times"] and start <= r["times"][0] <= stop]
    if not busy or not prompts:
        return None
    peak = flops.peaks(raw["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * fk.prefill_flops(cfg, prompts) / (busy * peak)
