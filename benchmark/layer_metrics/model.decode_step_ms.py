"""Models and kernels: device time of the decode-chunk program in the trace
(median) over the steps in a chunk.  The program is found by the name the
cell's file gives under ``decode_module``: the engine jits a
``functools.partial``, which XLA names ``jit__unknown``."""

UNIT = "ms"


def read(ctx, raw):
    if raw["kind"] != "serve":
        return None
    from benchmark.trace_reduce import program_seconds

    chunk_s = program_seconds(raw.get("trace"), raw["decode_module"])
    return None if chunk_s is None else 1e3 * chunk_s / raw["chunk_steps"]
