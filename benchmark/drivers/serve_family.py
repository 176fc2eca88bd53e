"""How a serve cell of ANY decoder family is brought up, warmed, measured and
torn down (kind ``serve_family``): ``drivers/serve.py``'s walk (the same
deployment, load generator, records, stage reads and shutdown; its ``traced``,
``post``, ``summarize`` and ``hbm_peak_from_gauge`` are imported, not copied)
with everything that names a model taken from the configuration file, so the
next family is data plus a plain reference.

**The kind's contract.**  The configuration file (``configs/<config>.json``)
holds, beside the published keys:

- ``family``, ``size``, ``model_config``: what ``llm_deployment(family, size,
  num_tpus=1, config_kwargs=model_config)`` and ``make_config`` take (the
  program's own keywords: published widths, the chip's share).  Token ids are
  drawn from ``[0, model_config["vocab_size"])`` (a sliced vocabulary is a
  smaller vocabulary);
- ``reference_module``: the plain reference (``benchmark.reference.*``), with
  ``logits(params, tokens, sizes, lower=None) -> [B, T, V]`` float32;
- ``reference_sizes``: what that function needs beyond the parameters' shapes;
- ``counts_module`` (optional): the family's operations and bytes
  (``benchmark.flops_*``), with ``window_counts(raw)``: what the engine's
  counters say of the window, for ``detail`` and for the family's readers
  under ``layer_metrics/``, which find it by the same key;
- ``trace_scopes`` (optional): ``{scope: [substrings]}``: the named scopes of
  the decode program a traced run sums device time under, each with the
  pieces of an instruction's ``op_name`` that mark it (a ``jax.named_scope``,
  or the name XLA gives a kernel of its own that keeps no path).

The cell's file is ``drivers/serve.py``'s (``engine``, ``client_timeout_s``,
``reference_sample``, ``logit_tie_margin``, ``min_exact_share``,
``max_over_margin_share`` (this kind's own),
``trace_seconds``, ``decode_module``).  The result is ``raw["kind"] ==
"serve"`` so that every serve reader answers (stages, engine, ownership, idle,
``model.decode_step_ms``); the client's records are kept under
``client_records`` and ``raw["records"]`` stays empty: ``run.py`` asks EVERY
reader in a traced run, and ``model.decode_roofline_pct`` looks up
``config["gpt2_config"]`` as soon as it finds a live record (a KeyError for
any other family, which would fail the run; each family brings its own
roofline reader).  ``proxy.ttft_unattributed_ms`` reads the same list, so the
driver computes that quantity itself, into ``detail.proxy``.

The family is checked BEFORE ``ray_tpu.init()``: a program that does not know
it (the parent of the PR that adds it) fails at once, with the reason, and
leaves no process behind.

``correct`` is decided as ``drivers/serve.py`` decides it: after the replica
is gone a ``num_tpus=1`` actor rebuilds the weights the server held (the
family's init from the same seed), runs the reference's forward over prompt +
served tokens of a seeded sample of requests, two rows at a time at one
width; at least ``min_exact_share`` of the served tokens must be exactly the
reference's best, at most ``max_over_margin_share`` of them may sit more than
``logit_tie_margin`` below it; and every answer has the length asked for.  (A
share and not "no token": where a layer routes to the top k of near-tied
scores, one token whose k-th expert flips under bfloat16 lands as far from
the reference as a whole lower precision puts its worst, so a single token's
distance tells the two apart no better than chance; the size of the tail
does.)  The reading also carries the 99th
percentile of the served tokens' distance below the reference's best logit
and the tail's counts (``gap_p99``, ``gap_over``), for whoever sets the
limits next.  That the limits tell the configuration's precision from the
one below is itself a run of this file: :func:`control`.

The traced replica reads the engine's ``perf_stats()`` at the two ends of the
traced interval (``raw["trace"]["counters"]``), so that a reader can count
experts and tiles over the very steps whose time the trace gives.  It also
adds to the reduction's ``device_ops`` one row a NAMED SCOPE of the decode
program (the configuration's ``trace_scopes``: ``scope:moe.expert_ffn``,
``scope:attention.window``, ...: the device time, inside the decode chunks of the traced interval, of the
operations that were traced under that ``jax.named_scope``), because a
fusion's own name says nothing of the layer it belongs to.  A trace names an
operation by its instruction alone, so the scopes come from the compiled
program's text (:func:`scopes_of_instructions`); a program without such
scopes gets one ``scope:(unnamed)`` row.
"""

from __future__ import annotations

import importlib
import json
import os
import random
import subprocess
import sys
import threading
import time

from benchmark.drivers.serve import (
    T0_SLACK_S,
    hbm_peak_from_gauge,
    post,
    summarize,
    traced,
)

GAP_STEPS = (0.004, 0.008, 0.012, 0.016, 0.02, 0.024, 0.032, 0.048, 0.064,
             0.125, 0.25)


def limits_broken(cell: dict, ref: dict) -> list:
    """The cell's limits that this reading of the reference breaks, by name
    (none: the served tokens are the reference's)."""
    broken = []
    if ref["over_margin"] > cell["max_over_margin_share"] * ref["tokens"]:
        broken.append("max_over_margin_share")
    if ref["equal"] < cell["min_exact_share"] * ref["tokens"]:
        broken.append("min_exact_share")
    return broken


def scopes_of_instructions(hlo_text: str, scopes: dict) -> dict:
    """``{instruction name: scope}`` from a compiled program's text: the
    first of ``scopes`` (``{scope: [substrings]}``, the configuration's
    ``trace_scopes``) one of whose substrings is in the instruction's
    ``op_name`` metadata (the path of ``jax.named_scope``s it was traced
    under, or a kernel's own name).  A device trace names an operation by its
    instruction alone, without the metadata."""
    import re

    found = {}
    for name, op_name in re.findall(
            r"^\s*(?:ROOT )?%([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"",
            hlo_text, flags=re.M):
        scope = next((s for s, marks in scopes.items()
                      if any(m in op_name for m in marks)), None)
        if scope:
            found[name] = scope
    return found


def scope_seconds(xplane_path: str, module: str, scope_of: dict) -> dict:
    """Device seconds by named scope inside the runs of the program whose
    module name contains ``module``: every operation of a device's ``XLA
    Ops`` line that starts inside such a run, under its instruction's scope
    (``scope_of``) or ``(unnamed)``.  Loops and calls span their bodies and
    are left out, as in ``trace_reduce``."""
    import bisect

    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    out: dict = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if not trace_reduce.is_device_plane(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if not {trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE} <= set(lines):
            continue
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns)
                      for e in lines[trace_reduce.MODULES_LINE].events
                      if module in e.name)
        starts = [r[0] for r in runs]
        for e in lines[trace_reduce.OPS_LINE].events:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i < 0 or e.start_ns >= runs[i][1]:
                continue
            name = trace_reduce.short_name(e.name)
            if trace_reduce.CONTAINER.match(name):
                continue
            scope = scope_of.get(name, "(unnamed)")
            out[scope] = out.get(scope, 0.0) + e.duration_ns / 1e9
    return out


def traced_by_scope(dep, trace_dir: str, decode_module: str, scopes: dict):
    """``drivers/serve.py``'s traceable replica, which also reads the
    engine's counters at the two ends of the traced interval and whose
    reduction also carries one ``scope:*`` row a named scope of the DECODE
    program (the prefill programs' instructions share names across buckets,
    so only their sum has a row: the module's own)."""
    from ray_tpu.serve.api import Deployment

    from benchmark import trace_reduce

    dep = traced(dep, trace_dir)

    class ScopedLLMServer(dep._func_or_class):
        def _counters(self):
            stats = self.engine.perf_stats()
            return {k: stats.get(k) for k in ("moe", "cache_tiles", "prefill")}

        def trace_start(self):
            t = super().trace_start()
            self._bench["counters"] = {"start": self._counters()}
            return t

        def trace_stop(self):
            # before the profiler is stopped: that takes seconds
            self._bench["counters"]["stop"] = self._counters()
            return super().trace_stop()

        def _decode_text(self):
            """The decode program's compiled text: the engine's own jitted
            function lowered for the shapes it is called with (the compile
            is a cache hit)."""
            import jax

            eng = self.engine
            shapes = lambda tree: jax.tree.map(  # noqa: E731
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
            return eng._decode_jit.lower(
                shapes(eng.params), shapes(eng.cache),
                shapes(eng._last_tok_dev),
                jax.ShapeDtypeStruct((eng.n_slots + 1,), bool),
                shapes(eng._key)).compile().as_text()

        def trace_reduce(self):
            try:
                seconds = scope_seconds(
                    trace_reduce.find_xplane(trace_dir), decode_module,
                    scopes_of_instructions(self._decode_text(), scopes))
            except Exception:  # noqa: BLE001 — the rows are an extra
                seconds = {}
            reduced = super().trace_reduce()
            reduced["scopes"] = seconds
            reduced["counters"] = self._bench.get("counters")
            reduced["device_ops"] = reduced.get("device_ops", []) + sorted(
                ([f"scope:{k}", v] for k, v in seconds.items()),
                key=lambda kv: -kv[1])
            return reduced

    return Deployment(ScopedLLMServer, dep.name, dep.config,
                      route_prefix=dep.route_prefix)


class FamilyReference:
    """Runs in a ``num_tpus=1`` actor after the replica is gone."""

    def check(self, config, seed, samples, margin, lower=None):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.serve.llm import _default_init, make_config

        ref = importlib.import_module(config["reference_module"])
        cfg = make_config(config["family"], config["size"],
                          **config["model_config"])
        params = _default_init(cfg, seed)  # the weights the server held
        params = jax.tree.map(
            lambda x: x.astype(cfg.dtype) if x.dtype == jnp.float32 else x,
            params)
        sizes = config["reference_sizes"]
        width = -(-max(len(p) + len(o) for p, o in samples) // 128) * 128
        exact = ties = over = 0
        gaps = []
        for lo in range(0, len(samples), 2):
            group = samples[lo:lo + 2]
            buf = np.zeros((2, width), np.int32)  # one shape, one compile
            for r, (p, o) in enumerate(group):
                buf[r, :len(p) + len(o)] = p + o
            logits = np.asarray(ref.logits(params, jnp.asarray(buf), sizes,
                                           lower=lower))
            for r, (p, o) in enumerate(group):
                # the logits at position len(p)-1+i chose served token o[i]
                at = logits[r, len(p) - 1:len(p) - 1 + len(o)]
                got = np.asarray(o)
                gap = at.max(-1) - at[np.arange(len(o)), got]
                exact += int((at.argmax(-1) == got).sum())
                ties += int(((at.argmax(-1) != got) & (gap <= margin)).sum())
                over += int((gap > margin).sum())
                gaps += gap.tolist()
        gaps.sort()
        dev = jax.devices()[0]
        return {"tokens": len(gaps), "equal": exact, "ties": ties,
                "over_margin": over, "worst_gap": gaps[-1] if gaps else 0.0,
                "gap_p99": gaps[int(0.99 * (len(gaps) - 1))] if gaps else 0.0,
                # the tail's shape, for whoever sets the limits next
                "gap_over": {str(t): sum(g > t for g in gaps)
                             for t in GAP_STEPS},
                "lower": lower, "logit_std": float(logits[0, 0].std()),
                "platform": dev.platform, "kind": dev.device_kind,
                "count": len(jax.devices())}


def proxy_share(raw: dict) -> dict:
    """What ``proxy.ttft_unattributed_ms`` and ``proxy.ttft_overhead_p50_ms``
    read for a GPT-2 cell, computed here over ``client_records`` (the first
    reader wants ``raw["records"]``, which this driver keeps empty): the mean
    client time from SENT to first token less the mean ``serve.first_reply``
    span, and the client's p50 of the same less the engine's own p50.  None
    where the program closes no such span or keeps no such reservoir."""
    from benchmark import stages

    seen = [r["times"][0] - r["sent"] for r, _ in raw["client_records"]
            if r.get("done") and r["times"]]
    covered = stages.window_mean_ms(raw, "serve.first_reply")
    engine_p50 = (raw["engine_after"].get("ttft") or {}).get("p50_s")
    client_p50 = raw.get("client_ttft_from_send_p50_s")
    return {
        "ttft_unattributed_ms": None if covered is None or not seen
        else 1e3 * sum(seen) / len(seen) - covered,
        "ttft_overhead_p50_ms": None if engine_p50 is None or client_p50 is None
        else 1e3 * (client_p50 - engine_p50)}


def run(ctx) -> dict:
    config, cell, traffic = ctx.config, ctx.cell, ctx.traffic
    # a program that does not know the family or a keyword fails HERE, at
    # once and with the reason, before any process is started (in the
    # replica's constructor the controller would replace the dead replica
    # until serve.run's deadline); this imports jax but starts no backend
    from ray_tpu.models import generate

    if config["family"] not in generate.FAMILIES:
        raise SystemExit(
            f"this program has no model family {config['family']!r} "
            f"(it has {sorted(generate.FAMILIES)})")
    from ray_tpu.serve.llm import llm_deployment, make_config

    make_config(config["family"], config["size"], **config["model_config"])

    import ray_tpu
    from ray_tpu import serve

    from benchmark import traffic_gen

    engine = dict(cell["engine"])
    engine["prefill_buckets"] = tuple(engine["prefill_buckets"])
    engine["seed"] = seed32 = ctx.seed % (1 << 32)
    if traffic["prompt_len"]["max"] > max(engine["prefill_buckets"]) \
            or traffic["output_len"]["max"] > engine["max_new_tokens"]:
        raise ValueError("the traffic asks for more than the engine admits")
    vocab = config["model_config"].get("vocab_size") or make_config(
        config["family"], config["size"], **config["model_config"]).vocab_size
    schedule = traffic_gen.serve_schedule(
        traffic, ctx.seed, ctx.seconds, vocab)
    trace_dir = os.path.join(ctx.out_dir, "trace-" + ctx.name)

    t_init = time.time()
    ctx.init_cluster(ray_tpu)
    raw: dict = {"kind": "serve", "t_init": t_init}
    try:
        dep = llm_deployment(config["family"], config["size"], num_tpus=1,
                             config_kwargs=config["model_config"],
                             engine_kwargs=engine)
        if ctx.trace:
            dep = traced_by_scope(
                dep, trace_dir, cell.get("decode_module", "decode_chunk"),
                config.get("trace_scopes") or {})
        try:
            handle = serve.run(dep.bind(), port=0, timeout_s=1100)
            t_ready = time.time()
            host, port = serve.get_http_address()
            url = f"http://{host}:{port}/{dep.name}"

            # warm every shape the window uses: one prompt per prefill
            # bucket, alone (admission pads to the bucket's fixed rows, so a
            # bucket is one program), through one decode chunk, streamed
            warm = []
            for b in engine["prefill_buckets"]:
                t = time.time()
                status, _ = post(url, {
                    "tokens": [1 + (i % 97) for i in range(b)],
                    "max_new_tokens": min(engine["max_new_tokens"],
                                          engine["decode_chunk_steps"] + 2),
                    "stream": True}, timeout=1100)
                if status != 200:
                    raise RuntimeError(f"warm-up POST answered {status}")
                warm.append(time.time() - t)
            before = ray_tpu.get(handle.perf_stats.remote(), timeout=60)

            spec_path = os.path.join(ctx.out_dir, f"loadgen-{ctx.name}.in.json")
            out_path = os.path.join(ctx.out_dir, f"loadgen-{ctx.name}.out.json")
            preroll = float(traffic.get("preroll_s", 0.0))
            with open(spec_path, "w") as f:
                json.dump({**schedule, "host": host, "port": port,
                           "path": "/" + dep.name,
                           "timeout_s": cell["client_timeout_s"]}, f)
            gen = subprocess.Popen(
                [sys.executable, os.path.join(ctx.root, "benchmark", "loadgen.py"),
                 spec_path, out_path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
            # the window's start (wall clock) is fixed only once the
            # generator has loaded its schedule: its start-up is not set-up
            if gen.stdout.readline().strip() != "ready":
                gen.kill()
                gen.wait()
                raise RuntimeError("the load generator did not come up")
            t0 = time.time() + preroll + T0_SLACK_S
            gen.stdin.write(f"{t0!r}\n")
            gen.stdin.close()
            polls: list = []
            trace_marks: dict = {}

            def poll():
                while time.time() < t0 + ctx.seconds:
                    if time.time() >= t0:
                        s = ray_tpu.get(handle.stats.remote(), timeout=30)
                        polls.append((s["active_slots"], s["queued"]))
                    time.sleep(0.5)

            poller = threading.Thread(target=poll, daemon=True)
            poller.start()
            try:
                if ctx.trace:
                    time.sleep(max(0.0, t0 - time.time()))
                    trace_marks["start"] = ray_tpu.get(
                        handle.trace_start.remote(), timeout=120) - t0
                    time.sleep(cell["trace_seconds"])
                    trace_marks["stop"] = ray_tpu.get(
                        handle.trace_stop.remote(), timeout=300) - t0
                gen.wait(timeout=preroll + ctx.seconds
                         + cell["client_timeout_s"] + 120)
            finally:
                if gen.poll() is None:
                    gen.kill()
                    gen.wait()
            poller.join(timeout=60)
            if gen.returncode != 0:
                raise RuntimeError(f"the load generator exited {gen.returncode}")
            with open(out_path) as f:
                records = json.load(f)
            os.remove(spec_path)
            os.remove(out_path)

            after = ray_tpu.get(handle.perf_stats.remote(), timeout=60)
            engine_stats = ray_tpu.get(handle.stats.remote(), timeout=60)
            if ctx.trace:
                raw["trace"] = ray_tpu.get(handle.trace_reduce.remote(),
                                           timeout=600)
                raw["trace"]["marks"] = trace_marks
                raw["replica"] = ray_tpu.get(handle.bench_facts.remote(),
                                             timeout=60)
                peak = {"bytes": raw["replica"]["memory_peak_bytes"],
                        "kind": "memory_stats"}
            else:
                peak = hbm_peak_from_gauge()
        finally:
            serve.shutdown()  # the replica's process ends here

        # correctness, on the chip the replica just gave back
        done = [r for r in records if r and r.get("done") and r["tokens"]]
        sample = random.Random(ctx.seed).sample(
            done, min(cell["reference_sample"], len(done)))
        checker = ray_tpu.remote(num_tpus=1)(FamilyReference).remote()
        program = {k: config[k] for k in (
            "family", "size", "model_config", "reference_module",
            "reference_sizes")}
        held_to = lambda lower: ray_tpu.get(checker.check.remote(  # noqa: E731
            program, seed32,
            [(schedule["prompts"][r["i"]], r["tokens"]) for r in sample],
            cell["logit_tie_margin"], lower), timeout=1500)
        ref = held_to(None)
        control_dtype = getattr(ctx, "control_dtype", None)
        lowered = held_to(control_dtype) if control_dtype else None
    finally:
        ray_tpu.shutdown()

    if not ctx.rehearsal and peak["kind"] not in ("hbm", "memory_stats"):
        raise RuntimeError(f"the replica reported no device memory: {peak}")
    raw["device"] = {"platform": ref["platform"], "kind": ref["kind"],
                     "count": ref["count"], "memory_peak_bytes": peak["bytes"]}
    raw.update(summarize(records, schedule, ctx.seconds,
                         cell["client_timeout_s"]))
    # GPT-2's roofline reader would fail the run over them: see the module
    # docstring
    raw["client_records"], raw["records"] = raw["records"], []
    raw["end_to_end"]["setup_s"] = t0 - ctx.t_process
    half = len(polls) // 2 or 1
    mean = lambda xs: sum(xs) / len(xs) if xs else 0.0
    # does the queue grow through the window?  (what the knee sweep reads)
    raw["detail"].update({
        "queued_first_half": mean([q for _, q in polls[:half]]),
        "queued_second_half": mean([q for _, q in polls[half:]]),
        "active_slots_mean": mean([a for a, _ in polls]),
        "rate_per_s": traffic["arrivals"]["rate_per_s"],
    })
    raw.update({
        "correct": bool(sample and not limits_broken(cell, ref)
                        and all(len(r["tokens"]) == schedule["max_new"][r["i"]]
                                for r in done)),
        "checks": {"reference": ref},
        "warmup": {"replica_ready_s": t_ready - t_init,
                   "warm_posts_s": warm, "memory_source": peak["kind"]},
        "polls": polls, "n_slots": engine["n_slots"],
        "chunk_steps": engine["decode_chunk_steps"],
        "decode_module": cell.get("decode_module", "decode_chunk"),
        "engine_before": before, "engine_after": after,
        "engine_stats": engine_stats, "t_ready": t_ready, "t0": t0,
    })
    # the line's ``metrics`` carry an end-to-end metric only where
    # BENCHMARK.json has the cell judged on it; this tail is read either way
    raw["detail"]["ttft_p95_ms"] = raw["end_to_end"]["ttft_p95_ms"]
    raw["detail"]["proxy"] = proxy_share(raw)
    # the window's counts, for a reader of the line (a configuration that
    # names no module for them, a program without the counters: nothing)
    if config.get("counts_module"):
        counting = importlib.import_module(config["counts_module"])
        counts = counting.window_counts(raw)
        if counts:
            raw["detail"]["window_counts"] = counts
        # the same over the traced interval alone: what the family's
        # roofline reader holds the traced step time against
        between = ctx.trace and counting.traced_counts(raw)
        if between:
            start = raw["trace"]["marks"]["start"]
            raw["detail"]["traced_counts"] = {
                **{k: v for k, v in between.items()
                   if k == "decode_steps" or k.endswith("_per_step")},
                "live_rows": counting.live_rows_between(
                    raw["client_records"], start,
                    start + raw["trace"]["window_s"])}
    if ctx.trace and raw.get("trace", {}).get("scopes"):
        raw["detail"]["scope_seconds"] = raw["trace"]["scopes"]
    if lowered:
        raw["checks"]["control"] = lowered
    return raw


def control(argv=None) -> int:
    """The control of a cell's limits::

        python3 -m benchmark.drivers.serve_family --workload <cell> \\
            --seed <n> --seconds <s> --reference-dtype float8_e4m3fn

    One run of the cell as ``run.py`` makes it (untraced), whose served
    tokens are then held to the reference twice by the run's own comparison:
    as the configuration states it (float32 over the served weights: has to
    come out correct) and with every matmul operand, weights and activations,
    rounded through ``--reference-dtype`` first, the nearest precision below
    the configuration's bfloat16 (has to come out NOT correct).  Prints one
    JSON line with both readings and the limits each broke; exits 0 when the
    cell's limits told the two apart."""
    import argparse

    from benchmark import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--reference-dtype", default="float8_e4m3fn")
    args = ap.parse_args(argv)
    args.trace = 0
    # as harness.main() sets them: workers import ``benchmark.*`` by name
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (harness.ROOT, os.environ.get("PYTHONPATH")) if p)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(harness.ROOT, ".jax_cache"))
    ctx = harness.Context(args)
    if ctx.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    ctx.control_dtype = args.reference_dtype
    raw = run(ctx)
    sound, lowered = raw["checks"]["reference"], raw["checks"]["control"]
    broke = limits_broken(ctx.cell, lowered)
    print(json.dumps({
        "workload": ctx.name, "seed": ctx.seed, "correct": raw["correct"],
        "failed": raw["failed"], "control_dtype": args.reference_dtype,
        "control_correct": not broke, "control_broke": broke,
        "reference": sound, "control": lowered,
        "end_to_end": raw["end_to_end"]}), flush=True)
    return 0 if raw["correct"] and broke else 1


if __name__ == "__main__":
    sys.exit(control())
