"""How a train cell of ANY trained family is brought up, warmed, measured and
torn down (kind ``train_family``): ``drivers/train.py``'s walk (ONE worker
holding the cell's chips, weights made on the device in one call, two warm
steps, a fresh host batch a step with one step kept in flight, the loss read
back and reported every step, the traced steps first) with everything that
names a model taken from the configuration file, so the next trained family is
data plus a plain reference.  ``train.py``'s loop is a closure inside its
``train_loop`` and cannot be imported, so it is written once more here, with
the SAME ``bench.host_batch`` / ``bench.dispatch`` / ``bench.loss_readback`` /
``bench.report`` annotations (the ledger's ``idle_gaps`` print those names) and
the same keys in ``raw``.

**The kind's contract.**  The configuration file (``configs/<config>.json``)
holds, beside the published keys:

- ``family``, ``size``, ``model_config``: the module ``ray_tpu.models.<family>``
  with the TRAINING contract (``SIZES``, ``init``, ``make_optimizer``,
  ``make_train_step``, ``param_shardings``, ``sharding_rules``,
  ``named_leaves``, ``num_params``, ``STEP_NAME``) and the keywords of
  ``SIZES[size]`` (the cell may override some: its own ``model_config``).
  Token ids are drawn from ``[0, model_config["vocab_size"])``;
- ``reference_module`` (``benchmark.reference.*``): ``grad_norms(params,
  inputs, targets, sizes, leaves, kept=None) -> {leaf: norm}``, whose first
  pass leaves the step's ``ce`` and ``aux`` in ``kept``, and ``loss(params,
  inputs, targets, sizes, lower=None) -> (ce, aux)``, ``lower`` a dtype every
  matmul operand is rounded through first (the control);
  ``reference_sizes``: what it needs beyond the parameters' shapes;
- ``counts_module`` (``benchmark.flops_*``): the family's operations, for the
  readers under ``layer_metrics/`` that find it by the same key, and
  ``chip_load_max_over_mean(pairs, chips)``;
- ``trace_scopes``: ``{scope: [substrings]}``, the named scopes of the STEP
  program a traced run sums device time under
  (``serve_family.scopes_of_instructions`` / ``scope_seconds``, imported).

The cell's file: ``optimizer``, ``mesh``, ``trace_steps``, ``model_config``
(overrides), ``limits``: ``{"ce", "aux", "grad_norm_rel", "loss_drop"}``.
Weights, which token id has which frequency rank, and the batches are all
drawn from ``--seed``, as ``drivers/train.py`` draws them.

That the module exists and has the contract is checked BEFORE
``ray_tpu.init()``: a program that does not know the family (the parent of the
PR that adds it) fails at once, with the reason, and leaves no process.

The result is ``raw["kind"] == "train"`` so that ``train.report_ms``,
``device.idle_pct.train``, ``ownership.*`` and ``collective.exposed_pct``
answer.  ``run.py`` asks EVERY reader in a traced run, and
``model.train_mfu_pct`` looks up ``config["gpt2_config"]`` as soon as the trace
holds a module whose name contains ``train_step``: the family's jitted step
carries another name (``STEP_NAME``, kept in ``raw["step_module"]``).

``correct`` (:func:`verdict`): every loss finite; the mean of the last ten
losses below the mean of the first ten by MORE than ``limits["loss_drop"]``
(fresh batches: an optimizer state or parameters left unchanged read a drop of
+-noise, a coin's toss without the margin; the schedule starts at 0, so a
run's first TWO losses are both the initial weights' and their distance is a
reading of that noise, kept in ``checks``); the first step's ``ce`` and
``aux`` within ``limits`` of the float32 reference on the same batch and the
same initial weights; the first step's gradient norms of the named leaves
within ``limits["grad_norm_rel"]`` (relative) of the reference's.  The
reference runs on ONE of the worker's chips, on one float32 copy of the
weights that is let go before the optimizer's state exists (the two would not
fit together); its seconds are taken out of ``setup_s`` as ``train.py`` does.
That the limits tell bfloat16 from the precision below is a run of this file
(:func:`control`): the reference's FORWARD pass once more on the same copy with
every matmul operand rounded through the lower dtype, its ``ce`` and ``aux``
held to the same limits (no gradient is taken through the rounding: a
cotangent rounded through float8 flushes to zero, which separates nothing).
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

CONTRACT = ("SIZES", "STEP_NAME", "init", "make_optimizer", "make_train_step",
            "param_shardings", "sharding_rules", "named_leaves", "num_params")


def limits_broken(limits: dict, got: dict, ref: dict) -> list:
    """The cell's limits that ``got`` (the program's first step) breaks
    against ``ref`` (a reading of the reference; one without ``grad_norms``,
    the control's, is held by ``ce`` and ``aux`` alone), by name."""
    broken = [k for k in ("ce", "aux") if abs(got[k] - ref[k]) > limits[k]]
    norms = ref.get("grad_norms")
    if norms and max(abs(got["grad_norms"][k] - v) / max(abs(v), 1e-30)
                     for k, v in norms.items()) > limits["grad_norm_rel"]:
        broken.append("grad_norm_rel")
    return broken


def verdict(limits: dict, losses: list, first_step: dict, reference: dict) -> dict:
    """A run's ``checks`` and, under ``"correct"``, whether it passed all of
    them (module docstring).  ``loss_drop``: the mean of the first ten losses
    less the mean of the last ten, held to be MORE than its limit: what an
    update that changes nothing cannot show on fresh batches."""
    finite = all(x == x and abs(x) < 1e4 for x in losses)
    k = min(10, len(losses) // 2)
    drop = (sum(losses[:k]) - sum(losses[-k:])) / k if k >= 2 else 0.0
    falls = finite and drop > limits["loss_drop"]
    broken = limits_broken(limits, first_step, reference)
    return {"correct": bool(finite and falls and not broken),
            "finite": finite, "loss_falls": falls, "loss_drop": drop,
            # two losses of the SAME weights (the schedule starts at 0)
            "same_weights_loss_distance": (
                abs(losses[1] - losses[0]) if len(losses) > 1 else None),
            "limits": limits, "limits_broken": broken,
            "first_step": first_step, "reference": reference}


def train_loop(config: dict) -> None:
    """Runs in the chip-holding worker."""
    import gc
    import shutil

    import jax

    cache_events: list = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: cache_events.append(name))
    devs = jax.devices()
    t_chip = time.time()
    chips = config["chips"]
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if facts["platform"] != config["platform"] or (
            config["platform"] == "tpu" and facts["count"] != chips):
        raise RuntimeError(
            f"the worker holds {facts}, the cell needs {chips} x "
            f"{config['platform']}: not measuring something else")
    devs = devs[:chips]
    facts["count"] = len(devs)

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import trace_reduce, traffic_gen
    from benchmark.drivers.serve_family import scope_seconds, scopes_of_instructions
    from ray_tpu.air import session
    from ray_tpu.parallel import create_mesh

    family = importlib.import_module("ray_tpu.models." + config["family"])
    reference = importlib.import_module(config["reference_module"])
    counting = importlib.import_module(config["counts_module"])
    cfg = family.SIZES[config["size"]](**config["model_config"])
    optimizer = family.make_optimizer(**config["optimizer"])
    key = jax.random.PRNGKey(config["seed"] % (1 << 32))
    mesh = create_mesh(dict(config["mesh"]), devices=devs)
    rules = family.sharding_rules(mesh)
    replicated = NamedSharding(mesh, P())
    p_shard = family.param_shardings(mesh, rules, cfg)
    with_state = lambda p: {"params": p, "opt_state": optimizer.init(p),  # noqa: E731
                            "step": jnp.zeros((), jnp.int32)}
    shapes = jax.eval_shape(lambda k: with_state(family.init(cfg, k)), key)
    # Adam's moments are shaped, and sharded, like the parameters
    o_shard = optax.tree_map_params(
        optimizer, lambda _, s: s, shapes["opt_state"], p_shard,
        transform_non_params=lambda _: replicated)
    s_shard = {"params": p_shard, "opt_state": o_shard, "step": replicated}
    batch_to = NamedSharding(mesh, P(rules.rules["batch"], None))

    # -- set-up: weights on the device in one call, the reference on them,
    # then the optimizer's state, every shape warmed ------------------------
    t = time.time()
    params = jax.block_until_ready(jax.jit(
        lambda k: family.init(cfg, k), out_shardings=p_shard)(key))
    init_s = time.time() - t
    n_params = family.num_params(params)
    vocab = config["model_config"]["vocab_size"]
    batches = traffic_gen.HostBatches(config["traffic"], config["seed"], vocab)
    B, T = batches.batch, batches.seq
    first = batches.next()
    leaves = family.named_leaves(cfg)

    # the reference: float32, whole, on ONE chip; the control's lowered
    # forward pass on the same copy, which is let go before the optimizer's
    # state is made (``leaf.named`` in the reference closes a cycle: collect)
    whole, kept = jax.device_put(params, devs[0]), {}
    t = time.time()
    ref = {"grad_norms": reference.grad_norms(
        whole, first["inputs"], first["targets"], config["reference_sizes"],
        leaves, kept=kept), "ce": kept["ce"], "aux": kept["aux"]}
    ref_s = time.time() - t
    lowered = None
    if config.get("control_dtype"):
        gc.collect()  # what the gradients' pass held, before the next pass
        lowered = dict(zip(("ce", "aux"), reference.loss(
            whole, first["inputs"], first["targets"],
            config["reference_sizes"], lower=config["control_dtype"])))
    del whole, kept
    gc.collect()

    t = time.time()
    state = jax.jit(with_state, out_shardings=s_shard, donate_argnums=(0,))(params)
    del params
    step_fn = family.make_train_step(cfg, optimizer, mesh, rules)
    as_shapes = lambda tree, shard: jax.tree.map(  # noqa: E731
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, shard)
    step = jax.jit(step_fn, donate_argnums=(0,),
                   out_shardings=(s_shard, None)).lower(
        as_shapes(state, s_shard),
        {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=batch_to)
         for k, v in first.items()}).compile()
    compile_s = time.time() - t
    memory = step.memory_analysis()
    step_module = "jit_" + step_fn.__name__

    def landed(m) -> dict:
        """A step's metrics on the host (``bench.loss_readback`` waits here)."""
        m = jax.device_get(m)
        return {"loss": float(m["loss"]), "ce": float(m["ce"]),
                "aux": float(m["aux"]),
                "routed_pairs": np.asarray(m["routed_pairs"]),
                "grad_norms": {k: float(v) for k, v in m["grad_norms"].items()}}

    t = time.time()
    state, m = step(state, jax.device_put(first, batch_to))
    got = landed(m)
    first_step_s = time.time() - t
    t = time.time()
    state, m = step(state, jax.device_put(batches.next(), batch_to))
    losses = [got["loss"], landed(m)["loss"]]
    second_step_s = time.time() - t

    # -- the measured window ---------------------------------------------
    report_s, n_reports, report_max, gap_max, t_prev = 0.0, 0, 0.0, 0.0, None
    pairs_sum, load_sum = np.zeros_like(got["routed_pairs"]), 0.0

    def run_steps(state, until, max_steps=None):
        """Steps until the clock passes ``until`` (or ``max_steps``), one
        kept in flight; ends with everything read back.  Returns the state
        and the number of steps completed."""
        nonlocal report_s, n_reports, report_max, gap_max, t_prev
        nonlocal pairs_sum, load_sum
        done, in_flight = 0, None
        while True:
            more = (time.perf_counter() < until
                    and (max_steps is None or done + (in_flight is not None)
                         < max_steps))
            nxt = None
            if more:
                with jax.profiler.TraceAnnotation("bench.host_batch"):
                    db = jax.device_put(batches.next(), batch_to)
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    state, nxt = step(state, db)
            if in_flight is not None:
                with jax.profiler.TraceAnnotation("bench.loss_readback"):
                    mm = landed(in_flight)
                losses.append(mm["loss"])
                done += 1
                load = counting.chip_load_max_over_mean(mm["routed_pairs"], chips)
                pairs_sum, load_sum = pairs_sum + mm["routed_pairs"], load_sum + load
                t_r = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.report"):
                    session.report({
                        "step": len(losses), "loss": mm["loss"], "ce": mm["ce"],
                        "aux": mm["aux"], "expert_chip_load_max_over_mean": load,
                        "grad_norms": mm["grad_norms"]})
                now = time.perf_counter()
                report_s += now - t_r
                report_max = max(report_max, now - t_r)
                if t_prev is not None:  # a stall anywhere shows as a long gap
                    gap_max = max(gap_max, now - t_prev)
                t_prev = now
                n_reports += 1
            in_flight = nxt
            if in_flight is None:
                return state, done

    trace = None
    t_window = time.time()
    w0 = time.perf_counter()
    steps, left = 0, config["seconds"]
    if config["trace"]:
        trace_dir = config["trace_dir"]
        # the loop's own bench.* annotations name the gaps: no python tracer
        trace_reduce.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                state, n = run_steps(state, w0 + config["seconds"],
                                     config["trace_steps"])
            traced_s = time.perf_counter() - w0
        finally:
            jax.profiler.stop_trace()  # tens of seconds with four devices
        steps, left = n, left - traced_s
    w1 = time.perf_counter()
    state, n = run_steps(state, w1 + left)
    steps += n
    # the time the profiler took to write its file is not part of the window
    window_s = (time.perf_counter() - w1) + (config["seconds"] - left)

    if config["trace"]:
        xplane = trace_reduce.find_xplane(trace_dir)
        events = trace_reduce.load_events(xplane)
        trace = trace_reduce.reduce_events(
            events, trace_reduce.window_of(events, trace_reduce.WINDOW))
        trace["traced_steps"] = config["trace_steps"]
        # one row a NAMED SCOPE of the step program, summed over the devices
        # and the step's runs in the trace (a fusion's own name says nothing
        # of the layer it belongs to)
        seconds = scope_seconds(xplane, step_module, scopes_of_instructions(
            step.as_text(), config.get("trace_scopes") or {}))
        trace["scopes"] = seconds
        trace["step_runs"] = sum(
            m["count"] for name, m in trace["modules"].items()
            if step_module in name)
        trace["device_ops"] = trace["device_ops"] + sorted(
            ([f"scope:{k}", v / max(len(trace["devices"]), 1)]
             for k, v in seconds.items()), key=lambda kv: -kv[1])
        shutil.rmtree(trace_dir, ignore_errors=True)

    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    session.report({
        "done": True, "device": {**facts, "memory_peak_bytes": peak},
        "steps": steps, "tokens": steps * B * T, "window_s": window_s,
        "batch": B, "seq": T, "n_params": n_params, "step_module": step_module,
        "losses": losses, "first_loss": got["loss"],
        "first_step": {k: got[k] for k in ("ce", "aux", "grad_norms")},
        "reference": ref, "control": lowered,
        "routed_pairs_per_step": (pairs_sum / max(steps, 1)).tolist(),
        "chip_load_max_over_mean": load_sum / max(steps, 1),
        "report_s": report_s, "n_reports": n_reports,
        "detail": {"steps": steps, "step_s_mean": window_s / max(steps, 1),
                   "longest_gap_between_reports_s": gap_max,
                   "longest_report_s": report_max,
                   "step_program_bytes": {
                       "arguments": int(memory.argument_size_in_bytes),
                       "temporaries": int(memory.temp_size_in_bytes),
                       "outputs": int(memory.output_size_in_bytes),
                       "aliased": int(memory.alias_size_in_bytes)}},
        "t_chip": t_chip, "t_window": t_window,
        "warmup": {"init_state_s": init_s, "reference_s": ref_s,
                   "step_compile_s": compile_s,
                   "first_step_s": first_step_s,
                   "second_step_s": second_step_s,
                   "cache_hits": sum(e.endswith("/cache_hits")
                                     for e in cache_events),
                   "cache_misses": sum(e.endswith("/cache_misses")
                                       for e in cache_events)},
        "trace": trace,
    })


def family_of(config: dict):
    """The configuration's family module, or SystemExit with the reason: a
    program without it (or without the training contract) fails here, before
    any process is started.  Imports jax, starts no backend."""
    name = "ray_tpu.models." + config.get("family", "")
    try:
        family = importlib.import_module(name)
    except ImportError as e:
        raise SystemExit(f"this program has no model family {name!r}: {e}")
    missing = [k for k in CONTRACT if not hasattr(family, k)]
    if missing or config["size"] not in family.SIZES:
        raise SystemExit(
            f"{name} cannot be trained through this driver: it lacks "
            f"{missing or config['size']}")
    return family


def run(ctx) -> dict:
    """Parent side.  Returns the raw measurements ``run.py`` turns into
    metrics; raises where the cell could not be measured as stated."""
    cell, chips = ctx.cell, ctx.cell["chips"]
    model_config = {**ctx.config["model_config"], **cell.get("model_config", {})}
    family_of(ctx.config).SIZES[ctx.config["size"]](**model_config)

    import ray_tpu
    from ray_tpu.air import ScalingConfig
    from ray_tpu.train import JaxTrainer

    t_init = time.time()
    ctx.init_cluster(ray_tpu)
    try:
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "seed": ctx.seed, "seconds": ctx.seconds,
                "trace": ctx.trace, "chips": chips,
                "platform": ctx.platform,
                "trace_dir": os.path.join(ctx.out_dir, "trace-" + ctx.name),
                "trace_steps": cell.get("trace_steps", 3),
                "family": ctx.config["family"], "size": ctx.config["size"],
                "model_config": model_config,
                "reference_module": ctx.config["reference_module"],
                "reference_sizes": ctx.config["reference_sizes"],
                "counts_module": ctx.config["counts_module"],
                "trace_scopes": ctx.config.get("trace_scopes"),
                "control_dtype": getattr(ctx, "control_dtype", None),
                "optimizer": cell["optimizer"], "mesh": cell["mesh"],
                "traffic": ctx.traffic,
            },
            scaling_config=ScalingConfig(
                num_workers=1,
                resources_per_worker={"CPU": 1, "TPU": chips}),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    m = result.metrics or {}
    if not m.get("done"):
        raise RuntimeError(f"the train loop never finished: {m}")

    checks = verdict(cell["limits"], m["losses"], m["first_step"], m["reference"])
    m.update({
        "kind": "train", "t_init": t_init, "correct": checks.pop("correct"),
        "attempted": m["steps"], "failed": 0, "checks": checks,
        "end_to_end": {
            "train_tokens_per_s_chip": m["tokens"] / m["window_s"] / chips,
            # process start to the window, less the benchmark's own float32
            # reference (timed in the worker): no user's process pays for it
            "setup_s": (m["t_window"] - ctx.t_process
                        - m["warmup"]["reference_s"]),
        },
    })
    if m.get("control"):
        m["checks"]["control"] = m["control"]
        m["checks"]["control_broken"] = limits_broken(
            cell["limits"], m["first_step"], m["control"])
    m["detail"]["chip_load_max_over_mean"] = m["chip_load_max_over_mean"]
    if ctx.trace and (m.get("trace") or {}).get("scopes"):
        m["detail"]["scope_seconds"] = m["trace"]["scopes"]
    return m


def control(argv=None) -> int:
    """The control of a cell's limits::

        python3 -m benchmark.drivers.train_family --workload <cell> \\
            --seed <n> --seconds <s> --reference-dtype float8_e4m3fn

    One run of the cell as ``run.py`` makes it (untraced), whose first step is
    held to the reference twice by the run's own comparison
    (:func:`limits_broken`): as the configuration states it (float32: has to
    come out correct) and against the reference's forward pass with every
    matmul operand rounded through ``--reference-dtype`` first, the nearest
    precision below the configuration's bfloat16 (has to come out NOT
    correct).  Prints one JSON line with the run's checks, both readings and
    the limits each broke; exits 0 when the cell's limits told the two
    apart."""
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
    checks = raw["checks"]
    print(json.dumps({
        "workload": ctx.name, "seed": ctx.seed, "correct": raw["correct"],
        "control_dtype": args.reference_dtype,
        "control_correct": not checks["control_broken"],
        "control_broke": checks["control_broken"], "checks": checks,
        "end_to_end": raw["end_to_end"], "device": raw["device"],
        "detail": raw["detail"], "setup_detail": raw["warmup"]}), flush=True)
    return 0 if raw["correct"] and checks["control_broken"] else 1


if __name__ == "__main__":
    sys.exit(control())
