"""How a ``train`` cell is brought up, warmed, measured and torn down.

The parent (``run.py``) stays off JAX: ``ray_tpu.init()`` finds the chips,
``JaxTrainer`` with ONE worker that holds all of the cell's chips runs
``train_loop`` below, and everything measured comes back through
``session.report``.  The loop is the benchmark's own (a plain, well-written
user loop: a fresh host batch per step, one step kept in flight, the loss
read back and reported every step); the model, optimizer, train step, mesh
and sharding rules are the program's.
"""

from __future__ import annotations

import os
import time


def train_loop(config: dict) -> None:
    """Runs in the chip-holding worker."""
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
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import trace_reduce, traffic_gen
    from benchmark.reference import gpt2_ref
    from ray_tpu.air import session
    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2Config.gpt2_small(**config["gpt2_config"])
    optimizer = gpt2.make_optimizer(**config["optimizer"])
    key = jax.random.PRNGKey(config["seed"] % (1 << 32))
    make_state = lambda k: gpt2.init_state(cfg, k, optimizer)
    if config.get("mesh"):
        from ray_tpu.parallel import create_mesh
        from ray_tpu.parallel.sharding import rules_for_mesh

        mesh = create_mesh(dict(config["mesh"]), devices=devs)
        rules = rules_for_mesh(mesh)
        replicated = NamedSharding(mesh, P())
        p_shard = gpt2.param_shardings(mesh, rules, cfg)
        shapes = jax.eval_shape(make_state, key)
        # Adam's moments are shaped, and sharded, like the parameters
        o_shard = optax.tree_map_params(
            optimizer, lambda _, s: s, shapes["opt_state"], p_shard,
            transform_non_params=lambda _: replicated)
        s_shard = {"params": p_shard, "opt_state": o_shard, "step": replicated}
        init = jax.jit(make_state, out_shardings=s_shard)
        step = jax.jit(gpt2.make_train_step(cfg, optimizer, mesh),
                       donate_argnums=(0,), out_shardings=(s_shard, None))
        batch_to = NamedSharding(mesh, P(rules.rules["batch"], None))
    else:
        mesh = None
        init = jax.jit(make_state)
        step = jax.jit(gpt2.make_train_step(cfg, optimizer),
                       donate_argnums=(0,))
        batch_to = devs[0]

    # -- set-up: weights on the device in one call, every shape warmed ----
    t = time.time()
    state = jax.block_until_ready(init(key))
    init_s = time.time() - t
    n_params = gpt2.num_params(state["params"])
    batches = traffic_gen.HostBatches(
        config["traffic"], config["seed"], config["vocab_real"])
    B, T = batches.batch, batches.seq
    first = batches.next()
    t = time.time()
    ref_loss = gpt2_ref.loss(
        state["params"], jnp.asarray(first["inputs"]),
        jnp.asarray(first["targets"]), cfg.n_heads)
    ref_s = time.time() - t
    t = time.time()
    state, m = step(state, jax.device_put(first, batch_to))
    first_loss = float(m["loss"])
    first_step_s = time.time() - t
    t = time.time()
    state, m = step(state, jax.device_put(batches.next(), batch_to))
    losses = [first_loss, float(m["loss"])]
    second_step_s = time.time() - t

    # -- the measured window ---------------------------------------------
    report_s, n_reports, report_max, gap_max, t_prev = 0.0, 0, 0.0, 0.0, None

    def run_steps(state, until, max_steps=None):
        """Steps until the clock passes ``until`` (or ``max_steps``), one
        kept in flight; ends with everything read back.  Returns the state
        and the number of steps completed."""
        nonlocal report_s, n_reports, report_max, gap_max, t_prev
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
                    state, mm = step(state, db)
                nxt = mm["loss"]
            if in_flight is not None:
                with jax.profiler.TraceAnnotation("bench.loss_readback"):
                    loss = float(in_flight)
                losses.append(loss)
                done += 1
                t_r = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.report"):
                    session.report({"step": len(losses), "loss": loss})
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
        events = trace_reduce.load_events(trace_reduce.find_xplane(trace_dir))
        trace = trace_reduce.reduce_events(
            events, trace_reduce.window_of(events, trace_reduce.WINDOW))
        trace["traced_steps"] = config["trace_steps"]
        shutil.rmtree(trace_dir, ignore_errors=True)

    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    session.report({
        "done": True, "device": {**facts, "memory_peak_bytes": peak},
        "steps": steps, "tokens": steps * B * T, "window_s": window_s,
        "batch": B, "seq": T, "n_params": n_params,
        "losses": losses, "first_loss": first_loss, "ref_loss_f32": ref_loss,
        "report_s": report_s, "n_reports": n_reports,
        "detail": {"steps": steps, "step_s_mean": window_s / max(steps, 1),
                   "longest_gap_between_reports_s": gap_max,
                   "longest_report_s": report_max},
        "t_chip": t_chip, "t_window": t_window,
        "warmup": {"init_state_s": init_s, "reference_s": ref_s,
                   "first_step_s": first_step_s,
                   "second_step_s": second_step_s,
                   "cache_hits": sum(e.endswith("/cache_hits")
                                     for e in cache_events),
                   "cache_misses": sum(e.endswith("/cache_misses")
                                       for e in cache_events)},
        "trace": trace,
    })


def run(ctx) -> dict:
    """Parent side.  Returns the raw measurements ``run.py`` turns into
    metrics; raises where the cell could not be measured as stated."""
    import ray_tpu
    from ray_tpu.air import ScalingConfig
    from ray_tpu.train import JaxTrainer

    cell, chips = ctx.cell, ctx.cell["chips"]
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
                "trace_steps": cell.get("trace_steps", 6),
                "gpt2_config": {**ctx.config["gpt2_config"],
                                **cell.get("gpt2_config", {})},
                "vocab_real": ctx.config["vocab_real"],
                "optimizer": cell["optimizer"], "mesh": cell.get("mesh"),
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

    losses = m["losses"]
    tol = cell["loss_tolerance"]
    finite = all(x == x and abs(x) < 1e4 for x in losses)
    k = min(10, len(losses) // 2)
    falls = k >= 2 and sum(losses[-k:]) / k < sum(losses[:k]) / k
    close = abs(m["first_loss"] - m["ref_loss_f32"]) <= tol
    m.update({
        "kind": "train", "t_init": t_init,
        "correct": bool(finite and falls and close),
        "attempted": m["steps"], "failed": 0,
        "checks": {"finite": finite, "loss_falls": falls,
                   "first_loss_vs_f32_reference":
                       [m["first_loss"], m["ref_loss_f32"], tol]},
        "end_to_end": {
            "train_tokens_per_s_chip": m["tokens"] / m["window_s"] / chips,
            # process start to the window, less the benchmark's own float32
            # reference (timed in the worker): no user's process pays for it
            "setup_s": (m["t_window"] - ctx.t_process
                        - m["warmup"]["reference_s"]),
        },
    })
    return m
