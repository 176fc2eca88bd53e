"""How a serve cell whose requests carry a VIDEO is brought up, warmed,
measured and torn down (kind ``serve_vision``): ``drivers/serve_family.py``'s
walk (the same deployment, records, stage reads, scopes and shutdown; its
``traced_by_scope``, ``limits_broken`` and ``proxy_share`` and
``drivers/serve.py``'s ``post``, ``summarize`` and ``hbm_peak_from_gauge`` are
imported, not copied) with three things of its own:

- the TRAFFIC (:func:`video_schedule`): a request is ``system_len`` tokens, ONE
  video of ``F`` frames of ``frame_grid`` patches (``F`` from the traffic's
  ``frames`` distribution; the placeholder id once a merged row), then a
  question (``question_len``); answers from ``output_len``.  One fixed trace
  for every seed, as ``traffic_gen`` makes it (the distributions' quantiles in
  one fixed shuffled order); the token ids (never the placeholder's) and the
  pixels are the seed's;
- the CLIENT (``drivers/loadgen_vision.py``): builds each body before it is
  due and streams the answer as ``loadgen.py`` does;
- the CHECK (:class:`VisionReference`): the plain reference is given the same
  pixels (drawn again from the seed) and asked for the served positions'
  logits alone (a row of 151,936 logits a position: the whole sequence's
  would be 10 GB).

The configuration file's contract is ``serve_family``'s (``family``, ``size``,
``model_config``, ``reference_module`` with ``logits(params, tokens, sizes,
lower=None, videos=None, rows=None)``, ``reference_sizes``, ``counts_module``,
``trace_scopes``); the cell's file is ``serve_family``'s.  The family is
checked BEFORE ``ray_tpu.init()``: a program that does not know it (the parent
of the PR that adds it) fails at once, with the reason.

The traced replica also reads ``perf_stats()``'s ``vision``, ``vision_ticks``
and ``dsa`` at the trace's two ends, and its reduction carries the ``scope:*``
rows of the TOWER's program beside the decode program's (``scope:vision.*``:
the device time inside the runs of ``llm_vision_encode``).

The controls of a cell's limits are runs of this file (:func:`control`):
``--control float8_e4m3fn`` (every matmul operand of the reference through it),
``--control dense`` (``index_topk`` above every context: the selection left
out), ``--control one_axis`` (the video's tokens at a text token's positions);
each must come out NOT correct.
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

import numpy as np

from benchmark.drivers.serve import (
    T0_SLACK_S,
    hbm_peak_from_gauge,
    post,
    summarize,
)
from benchmark.drivers.serve_family import (
    GAP_STEPS,
    limits_broken,
    proxy_share,
    scope_seconds,
    scopes_of_instructions,
    traced_by_scope,
)

VISION_MODULE = "llm_vision_encode"
CONTROLS = {"dense": {"index_topk": 1 << 30}, "one_axis": {"one_axis_positions": True}}


def video_schedule(traffic: dict, config: dict, seed: int, seconds: float) -> dict:
    """The open-loop schedule of a video cell: ``due`` (seconds from the
    window's start; negative: the pre-roll), ``prompts`` (token ids, the
    placeholder where the video's rows stand), ``frames``, ``max_new``.  Times,
    frame counts and lengths are the same for every seed."""
    from benchmark import traffic_gen as tg

    rate = traffic["arrivals"]["rate_per_s"]
    if traffic["arrivals"]["process"] != "poisson":
        raise ValueError("the only arrival process so far is 'poisson'")
    model = config["model_config"]
    placeholder, vocab = model["video_token_id"], model["vocab_size"]
    gh, gw = traffic["frame_grid"]
    per_frame = (gh // 2) * (gw // 2)

    def fixed(n, span):
        gaps = tg.quantile_set({"dist": "exponential", "mean": 1.0}, n)
        columns = [gaps * (span / gaps.sum())] + [
            tg.int_lengths(traffic[name], n)
            for name in ("frames", "question_len", "output_len")]
        for stream, values in zip(
                ("gaps", "frames", "question_len", "output_len"), columns):
            tg.rng_for(tg.SET_SEED, stream).shuffle(values)
        return columns

    gaps, frames, question, output = fixed(
        max(1, int(round(rate * seconds))), float(seconds))
    due = np.cumsum(gaps) - gaps[0]
    preroll = float(traffic.get("preroll_s", 0.0))
    n_pre = int(round(rate * preroll))
    if n_pre:
        g, f, q, o = fixed(n_pre, preroll)
        due = np.concatenate([np.cumsum(g) - g[0] - preroll, due])
        frames, question, output = (
            np.concatenate(pair) for pair in ((f, frames), (q, question), (o, output)))
    tok = tg.rng_for(seed, "tokens")

    def text(n):  # ids of the seed's, never the placeholder's
        ids = tok.integers(0, vocab - 1, int(n))
        return np.where(ids >= placeholder, ids + 1, ids).tolist()

    prompts = [text(traffic["system_len"]) + [placeholder] * int(f * per_frame)
               + text(q) for f, q in zip(frames, question)]
    return {"due": due.tolist(), "prompts": prompts,
            "frames": [int(f) for f in frames], "max_new": output.tolist(),
            "frame_grid": [gh, gw], "seed": int(seed),
            "patch_values": 3 * model["vision_patch"] ** 2}


def traced_with_vision(dep, trace_dir: str, decode_module: str, scopes: dict):
    """``serve_family.traced_by_scope``'s replica, which also reads the
    tower's counters and the selection's at the trace's two ends and adds the
    tower program's own ``scope:*`` rows to the reduction."""
    from ray_tpu.serve.api import Deployment

    from benchmark import trace_reduce

    dep = traced_by_scope(dep, trace_dir, decode_module, scopes)

    class VisionLLMServer(dep._func_or_class):
        def _counters(self):
            stats = self.engine.perf_stats()
            return {k: stats.get(k) for k in (
                "moe", "cache_tiles", "prefill", "vision", "vision_ticks",
                "dsa", "tick_s")}

        def _vision_text(self):
            """The tower program's compiled text, for the grid it ran at."""
            import jax

            eng = self.engine
            grid = self._bench.get("vision_grid")
            if grid is None or eng._vision_jit is None:
                return None
            shape = (eng._frames_a_call({"grid": (0, *grid)}),)
            shapes = lambda tree: jax.tree.map(  # noqa: E731
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
            values = 3 * eng.cfg.vision_patch ** 2
            return eng._vision_jit.lower(
                shapes(eng.params), jax.ShapeDtypeStruct(
                    (shape[0], grid[0] * grid[1], values), "uint8"),
                grid=tuple(grid)).compile().as_text()

        def set_vision_grid(self, grid):
            self._bench["vision_grid"] = tuple(grid)

        def trace_reduce(self):
            try:
                text = self._vision_text()
                tower = scope_seconds(
                    trace_reduce.find_xplane(trace_dir), VISION_MODULE,
                    scopes_of_instructions(text, scopes)) if text else {}
            except Exception:  # noqa: BLE001 — the rows are an extra
                tower = {}
            reduced = super().trace_reduce()
            tower = {k: v for k, v in tower.items() if k.startswith("vision.")}
            reduced["scopes"] = {**(reduced.get("scopes") or {}), **tower}
            reduced["device_ops"] = reduced.get("device_ops", []) + sorted(
                ([f"scope:{k}", v] for k, v in tower.items()),
                key=lambda kv: -kv[1])
            return reduced

    return Deployment(VisionLLMServer, dep.name, dep.config,
                      route_prefix=dep.route_prefix)


class VisionReference:
    """Runs in a ``num_tpus=1`` actor after the replica is gone."""

    def check(self, config, seed, samples, margin, lower=None, changed=None):
        """``samples``: ``(request index, prompt, served tokens, frames, grid,
        patch values)``; the pixels are drawn again from ``seed``."""
        import jax
        import jax.numpy as jnp

        from benchmark.drivers.loadgen_vision import pixels
        from ray_tpu.serve.llm import _default_init, make_config

        ref = importlib.import_module(config["reference_module"])
        cfg = make_config(config["family"], config["size"],
                          **config["model_config"])
        params = _default_init(cfg, seed % (1 << 32))  # the weights the server held
        params = jax.tree.map(
            lambda x: x.astype(cfg.dtype) if x.dtype == jnp.float32 else x,
            params)
        sizes = {**config["reference_sizes"], **(changed or {})}
        exact = ties = over = 0
        gaps = []
        logit_std = 0.0
        for i, prompt, served, frames, (gh, gw), values in samples:
            width = -(-(len(prompt) + len(served)) // 128) * 128
            buf = np.zeros((1, width), np.int32)
            buf[0, :len(prompt) + len(served)] = prompt + served
            video = None if not frames else (pixels(
                seed, i, frames * gh * gw, values).reshape(
                    frames, gh * gw, values), (gh, gw))
            # the logits at position len(p)-1+j chose served token j
            at = ref.logits(
                params, buf, sizes, lower=lower, videos=[video],
                rows=[(len(prompt) - 1, len(prompt) - 1 + len(served))])[0]
            got = np.asarray(served)
            gap = at.max(-1) - at[np.arange(len(served)), got]
            exact += int((at.argmax(-1) == got).sum())
            ties += int(((at.argmax(-1) != got) & (gap <= margin)).sum())
            over += int((gap > margin).sum())
            gaps += gap.tolist()
            logit_std = float(at[0].std())
        gaps.sort()
        dev = jax.devices()[0]
        return {"tokens": len(gaps), "equal": exact, "ties": ties,
                "over_margin": over, "worst_gap": gaps[-1] if gaps else 0.0,
                "gap_p99": gaps[int(0.99 * (len(gaps) - 1))] if gaps else 0.0,
                "gap_over": {str(t): sum(g > t for g in gaps)
                             for t in GAP_STEPS},
                "lower": lower, "changed": changed, "logit_std": logit_std,
                "platform": dev.platform, "kind": dev.device_kind,
                "count": len(jax.devices())}


def run(ctx) -> dict:
    config, cell, traffic = ctx.config, ctx.cell, ctx.traffic
    from ray_tpu.models import generate

    if config["family"] not in generate.FAMILIES:
        raise SystemExit(
            f"this program has no model family {config['family']!r} "
            f"(it has {sorted(generate.FAMILIES)})")
    from ray_tpu.serve.llm import llm_deployment, make_config

    make_config(config["family"], config["size"], **config["model_config"])

    import ray_tpu
    from ray_tpu import serve

    engine = dict(cell["engine"])
    engine["prefill_buckets"] = tuple(engine["prefill_buckets"])
    engine["seed"] = seed32 = ctx.seed % (1 << 32)
    schedule = video_schedule(traffic, config, ctx.seed, ctx.seconds)
    if max(map(len, schedule["prompts"])) > max(engine["prefill_buckets"]) \
            or traffic["output_len"]["max"] > engine["max_new_tokens"]:
        raise ValueError("the traffic asks for more than the engine admits")
    trace_dir = os.path.join(ctx.out_dir, "trace-" + ctx.name)
    grid, values = schedule["frame_grid"], schedule["patch_values"]

    t_init = time.time()
    ctx.init_cluster(ray_tpu)
    raw: dict = {"kind": "serve", "t_init": t_init}
    try:
        dep = llm_deployment(config["family"], config["size"], num_tpus=1,
                             config_kwargs=config["model_config"],
                             engine_kwargs=engine)
        if ctx.trace:
            dep = traced_with_vision(
                dep, trace_dir, cell.get("decode_module", "decode_chunk"),
                config.get("trace_scopes") or {})
        try:
            handle = serve.run(dep.bind(), port=0, timeout_s=1100)
            t_ready = time.time()
            host, port = serve.get_http_address()
            url = f"http://{host}:{port}/{dep.name}"
            if ctx.trace:
                ray_tpu.get(handle.set_vision_grid.remote(grid), timeout=60)

            # warm every shape the window uses: the cell's ``warm_frames``
            # videos, each a request alone (the first part through its
            # bucket's program, the others through the part program, the
            # tower's call, a whole and a cut decode chunk), streamed
            import base64

            model = config["model_config"]
            per_frame = (grid[0] // 2) * (grid[1] // 2)
            warm = []
            for frames in cell["warm_frames"]:
                t = time.time()
                status, _ = post(url, {
                    "tokens": [1 + (i % 97) for i in range(traffic["system_len"])]
                    + [model["video_token_id"]] * (frames * per_frame)
                    + [1 + (i % 89) for i in range(16)],
                    "max_new_tokens": min(engine["max_new_tokens"],
                                          engine["decode_chunk_steps"] + 2),
                    "stream": True,
                    "video": {"grid": [frames, *grid], "patches": base64.b64encode(
                        bytes(frames * grid[0] * grid[1] * values)).decode()}},
                    timeout=1100)
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
                [sys.executable, os.path.join(
                    ctx.root, "benchmark", "drivers", "loadgen_vision.py"),
                 spec_path, out_path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
            if gen.stdout.readline().strip() != "ready":
                gen.kill()
                gen.wait()
                raise RuntimeError("the load generator did not come up")
            # (the first bodies are built LEAD_S ahead of the pre-roll's first)
            t0 = time.time() + preroll + T0_SLACK_S + 2.5
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
                         + cell["client_timeout_s"] + 180)
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
        checker = ray_tpu.remote(num_tpus=1)(VisionReference).remote()
        program = {k: config[k] for k in (
            "family", "size", "model_config", "reference_module",
            "reference_sizes")}
        samples = [(r["i"], schedule["prompts"][r["i"]], r["tokens"],
                    schedule["frames"][r["i"]], grid, values) for r in sample]
        held_to = lambda lower, changed: ray_tpu.get(  # noqa: E731
            checker.check.remote(program, ctx.seed, samples,
                                 cell["logit_tie_margin"], lower, changed),
            timeout=3000)
        ref = held_to(None, None)
        control = getattr(ctx, "control", None)
        lowered = None
        if control:
            lowered = held_to(None, CONTROLS[control]) if control in CONTROLS \
                else held_to(control, None)
    finally:
        ray_tpu.shutdown()

    if not ctx.rehearsal and peak["kind"] not in ("hbm", "memory_stats"):
        raise RuntimeError(f"the replica reported no device memory: {peak}")
    raw["device"] = {"platform": ref["platform"], "kind": ref["kind"],
                     "count": ref["count"], "memory_peak_bytes": peak["bytes"]}
    raw.update(summarize(records, schedule, ctx.seconds,
                         cell["client_timeout_s"]))
    # (``serve_family``'s reason: GPT-2's roofline reader would fail the run)
    raw["client_records"], raw["records"] = raw["records"], []
    raw["end_to_end"]["setup_s"] = t0 - ctx.t_process
    half = len(polls) // 2 or 1
    mean = lambda xs: sum(xs) / len(xs) if xs else 0.0
    raw["detail"].update({
        "queued_first_half": mean([q for _, q in polls[:half]]),
        "queued_second_half": mean([q for _, q in polls[half:]]),
        "active_slots_mean": mean([a for a, _ in polls]),
        "rate_per_s": traffic["arrivals"]["rate_per_s"],
        "refused": engine_stats.get("refused"),
        "body_bytes_max": max((r.get("body_bytes") or 0) for r in records if r),
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
        "frame_grid": grid,
    })
    raw["detail"]["ttft_p95_ms"] = raw["end_to_end"]["ttft_p95_ms"]
    raw["detail"]["proxy"] = proxy_share(raw)
    counting = importlib.import_module(config["counts_module"])
    counts = counting.window_counts(raw)
    if counts:
        raw["detail"]["window_counts"] = {
            k: v for k, v in counts.items() if not k.startswith("expert_tokens")}
    between = ctx.trace and counting.traced_counts(raw)
    if between:
        start = raw["trace"]["marks"]["start"]
        raw["detail"]["traced_counts"] = {
            **{k: v for k, v in between.items()
               if k in ("decode_steps", "vision") or k.endswith("_per_step")},
            "live_rows": counting.live_rows_between(
                raw["client_records"], start,
                start + raw["trace"]["window_s"])}
    if ctx.trace and raw.get("trace", {}).get("scopes"):
        raw["detail"]["scope_seconds"] = raw["trace"]["scopes"]
    if lowered:
        raw["checks"]["control"] = lowered
    return raw


def control(argv=None) -> int:
    """A control of a cell's limits::

        python3 -m benchmark.drivers.serve_vision --workload <cell> \\
            --seed <n> --seconds <s> --control float8_e4m3fn | dense | one_axis

    One run of the cell as ``run.py`` makes it (untraced), whose served tokens
    are held to the reference twice: as the configuration states it (has to
    come out correct) and as the control changes it (has to come out NOT
    correct).  Prints one JSON line; exits 0 when the limits told them apart."""
    import argparse

    from benchmark import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", default="float8_e4m3fn")
    args = ap.parse_args(argv)
    args.trace = 0
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
    ctx.control = args.control
    raw = run(ctx)
    sound, changed = raw["checks"]["reference"], raw["checks"]["control"]
    broke = limits_broken(ctx.cell, changed)
    print(json.dumps({
        "workload": ctx.name, "seed": ctx.seed, "correct": raw["correct"],
        "failed": raw["failed"], "control": args.control,
        "control_correct": not broke, "control_broke": broke,
        "reference": sound, "control_reading": changed,
        "end_to_end": raw["end_to_end"]}), flush=True)
    return 0 if raw["correct"] and broke else 1


if __name__ == "__main__":
    sys.exit(control())
