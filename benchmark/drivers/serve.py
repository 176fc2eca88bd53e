"""How a ``serve`` cell is brought up, warmed, measured and torn down.

The parent stays off JAX.  ``serve.run(llm_deployment(...))`` puts the
continuous-batching engine on a ``num_tpus=1`` replica behind the HTTP
proxy; load comes from ``loadgen.py`` in a child process, open loop, on the
schedule ``traffic_gen`` draws from the seed.  After the window and
``serve.shutdown()`` a ``num_tpus=1`` actor checks a seeded sample of the
served tokens against the plain reference (which also shows the replica
gave the chip back).

The untraced run deploys ``llm_deployment(...)`` unchanged.  The program has
no hook to trace the replica's device, so the traced run (only) deploys a
subclass of the deployment's own class that adds ``trace_start`` /
``trace_stop`` / ``bench_facts`` and leaves the request path alone.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

HBM_PEAK_GAUGE = "ray_tpu_hbm_peak_bytes_in_use"
# between telling the generator the window's start and its first request
T0_SLACK_S = 0.25


def traced(dep, trace_dir: str):
    """The same deployment with a traceable replica class."""
    from ray_tpu.serve.api import Deployment

    base = dep._func_or_class

    class TracedLLMServer(base):
        def __init__(self):
            import jax

            jax.devices()
            self._bench = {"t_chip": time.time()}
            super().__init__()
            self._bench["t_ready"] = time.time()

        def bench_facts(self):
            import jax

            devs = jax.devices()
            stats = devs[0].memory_stats() or {}
            return {**self._bench, "platform": devs[0].platform,
                    "kind": devs[0].device_kind, "count": len(devs),
                    "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

        def trace_start(self):
            from benchmark import trace_reduce

            trace_reduce.start_trace(trace_dir)
            return time.time()

        def trace_stop(self):
            import jax

            jax.profiler.stop_trace()
            return time.time()

        def trace_reduce(self):
            import shutil

            from benchmark import trace_reduce

            events = trace_reduce.load_events(
                trace_reduce.find_xplane(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
            return trace_reduce.reduce_events(events)

    return Deployment(TracedLLMServer, dep.name, dep.config,
                      route_prefix=dep.route_prefix)


class Reference:
    """Runs in a ``num_tpus=1`` actor after the replica is gone: the plain
    float32 forward over prompt + served tokens, on the parameters the
    replica made from the same seed."""

    def check(self, gpt2_config, seed, samples, margin):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmark.reference import gpt2_ref
        from ray_tpu.serve.llm import _default_init, make_config

        cfg = make_config("gpt2", "small", **gpt2_config)
        params = _default_init(cfg, seed)  # the weights are the program's
        width = -(-max(len(p) + len(o) for p, o in samples) // 128) * 128
        exact = ties = wrong = 0
        gaps = []
        for lo in range(0, len(samples), 2):
            group = samples[lo:lo + 2]
            buf = np.zeros((len(group), width), np.int32)
            for r, (p, o) in enumerate(group):
                buf[r, :len(p) + len(o)] = p + o
            logits = np.asarray(gpt2_ref.logits(
                params, jnp.asarray(buf), cfg.n_heads))
            for r, (p, o) in enumerate(group):
                # the logits at position len(p)-1+i chose served token o[i]
                at = logits[r, len(p) - 1:len(p) - 1 + len(o)]
                got = np.asarray(o)
                gap = at.max(-1) - at[np.arange(len(o)), got]
                exact += int((at.argmax(-1) == got).sum())
                ties += int(((at.argmax(-1) != got) & (gap <= margin)).sum())
                wrong += int((gap > margin).sum())
                gaps += gap.tolist()
        gaps.sort()
        dev = jax.devices()[0]
        return {"tokens": len(gaps), "equal": exact, "ties": ties,
                "wrong": wrong, "worst_gap": gaps[-1] if gaps else 0.0,
                "gap_p99": gaps[int(0.99 * (len(gaps) - 1))] if gaps else 0.0,
                "logit_std": float(logits[0, 0].std()),
                "platform": dev.platform, "kind": dev.device_kind,
                "count": len(jax.devices())}


def post(url: str, payload: dict, timeout: float):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def hbm_peak_from_gauge() -> dict:
    """The replica's own high-water gauge, read from the head's TSDB: the
    engine publishes ``device.memory_stats()`` there, tagged kind=hbm only
    where the backend has device memory (on a CPU there is no such series)."""
    from ray_tpu.experimental.state import api as state

    out = state.query_metric(HBM_PEAK_GAUGE, window_s=3600.0, agg="max",
                             tags={"kind": "hbm"})
    values = [p[1] for series in out.get("series", [])
              for p in series.get("points", []) if p[1]]
    return {"bytes": int(max(values)) if values else 0,
            "kind": "hbm" if values else None}


def run(ctx) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import llm_deployment

    from benchmark import traffic_gen

    cell, traffic = ctx.cell, ctx.traffic
    engine = dict(cell["engine"])
    engine["prefill_buckets"] = tuple(engine["prefill_buckets"])
    engine["seed"] = seed32 = ctx.seed % (1 << 32)
    gpt2_config = ctx.config["gpt2_config"]
    reach = (max(engine["prefill_buckets"]) + engine["max_new_tokens"]
             + engine["decode_chunk_steps"])
    if reach > gpt2_config["max_seq_len"]:
        raise ValueError(  # nothing in the program checks this; JAX clamps
            f"the cache reaches position {reach}, GPT-2 has "
            f"{gpt2_config['max_seq_len']}")
    schedule = traffic_gen.serve_schedule(
        traffic, ctx.seed, ctx.seconds, ctx.config["vocab_real"])
    trace_dir = os.path.join(ctx.out_dir, "trace-" + ctx.name)

    t_init = time.time()
    ctx.init_cluster(ray_tpu)
    raw: dict = {"kind": "serve", "t_init": t_init}
    try:
        dep = llm_deployment("gpt2", "small", num_tpus=1,
                             config_kwargs=gpt2_config, engine_kwargs=engine)
        if ctx.trace:
            dep = traced(dep, trace_dir)
        try:
            handle = serve.run(dep.bind(), port=0, timeout_s=1100)
            t_ready = time.time()
            host, port = serve.get_http_address()
            url = f"http://{host}:{port}/{dep.name}"

            # warm every shape the window uses: one prompt per prefill
            # bucket, alone (admission pads to n_slots rows, so a bucket is
            # one program), through one decode chunk, streamed like the rest
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
        import random

        sample = random.Random(ctx.seed).sample(
            done, min(cell["reference_sample"], len(done)))
        ref = ray_tpu.get(
            ray_tpu.remote(num_tpus=1)(Reference).remote().check.remote(
                gpt2_config, seed32,
                [(schedule["prompts"][r["i"]], r["tokens"]) for r in sample],
                cell["logit_tie_margin"]),
            timeout=900)
    finally:
        ray_tpu.shutdown()

    if not ctx.rehearsal and peak["kind"] not in ("hbm", "memory_stats"):
        raise RuntimeError(f"the replica reported no device memory: {peak}")
    raw["device"] = {"platform": ref["platform"], "kind": ref["kind"],
                     "count": ref["count"], "memory_peak_bytes": peak["bytes"]}
    raw.update(summarize(records, schedule, ctx.seconds,
                         cell["client_timeout_s"]))
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
        # every sampled token's logit is the reference's maximum or within
        # the cell's tie margin of it, and nearly all ARE the maximum: a
        # server in a lower precision than bf16 picks second-best far more
        # often (the reasons for both numbers are in the cell's file)
        "correct": bool(sample and ref["wrong"] == 0
                        and ref["equal"] >= cell["min_exact_share"] * ref["tokens"]
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
    return raw


def summarize(records, schedule, seconds, timeout_s) -> dict:
    """Client-side arithmetic over the generator's records.  Only requests
    due inside the window are judged; a request that failed, was refused
    or timed out counts in ``failed`` and enters both latency percentiles
    at the client's timeout."""
    from benchmark.traffic_gen import percentile

    judged = [r for r in records if r is not None and 0 <= r["due"] < seconds]
    ttft, tpot, ttft_sent, late = [], [], [], []
    failed = 0
    for r in judged:
        late.append(r["sent"] - r["due"])
        if r.get("done") and r["times"]:
            ttft.append(r["times"][0] - r["due"])
            ttft_sent.append(r["times"][0] - r["sent"])
            if len(r["times"]) >= 2:
                tpot.append((r["times"][-1] - r["times"][0])
                            / (len(r["times"]) - 1))
        else:
            failed += 1
            ttft.append(timeout_s)
            tpot.append(timeout_s)
    in_window = sum(1 for r in records if r is not None
                    for t in r["times"] if 0 <= t <= seconds)
    return {
        "attempted": len(judged), "failed": failed,
        "end_to_end": {
            "serve_tokens_per_s": in_window / seconds,
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "tpot_p95_ms": 1e3 * percentile(tpot, 95),
        },
        "detail": {
            "requests_sent": sum(r is not None for r in records),
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "tpot_p50_ms": 1e3 * percentile(tpot, 50),
            "tokens_in_window": in_window,
        },
        "client_ttft_from_send_p50_s": percentile(ttft_sent, 50) if ttft_sent else None,
        "late_p95_ms": 1e3 * percentile(late, 95),
        # (record, prompt length) pairs: the decode roofline reads live
        # cache positions off them
        "records": [(r, len(schedule["prompts"][r["i"]]))
                    for r in records if r is not None],
    }
