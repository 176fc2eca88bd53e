"""Proof that the runtime's main path runs on the attached TPU.

    python chip_smoke.py              # one chip: core, hand-back, train, serve
    python chip_smoke.py --chips 4    # four chips: one-chip actors x4, 2x2 step
    python chip_smoke.py --rehearse [--chips 4]   # CPU, tiny; never "ok": true

One chip, in this order, each phase its own ``ray_tpu.init()`` …
``shutdown()`` so the chip passes from one worker process to the next:

- core: a bare ``init()`` finds the chip; a ``num_tpus=1`` actor reports the
  device and a bf16 matmul; a concurrent ``num_tpus=0`` task that uses jax
  lands on the CPU and does not disturb the actor.
- hand-back: a ``num_tpus=1`` TASK touches JAX and returns; an actor created
  afterwards in the same session gets the chip (the task's worker retired).
- train: ``JaxTrainer`` with one ``{"CPU": 1, "TPU": 1}`` worker takes a few
  steps of GPT-2 125M (unchanged: 12 layers, d_model 768, vocab 50304,
  T=1024, bf16, B=6, dots remat) on a fixed batch made from the seed; the
  loss falls, and the first step agrees with the same loss computed in f32
  with materialized attention in that worker.
- serve: ``llm_deployment("gpt2", "small", num_tpus=1)`` behind the HTTP
  proxy answers a few POSTs; after ``serve.shutdown()`` a new ``num_tpus=1``
  actor in the same session recomputes every token step by step WITHOUT a
  cache from the same seed's parameters (which also shows the replica's
  process gave the chip back).

The parent never starts a JAX backend: a chip belongs to one process, and
every device fact printed here came back from a worker through
``ray_tpu.get``.  Each phase prints one JSON line; the last line of output
is the verdict, ``{"ok": ..., "device": {...}}``, and nothing follows it.
With no chip the script fails; it does not run on the CPU and call that
success.  ``--rehearse`` walks the same control flow on the CPU at tiny
sizes with fake chips — it tests this script, not the chip, and says so:
its verdict is never ``"ok": true`` and never names a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import signal
import sys
import threading
import time

OVERALL_S = 1150.0  # the driver allows 1200 s, compilation included
_T0 = time.monotonic()


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


@contextlib.contextmanager
def deadline(seconds: float, what: str):
    """Every wait in a phase ends: SIGALRM raises in the main thread, which
    interrupts the blocking get/join/urlopen the phase is parked in."""
    seconds = max(1.0, min(seconds, OVERALL_S - (time.monotonic() - _T0)))

    def on_alarm(signum, frame):
        raise TimeoutError(f"{what}: no result within {seconds:.0f}s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


# --------------------------------------------------------------------------
# what runs in workers (cloudpickle ships these by value: imports inside)
# --------------------------------------------------------------------------

def device_facts() -> dict:
    """Called ONCE in a fresh worker process: start JAX, say what it found,
    run the bf16 matmul every chip phase shares (so a later process finds
    it in the compile cache), and say whether the cache was hit."""
    import glob
    import os
    import time

    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp

    cache_events = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: cache_events.append(name))
    devs = jax.devices()
    backend_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    total = float(jax.jit(lambda a: (a @ a).astype(jnp.float32).sum())(x))
    groups = []  # the VFIO groups this process holds open = its chips
    for fd in glob.glob("/proc/self/fd/*"):
        try:
            target = os.readlink(fd)
        except OSError:
            continue
        if target.startswith("/dev/vfio/") and target[10:].isdigit():
            groups.append(int(target[10:]))
    return {
        "pid": os.getpid(),
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "vfio_groups": sorted(set(groups)),
        "jax_platforms": os.environ.get("JAX_PLATFORMS"),
        "matmul_sum": total,
        "backend_s": round(backend_s, 2),
        "matmul_s": round(time.perf_counter() - t1, 2),
        "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "cache_hits": sum(e.endswith("/cache_hits") for e in cache_events),
        "cache_misses": sum(e.endswith("/cache_misses") for e in cache_events),
    }


class ChipHolder:
    def report(self):
        return device_facts()

    def matmul_again(self):
        import jax.numpy as jnp

        x = jnp.ones((1024, 1024), jnp.bfloat16)
        return float((x @ x).astype(jnp.float32).sum())


def chipless_add() -> dict:
    """A worker that was granted no chip and uses jax anyway."""
    import os

    import jax
    import jax.numpy as jnp

    return {"pid": os.getpid(),
            "platform": jax.devices()[0].platform,
            "sum": float((jnp.arange(4.0) + jnp.ones(4)).sum()),
            "jax_platforms": os.environ.get("JAX_PLATFORMS")}


def train_loop(config: dict) -> None:
    """A few steps of GPT-2 on a fixed batch, reported per step; first the
    same loss in f32 with materialized attention, as the reference."""
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.air import session
    from ray_tpu.models import gpt2, transformer
    from ray_tpu.ops.attention import full_attention

    cfg = (gpt2.GPT2Config.gpt2_small() if config["size"] == "small"
           else gpt2.GPT2Config.tiny())
    B, T = config["batch"], cfg.max_seq_len
    # a short warmup, then a rate small enough that six steps on ONE batch
    # descend instead of overshooting (3e-4 bounced: 10.98 .. 10.27 .. 10.69)
    optimizer = gpt2.make_optimizer(lr=1e-4, warmup=2, total_steps=100)
    state = jax.jit(lambda k: gpt2.init_state(cfg, k, optimizer))(
        jax.random.PRNGKey(config["seed"]))
    rng = np.random.default_rng(config["seed"])
    batch = {k: jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T), np.int32))
             for k in ("inputs", "targets")}

    ref_cfg = dataclasses.replace(cfg, dtype=jnp.float32, remat=False)
    dispatcher = transformer.attention
    transformer.attention = (
        lambda q, k, v, causal=False, window=0, scale=None: full_attention(
            q, k, v, causal=causal, scale=scale))
    try:
        with jax.default_matmul_precision("highest"):
            ref_loss = float(jax.jit(
                lambda p, b: gpt2.loss_fn(p, b, ref_cfg))(state["params"], batch))
    finally:
        transformer.attention = dispatcher

    step = jax.jit(gpt2.make_train_step(cfg, optimizer), donate_argnums=(0,))
    losses, step_s = [], []
    for i in range(config["steps"]):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))  # the readback is the sync
        step_s.append(round(time.perf_counter() - t0, 3))
        session.report({"step": i + 1, "loss": losses[-1]})
    dev = jax.devices()[0]
    session.report({
        "done": True, "losses": losses, "ref_loss_f32": ref_loss,
        "first_step_s": step_s[0], "later_step_s": step_s[1:],
        "n_params": gpt2.num_params(state["params"]),
        "shape": {"layers": cfg.n_layers, "d_model": cfg.d_model,
                  "heads": cfg.n_heads, "vocab": cfg.vocab_size, "T": T,
                  "B": B, "dtype": jnp.dtype(cfg.dtype).name,
                  "remat": cfg.remat_policy if cfg.remat else None},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    })


class Reference:
    """Greedy decoding the plain way: the full forward at every step, no
    cache, from the same seed's parameters the replica initialised."""

    def check(self, size: str, seed: int, prompts, outputs) -> dict:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import gpt2
        from ray_tpu.serve.llm import _default_init, make_config

        cfg = make_config("gpt2", size)
        params = _default_init(cfg, seed)
        n_new = len(outputs[0])
        width = -(-(max(map(len, prompts)) + n_new) // 32) * 32
        buf = np.zeros((len(prompts), width), np.int32)
        for r, p in enumerate(prompts):
            buf[r, :len(p)] = p
        forward = jax.jit(lambda p, t: gpt2.apply(p, t, cfg))
        rows = np.arange(len(prompts))
        exact = ties = wrong = 0
        worst = 0.0
        for i in range(n_new):
            # causal: what sits right of a row's last real token is unseen
            logits = np.asarray(forward(params, jnp.asarray(buf)))
            last = np.array([len(p) + i - 1 for p in prompts])
            at = logits[rows, last]                       # [R, V]
            got = np.array([out[i] for out in outputs])
            top = at.max(axis=-1)
            gap = top - at[rows, got]
            # logits leave the model in bf16: candidates closer than two
            # of its steps at the top's magnitude are the same number
            tol = 2.0 * 2.0 ** (np.floor(np.log2(np.maximum(np.abs(top), 1e-3))) - 7)
            exact += int((at.argmax(axis=-1) == got).sum())
            ties += int(((at.argmax(axis=-1) != got) & (gap <= tol)).sum())
            wrong += int((gap > tol).sum())
            worst = max(worst, float(gap.max()))
            buf[rows, last + 1] = got  # continue from what was served
        dev = jax.devices()[0]
        return {"tokens": int(n_new * len(prompts)), "equal": exact,
                "bf16_ties": ties, "wrong": wrong, "worst_gap": round(worst, 5),
                "platform": dev.platform, "kind": dev.device_kind,
                "count": len(jax.devices()), "pid": __import__("os").getpid()}


def sharded_loop(config: dict) -> None:
    """--chips 4: the GPT-2 step over create_mesh({"fsdp": 2, "tp": 2}) of
    this worker's four devices, against the single-device run of the same
    init and batch in this process (``__graft_entry__._dryrun_one``)."""
    import jax

    import __graft_entry__ as graft
    from ray_tpu.air import session
    from ray_tpu.models import gpt2

    cfg = (gpt2.GPT2Config.gpt2_small() if config["size"] == "small"
           else gpt2.GPT2Config.tiny())
    out = graft._dryrun_one(gpt2, cfg, {"fsdp": 2, "tp": 2}, 4,
                            parity_atol=config["atol"], label="chip_smoke 2x2")
    dev = jax.devices()[0]
    out["param_bytes_per_device"] = {
        str(k): v for k, v in out["param_bytes_per_device"].items()}
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    out["done"] = True
    session.report(out)


# --------------------------------------------------------------------------
# phases (driver side; no jax here)
# --------------------------------------------------------------------------

class Smoke:
    def __init__(self, args):
        self.rehearse = args.rehearse
        self.chips = args.chips
        self.seed = args.seed
        self.want_platform = "cpu" if self.rehearse else "tpu"
        self.device = None  # as the worker that held the chip saw it
        if self.rehearse:
            self.size, self.batch, self.steps = "tiny", 4, 4
            self.engine = dict(n_slots=4, max_new_tokens=6, decode_chunk_steps=3,
                               prefill_buckets=(32,), seed=self.seed)
            self.prompt_lens, self.n_requests = (4, 20), 3
            self.wait = 120.0
        else:
            self.size, self.batch, self.steps = "small", 6, 6
            self.engine = dict(n_slots=16, max_new_tokens=32,
                               decode_chunk_steps=64, prefill_buckets=(128,),
                               seed=self.seed)
            self.prompt_lens, self.n_requests = (16, 99), 4
            self.wait = 240.0

    # -- session -------------------------------------------------------
    @contextlib.contextmanager
    def session(self):
        import ray_tpu

        if self.rehearse:
            # fake chips, asked for by name: resource counts and
            # environment variables, no device behind them
            ray_tpu.init(num_cpus=8, num_tpus=self.chips)
        else:
            ray_tpu.init()  # the node must find its own chips
        try:
            found = int(ray_tpu.cluster_resources().get("TPU", 0))
            if found < self.chips:
                raise RuntimeError(
                    f"ray_tpu.init() found {found} TPU chip(s), need {self.chips}")
            yield ray_tpu
        finally:
            ray_tpu.shutdown()

    def check_device(self, facts: dict, count: int) -> None:
        if facts["platform"] != self.want_platform:
            raise RuntimeError(
                f"worker computed on {facts['platform']!r}, "
                f"expected {self.want_platform!r}: {facts}")
        if not self.rehearse and facts["count"] != count:
            raise RuntimeError(f"worker sees {facts['count']} devices, "
                               f"expected {count}: {facts}")

    # -- one chip ------------------------------------------------------
    def phase_core(self) -> dict:
        with self.session() as ray_tpu:
            from ray_tpu._private.worker import global_worker

            store = "native" if global_worker.node.arena is not None else "python"
            if store != "native":
                raise RuntimeError("the native object store did not build; "
                                   "the Python store is live")
            holder = ray_tpu.remote(num_tpus=1)(ChipHolder).remote()
            facts = ray_tpu.get(holder.report.remote(), timeout=self.wait)
            self.check_device(facts, 1)
            if facts["matmul_sum"] != 1024.0 ** 3:
                raise RuntimeError(f"bf16 matmul is wrong: {facts}")
            cpu = ray_tpu.get(
                ray_tpu.remote(num_tpus=0)(chipless_add).remote(),
                timeout=self.wait)
            if cpu["platform"] != "cpu" or cpu["sum"] != 10.0:
                raise RuntimeError(f"chipless worker was not held to the CPU: {cpu}")
            again = ray_tpu.get(holder.matmul_again.remote(), timeout=self.wait)
            if again != 1024.0 ** 3:
                raise RuntimeError("the chipless task disturbed the actor")
            self.device = {k: facts[k] for k in ("platform", "kind", "count")}
            return {"resources": ray_tpu.cluster_resources(), "store": store,
                    "actor": facts, "chipless_task": cpu}

    def phase_handback(self) -> dict:
        with self.session() as ray_tpu:
            task = ray_tpu.get(
                ray_tpu.remote(num_tpus=1)(device_facts).remote(),
                timeout=self.wait)
            self.check_device(task, 1)
            t0 = time.monotonic()
            holder = ray_tpu.remote(num_tpus=1)(ChipHolder).remote()
            facts = ray_tpu.get(holder.report.remote(), timeout=self.wait)
            self.check_device(facts, 1)
            if facts["pid"] == task["pid"]:
                raise RuntimeError("the chip-holding task's worker was reused")
            # the earlier processes compiled this same matmul: with a cache
            # in place a later one reads it instead of compiling
            if task["cache_dir"] and not task["cache_hits"]:
                raise RuntimeError(f"no compile-cache hit in a later process: {task}")
            return {"task": task, "actor_after_task": facts,
                    "actor_ready_s": round(time.monotonic() - t0, 2)}

    def fit(self, loop, config: dict, tpus: int) -> dict:
        from ray_tpu.air import ScalingConfig
        from ray_tpu.train import JaxTrainer

        result = JaxTrainer(
            loop, train_loop_config=config,
            scaling_config=ScalingConfig(
                num_workers=1, resources_per_worker={"CPU": 1, "TPU": tpus}),
        ).fit()
        if result.error is not None:
            raise result.error
        if not (result.metrics or {}).get("done"):
            raise RuntimeError(f"the train loop never finished: {result.metrics}")
        return result.metrics

    def phase_train(self) -> dict:
        with self.session():
            m = self.fit(train_loop, {"size": self.size, "batch": self.batch,
                                      "steps": self.steps, "seed": self.seed}, 1)
        self.check_device(m["device"], 1)
        losses, ref = m["losses"], m["ref_loss_f32"]
        if not all(x == x and abs(x) < 1e4 for x in losses):
            raise RuntimeError(f"non-finite loss: {losses}")
        if not losses[-1] < losses[0]:
            raise RuntimeError(f"loss did not fall: {losses}")
        if abs(losses[0] - ref) > 0.05:
            raise RuntimeError(
                f"first-step loss {losses[0]:.4f} is not the f32 loss {ref:.4f}")
        return {k: m[k] for k in ("shape", "n_params", "losses", "ref_loss_f32",
                                  "first_step_s", "later_step_s", "device")}

    def phase_serve(self) -> dict:
        import random
        import urllib.request

        with self.session() as ray_tpu:
            from ray_tpu import serve
            from ray_tpu.serve.llm import llm_deployment, make_config

            vocab = make_config("gpt2", self.size).vocab_size
            rng = random.Random(self.seed)
            prompts = [[rng.randrange(1, vocab)
                        for _ in range(rng.randint(*self.prompt_lens))]
                       for _ in range(self.n_requests)]
            n_new = self.engine["max_new_tokens"]
            t0 = time.monotonic()
            try:
                serve.run(
                    llm_deployment("gpt2", self.size, num_tpus=1,
                                   engine_kwargs=self.engine).bind(),
                    port=0, timeout_s=self.wait)
                ready_s = time.monotonic() - t0
                host, port = serve.get_http_address()
                answers = [None] * len(prompts)

                def post(i: int) -> None:
                    req = urllib.request.Request(
                        f"http://{host}:{port}/llm",
                        data=json.dumps({"tokens": prompts[i],
                                         "max_new_tokens": n_new}).encode(),
                        headers={"Content-Type": "application/json"})
                    t = time.monotonic()
                    with urllib.request.urlopen(req, timeout=2 * self.wait) as r:
                        answers[i] = (r.status, json.loads(r.read())["tokens"],
                                      round(time.monotonic() - t, 2))

                post(0)  # alone: pays the compiles
                rest = [threading.Thread(target=post, args=(i,), daemon=True)
                        for i in range(1, len(prompts))]
                for t in rest:
                    t.start()
                for t in rest:
                    t.join(2 * self.wait)
            finally:
                serve.shutdown()  # the replica's process ends here
            if any(a is None or a[0] != 200 or len(a[1]) != n_new
                   for a in answers):
                raise RuntimeError(f"bad HTTP answers: {answers}")
            t1 = time.monotonic()
            ref = ray_tpu.get(
                ray_tpu.remote(num_tpus=1)(Reference).remote().check.remote(
                    self.size, self.seed, prompts, [a[1] for a in answers]),
                timeout=2 * self.wait)
            self.check_device(ref, 1)
            if ref["wrong"] or 2 * ref["equal"] < ref["tokens"]:
                raise RuntimeError(f"served tokens are not the uncached argmax: {ref}")
            return {"engine": {**self.engine, "model": f"gpt2-{self.size}"},
                    "replica_ready_s": round(ready_s, 2),
                    "requests": [{"status": a[0], "prompt_len": len(p),
                                  "new_tokens": len(a[1]), "seconds": a[2]}
                                 for p, a in zip(prompts, answers)],
                    "reference": ref,
                    "reference_after_replica_s": round(time.monotonic() - t1, 2)}

    # -- four chips ----------------------------------------------------
    def phase_four_actors(self) -> dict:
        with self.session() as ray_tpu:
            holders = [ray_tpu.remote(num_tpus=1)(ChipHolder).remote()
                       for _ in range(4)]
            facts = ray_tpu.get([h.report.remote() for h in holders],
                                timeout=self.wait)  # all four alive at once
            for f in facts:
                self.check_device(f, 1)
            chips = sorted(f["visible_chips"] for f in facts)
            if chips != ["0", "1", "2", "3"] or len({f["pid"] for f in facts}) != 4:
                raise RuntimeError(f"not four processes on four chips: {facts}")
            groups = [tuple(f["vfio_groups"]) for f in facts]
            if not self.rehearse and (
                    any(len(g) != 1 for g in groups) or len(set(groups)) != 4):
                raise RuntimeError(f"the processes do not hold four distinct "
                                   f"devices: {groups}")
            return {"actors": facts}

    def phase_sharded_step(self) -> dict:
        with self.session():
            m = self.fit(sharded_loop, {"size": self.size, "atol": 0.05}, 4)
        self.check_device(m["device"], 4)
        share = {d: b / m["param_bytes"]
                 for d, b in m["param_bytes_per_device"].items()}
        if len(share) != 4 or not all(0.2 <= s <= 0.3 for s in share.values()):
            raise RuntimeError(f"parameters are not spread over four devices: {share}")
        # what the compiled step moves between chips (kind -> place ->
        # [count, bytes of the largest operand]): under fsdp the layer loop
        # gathers weights, so an all-gather in it says the mechanism engaged
        collectives = {
            kind: {place: [e["count"], e["max_operand_bytes"]]
                   for place, e in places.items() if e["count"]}
            for kind, places in m["collectives"].items()}
        if "in_loop" not in collectives["all-gather"]:
            raise RuntimeError(f"no per-layer weight gather in the fsdp2 x tp2 "
                               f"step: {collectives}")
        self.device = m["device"]
        return {"mesh": m["mesh"], "loss": m["loss"],
                "single_device_loss": m["ref_loss"],
                "param_share_per_device": {d: round(s, 4) for d, s in share.items()},
                "collectives": collectives, "device": m["device"]}

    # -- run -----------------------------------------------------------
    def run(self) -> bool:
        if self.chips == 4:
            phases = [("four_actors", self.phase_four_actors, 300),
                      ("sharded_step", self.phase_sharded_step, 600)]
        else:
            phases = [("core", self.phase_core, 240),
                      ("handback", self.phase_handback, 240),
                      ("train", self.phase_train, 420),
                      ("serve", self.phase_serve, 600)]
        for name, fn, seconds in phases:
            t0 = time.monotonic()
            try:
                with deadline(seconds, name):
                    detail = fn()
            except BaseException as e:  # noqa: BLE001 — report, then fail
                emit({"phase": name, "ok": False,
                      "seconds": round(time.monotonic() - t0, 1),
                      "error": f"{type(e).__name__}: {e}"[:2000]})
                return False
            emit({"phase": name, "ok": True,
                  "seconds": round(time.monotonic() - t0, 1), **detail})
        return True


def stragglers() -> list:
    """Processes this one started that are still alive (there must be none:
    ``ray_tpu.shutdown()`` stops head threads, forkserver and workers)."""
    alive: list = []
    for _ in range(300):  # a killed chip holder takes seconds to be gone
        alive = _live_children()
        if not alive:
            break
        time.sleep(0.1)
    return alive


def _live_children() -> list:
    alive = []
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/children") as f:
                pids = [int(p) for p in f.read().split()]
        except OSError:
            continue
        for pid in pids:
            try:
                with open(f"/proc/{pid}/stat") as f:
                    if f.read().rsplit(")", 1)[1].split()[0] != "Z":
                        alive.append(pid)
            except OSError:
                pass
    return alive


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phases")
    ap.add_argument("--seed", type=int, default=0,
                    help="parameters, batch and prompts are made from it")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, fake chips: tests this script's "
                         "control flow; can never report ok or a TPU")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    verdict = {"ok": False, "device": None}
    try:
        smoke = Smoke(args)
        passed = smoke.run()
        verdict["device"] = smoke.device
        from ray_tpu._private.resource_spec import jax_backend_initialized

        if jax_backend_initialized():
            emit({"phase": "parent", "ok": False,
                  "error": "this process started a JAX backend"})
            passed = False
        left = stragglers()
        if left:
            what = {}
            for pid in left:
                with contextlib.suppress(OSError):
                    with open(f"/proc/{pid}/cmdline") as f:
                        what[pid] = f.read().replace("\0", " ")[:120]
                    os.kill(pid, signal.SIGKILL)
            emit({"phase": "cleanup", "ok": False,
                  "error": f"processes left running (killed): {what}"})
            passed = False
    except BaseException as e:  # noqa: BLE001 — e.g. ray_tpu is not importable
        emit({"phase": "setup", "ok": False,
              "error": f"{type(e).__name__}: {e}"[:2000]})
        passed = False

    if args.rehearse:
        # a rehearsal proves the script, not the chip: "ok" stays false, and
        # the device is a CPU (check_device fails a phase on anything else)
        verdict["rehearsal"] = "passed" if passed else "failed"
    else:
        verdict["ok"] = passed  # every phase computed on a TPU (check_device)
    emit({"total_seconds": round(time.monotonic() - _T0, 1)})
    emit(verdict)
    return 0 if passed else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # nothing may print after the verdict
