"""LLM serving: iteration-level continuous batching over the KV-cache
decode kernels, behind a Serve deployment.

The reference's serving data plane stops at routing a request to a replica
(``python/ray/serve/_private/router.py:221`` -> ``replica.py:250``); token
generation is user code.  On TPU the generation loop IS the workload, so it
is part of the framework here:

- :class:`GenerationEngine` — Orca-style continuous batching: a fixed set
  of cache slots, prompt prefills admitted into free slots, one fused
  ``decode_chunk`` advancing every active slot per iteration.  New requests
  join between chunks; finished slots free mid-stream.  All device
  computations have static shapes (a prompt bucket at its fixed narrow width,
  :data:`CALL_TOKENS`; a chunk of ``decode_chunk_steps``), so everything
  compiles exactly once per bucket, and a burst is several such calls in one
  iteration.  A chunk ends where the nearest live request ends: when one has
  fewer tokens left than the chunk is long, the CUT chunk runs just those
  steps (the same step body under a runtime bound: one further program
  whatever the bound), so no answer waits for steps nobody needs.
- :func:`llm_deployment` — wraps the engine in a Serve deployment on a
  ``num_tpus`` replica; requests block on a future the engine thread
  resolves, so Serve's threaded replica concurrency (not the engine)
  bounds in-flight requests.
"""

from __future__ import annotations

import math
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from functools import partial
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu._private import events as _events
from ray_tpu.util import compile_cache, tracing

# the stages of one served request as phases of the span aggregate
# (``perf_stats()["stages"]``); emitted here, in the worker's task entry and
# in ``serve/_private/replica.py``.  Ingress to the first reply, which the
# seven before it tile ...
FIRST_REPLY_STAGES = (
    "serve.route", "task.dispatch", "serve.submit", "engine.queue",
    "engine.first_token", "engine.stream_yield", "serve.pickup",
    "serve.first_reply")
# ... and from the first token to the last reply, emitted once, when the
# request ends: ``serve.stream`` = ``engine.decode`` + (``engine.last_yield``
# + ``serve.last_pickup``) - (``engine.stream_yield`` + ``serve.pickup``), up
# to the encode-and-put between a yield and its put
DECODE_STAGES = ("engine.decode", "engine.last_yield", "serve.last_pickup",
                 "serve.stream")
STAGES = FIRST_REPLY_STAGES + DECODE_STAGES
# folded only (no span in any tree): a request's ``engine.decode`` over its
# tokens - 1 and its ``serve.stream`` over its chunks - 1, the client's
# per-request pace after the first token taken at two depths inside the
# program; a request of one token has no gap and folds nothing
PER_GAP = ("engine.decode_per_token", "serve.stream_per_chunk")

# Lazy engine metric singletons.
_LLM_METRICS = None

# per-process engine sequence: tick-meter entity ids must stay unique
# when one replica process hosts several engines
import itertools as _itertools  # noqa: E402

_ENGINE_SEQ = _itertools.count()

# Padded tokens one prefill call is wide (rows x bucket; a bucket above it
# runs one row): about the chip's ridge.  A bf16 weight matmul on a v5e is
# compute-bound from ~240 tokens a call, so a wider call is not more
# efficient, only later, and all of it is padding when one prompt arrives;
# a narrower one still costs a whole read of the weights.
CALL_TOKENS = 256


def call_rows(bucket: int, n_slots: int) -> int:
    """Rows a prefill call of ``bucket`` is wide: :data:`CALL_TOKENS`' worth,
    at most the slots, at least one."""
    return max(1, min(n_slots, CALL_TOKENS // bucket))


def _llm_metrics():
    global _LLM_METRICS
    if _LLM_METRICS is None:
        from ray_tpu.util.metrics import Counter, Histogram

        _LLM_METRICS = {
            "admission": Histogram(
                "ray_tpu_llm_slot_admission_latency_s",
                "request submit -> decode-slot admission latency (s)",
                boundaries=[0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 30]),
            "tokens": Counter(
                "ray_tpu_llm_generated_tokens_total",
                "tokens generated by the continuous-batching engine"),
            # decode-tail attribution (the gpt2 p99/p50=1.39x chase):
            # TTFT + inter-token latency per request, and the decode-tick
            # seconds billed to co-scheduled prefill chunks — all
            # TSDB-exported via the push path
            "ttft": Histogram(
                "ray_tpu_llm_ttft_s",
                "request submit -> first generated token (s)",
                boundaries=[0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1, 5, 30]),
            "itl": Histogram(
                "ray_tpu_llm_itl_s",
                "inter-token latency (per-drain mean per request, s)",
                boundaries=[0.0005, 0.001, 0.005, 0.01, 0.025, 0.05,
                            0.1, 0.5, 1]),
            "interference": Counter(
                "ray_tpu_llm_prefill_interference_s_total",
                "seconds of the periods between two chunk landings that "
                "went to prefill calls while other slots were decoding"),
        }
    return _LLM_METRICS


class _TickMeter:
    """The engine's ticks on the clock the device keeps.  A tick's record is
    made when it is DRAINED: between the previous landing (``np.asarray`` of
    a chunk or a prefill call's first tokens returned) and its own the device
    ran exactly that tick's prefill calls and its chunk, as long as the
    look-ahead kept the device queue non-empty; a tick dispatched behind no
    undrained one (the first after an idle engine) has no previous landing
    and its period is left out.  The instant a tick's last prefill call
    landed splits its period into a prefill part and a chunk part; where the
    host looks late the call had landed earlier, so the prefill part is an
    upper bound.  Periods are classed ``decode_only`` / ``interleaved`` (a
    prefill call while other slots were mid-decode) / ``prefill_only``; an
    interleaved period's prefill part is *prefill interference*: what the
    requests that were decoding waited for other requests' prompts.

    Also summed here: the engine thread's own time a tick (``host_s``) and,
    over finished requests, what their decode spans held (``decode``).

    Owned by the engine thread (no locking needed on the hot path);
    ``snapshot()`` reads are torn-tolerant counters."""

    EMIT_EVERY = 32  # interleaved ticks per flight-recorder event

    def __init__(self, entity_id: str):
        self.entity_id = entity_id
        self.ticks = {"decode_only": 0, "interleaved": 0, "prefill_only": 0}
        self.tick_s = {"decode_only": 0.0, "interleaved": 0.0,
                       "prefill_only": 0.0}
        self.interference_s = 0.0
        # seconds and count of EVERY prefill call that landed behind another
        # landing (a left-out tick's second call too): a request's decode
        # span differences them between its first and its last token
        self.prefill_s = 0.0
        self.prefill_calls = 0
        self.host_s = {"admit": 0.0, "dispatch": 0.0, "drain_book": 0.0}
        self.ticks_live = 0
        self.decode = {"requests": 0, "gaps": 0, "chunk_steps_paid": 0,
                       "span_s": 0.0, "prefill_s": 0.0}
        self._landed: Optional[float] = None  # the previous landing
        self._period_s = 0.0    # of the tick being drained, so far
        self._counted = False   # it has a previous landing to start from
        self._since_emit = 0
        # the engine's recurrent-state counters (its own dict, or None): they
        # ride this meter's event to `ray_tpu perf`
        self.state: Optional[Dict[str, int]] = None

    def begin(self, chained: bool) -> None:
        """The drain of one tick begins; ``chained``: it was dispatched
        behind a tick not yet drained, whose landing its period starts at."""
        self._counted = chained and self._landed is not None
        if not self._counted:
            self._landed = None
        self._period_s = 0.0

    def call_landed(self, t: float) -> None:
        """One of the tick's prefill calls landed at ``t``."""
        if self._landed is not None:
            self._period_s += t - self._landed
            self.prefill_s += t - self._landed
            self.prefill_calls += 1
        self._landed = t

    def chunk_landed(self, t: float, n_admitted: int, n_rows: int) -> None:
        """The tick's chunk landed at ``t``: its record is made
        (``n_admitted`` of the ``n_rows`` it decoded were prefilled in it)."""
        prefill_part = self._period_s
        if self._landed is not None:
            self._period_s += t - self._landed
        self._landed = t
        if not self._counted:
            return
        if not n_admitted:
            mode = "decode_only"
        elif n_rows > n_admitted:
            mode = "interleaved"
        else:
            mode = "prefill_only"
        self.ticks[mode] += 1
        self.tick_s[mode] += self._period_s
        if sum(self.ticks.values()) % 256 == 1:
            # periodic HBM watermark from the serving process (the train
            # profiler samples per step; the engine samples per ~256
            # ticks — decode caches are the steady HBM floor)
            from ray_tpu.util import perf as _perf

            _perf.publish_device_memory()
        if mode == "interleaved":
            self.interference_s += prefill_part
            _llm_metrics()["interference"].inc(prefill_part)
            self._since_emit += 1
            if self._since_emit >= self.EMIT_EVERY:
                self.emit_event()

    def tick_host(self, admit: float, dispatch: float,
                  drain_book: float) -> None:
        """What one tick that dispatched or drained anything cost the
        engine thread, apart from blocking in the drain's reads."""
        host = self.host_s
        host["admit"] += admit
        host["dispatch"] += dispatch
        host["drain_book"] += drain_book
        self.ticks_live += 1

    def request_done(self, tokens: int, chunk_steps: int, span_s: float,
                     prefill_s: float) -> None:
        """A request ended: ``span_s`` from its first to its last token on
        the host, the chunks that gave it one ran ``chunk_steps`` steps, and
        ``prefill_s`` of the span the device spent on other requests' prefill
        calls."""
        tally = self.decode
        tally["requests"] += 1
        tally["gaps"] += tokens - 1
        tally["chunk_steps_paid"] += chunk_steps
        tally["span_s"] += span_s
        tally["prefill_s"] += prefill_s

    def snapshot(self) -> Dict[str, Any]:
        decode_wall = self.tick_s["decode_only"] + self.tick_s["interleaved"]
        n_decode = self.ticks["decode_only"]
        baseline = self.tick_s["decode_only"] / n_decode if n_decode else None
        # what the interleaved periods took above as many decode-only ones
        excess = 0.0 if baseline is None else max(
            0.0, self.tick_s["interleaved"]
            - self.ticks["interleaved"] * baseline)
        return {
            "ticks": dict(self.ticks),
            "tick_s": {k: round(v, 6) for k, v in self.tick_s.items()},
            # the mean decode-only period, over whole and cut chunks alike
            # (a cut chunk ran fewer steps: GenerationEngine.step)
            "decode_tick_baseline_s":
                round(baseline, 6) if baseline is not None else None,
            "interference_s": round(self.interference_s, 6),
            "interference_frac":
                round(self.interference_s / decode_wall, 4)
                if decode_wall > 0 else 0.0,
            "tick_excess_s": round(excess, 6),
            # of what the interleaved periods took above the decode-only
            # baseline, the share that was their prefill calls (all of it
            # where they took no longer than their calls).  None (not 0,
            # not 1) until a decode-only baseline exists — an unmeasurable
            # share must not masquerade as a measured one
            "excess_billed_to_prefill":
                round(self.interference_s / excess, 4)
                if excess > self.interference_s
                else 1.0 if baseline is not None and self.interference_s > 0
                else None,
            "host_s": dict(self.host_s),
            "ticks_live": self.ticks_live,
            "decode": dict(self.decode),
        }

    def emit_event(self) -> None:
        self._since_emit = 0
        if not _events.ENABLED or self.ticks["interleaved"] == 0:
            return
        snap = self.snapshot()
        # the engine's own reading of the judged pace, for `ray_tpu perf`
        tpot = tracing.span_stats(("engine.decode_per_token",)).get(
            "engine.decode_per_token") or {}
        _events.emit(
            "perf", "prefill interference", severity="DEBUG",
            entity_id=self.entity_id,
            interference_s=snap["interference_s"],
            interference_frac=snap["interference_frac"],
            excess_billed_to_prefill=snap["excess_billed_to_prefill"],
            interleaved_ticks=self.ticks["interleaved"],
            decode_only_ticks=self.ticks["decode_only"],
            baseline_s=snap["decode_tick_baseline_s"],
            tpot_p50_s=tpot.get("p50_s"), tpot_p95_s=tpot.get("p95_s"),
            **({"state": dict(self.state)} if self.state else {}))


def cache_positions(largest_bucket: int, max_new_tokens: int,
                    chunk_steps: int) -> int:
    """Cache positions an engine gives a slot: the longest prompt, the
    longest answer and one chunk of slack for the flush, rounded UP to whole
    tiles of the decode kernel — a cache of whole tiles is what lets the
    decode step read only a slot's live tiles
    (:func:`ray_tpu.ops.attention.ragged_decode_attention`)."""
    from ray_tpu.ops.attention import DECODE_TILE

    need = largest_bucket + max_new_tokens + chunk_steps
    return -(-need // DECODE_TILE) * DECODE_TILE


class _Request:
    __slots__ = ("tokens", "max_new", "future", "emitted", "scheduled",
                 "submitted_at", "trace_ctx", "dispatched_at",
                 "first_host_t", "last_host_t", "chunks", "chunk_steps",
                 "prefill_mark")

    def __init__(self, tokens: List[int], max_new: int):
        self.tokens = list(tokens)
        self.max_new = int(max_new)
        self.future: Future = Future()
        self.emitted: List[int] = []
        # tokens DISPATCHED for this request (prefill + chunks), maintained
        # at dispatch time — emitted lags one chunk behind in the pipeline,
        # so completion prediction must count scheduled, not emitted
        self.scheduled = 0
        self.submitted_at = time.perf_counter()
        # stage boundaries (perf_counter), set on the engine thread with the
        # observability layer on: prefill dispatched; the first token on the
        # host (TTFT, and where ``engine.decode`` starts); the newest token
        # on the host (inter-token latency; at the end, where it stops)
        self.dispatched_at: Optional[float] = None
        self.first_host_t: Optional[float] = None
        self.last_host_t: Optional[float] = None
        # decode chunks that gave it a token and the steps they ran, and the
        # tick meter's prefill seconds and calls when its first token landed
        self.chunks = 0
        self.chunk_steps = 0
        self.prefill_mark = (0.0, 0)
        # the submitter's trace context (a traced serve replica): the
        # engine loop runs on its own thread, so the context is captured
        # HERE and the engine's spans are tagged with it.  A caller that
        # brought none (a plain DeploymentHandle, bench.py) gets a root of
        # the engine's own, so engine.* stages never depend on the ingress
        self.trace_ctx = None
        if _events.ENABLED:
            self.trace_ctx = (tracing.child_context("llm.generate")
                              or tracing.root_context("llm.generate"))


class _PendingChunk:
    """One dispatched-but-not-drained engine iteration: the device arrays
    (tokens already streaming host-ward via ``copy_to_host_async``) plus
    the host bookkeeping needed to route them when they land."""

    __slots__ = ("chunk_dev", "steps", "rows", "prefills", "routed_dev",
                 "chained")

    def __init__(self, chunk_dev, steps, rows, prefills, routed_dev, chained):
        self.chunk_dev = chunk_dev          # [n_slots+1, chunk] device
        self.steps = steps                  # the steps it ran: chunk, or its cut
        self.rows = rows                    # [(slot, _Request)] active in chunk
        # this iteration's prefill calls, in dispatch order: (admissions
        # [(row_j, slot, _Request)], first tokens [rows] device, the call's
        # routing counts: device, None where the family counts nothing)
        self.prefills = prefills
        # the chunk's routing counts (device or None): they land with the tokens
        self.routed_dev = routed_dev
        # for the tick meter: the tick before this one was still undrained
        # at dispatch (the device then runs this one straight after it)
        self.chained = chained


class GenerationEngine:
    """Continuous-batching decode engine over :mod:`ray_tpu.models.generate`.

    One background thread owns the device state (cache, last tokens); the
    public :meth:`submit` is thread-safe and returns a Future of the
    generated token list.
    """

    def __init__(
        self,
        cfg,
        params=None,
        *,
        n_slots: int = 4,
        max_new_tokens: int = 128,
        decode_chunk_steps: int = 16,
        prefill_buckets: tuple = (32, 64, 128, 256),
        prefill_token_budget: Optional[int] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        eos_id: Optional[int] = None,
        seed: int = 0,
    ):
        import jax
        from ray_tpu.models import generate as gen

        # before the first program is built: every executable this process
        # compiles or loads from now on is counted (perf_stats()["compiles"])
        compile_cache.listen()
        # host phases of the engine thread on the profiler's clock: they
        # land in the same trace as the device's ops, so an idle gap there
        # can be named by what this thread was doing (free when no trace
        # is being taken)
        self._annotate = jax.profiler.TraceAnnotation
        self._gen = gen
        self.cfg = cfg
        if params is None:
            params = _default_init(cfg, seed)
        # inference-only params: pre-cast master f32 weights to the compute
        # dtype ONCE — the per-step .astype inside the blocks otherwise
        # re-reads the f32 copy every decode step (2x the HBM traffic of
        # the weights, which is the whole cost of a decode step).  A family
        # whose init makes its leaves in cfg.dtype, a layer at a time, never
        # has masters (exaone_moe: they would not fit the chip)
        import jax.numpy as jnp

        self.params = jax.tree.map(
            lambda x: x.astype(cfg.dtype)
            if hasattr(x, "dtype") and x.dtype == jnp.float32 else x,
            params)
        self.n_slots = n_slots
        self.max_new_tokens = max_new_tokens
        self.chunk = decode_chunk_steps
        self.buckets = tuple(sorted(prefill_buckets))
        # rows a prefill of each bucket is wide.  One compiled program a
        # bucket, and a narrow one: a lone prompt is not padded to every
        # slot's rows, and a burst becomes several calls in one tick (_admit)
        self._rows = {b: call_rows(b, n_slots) for b in self.buckets}
        # the most padded prefill tokens between two decode chunks: what
        # every live request's next gap may wait for.  Every slot at the
        # largest bucket unless set (an engine whose largest prompts are
        # thousands of tokens sets one); a tick's first call goes whatever
        # it is wide, so no budget can starve the queue
        self._tick_tokens = prefill_token_budget or n_slots * self.buckets[-1]
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id

        self._max_len = cache_positions(
            self.buckets[-1], max_new_tokens, decode_chunk_steps)
        # cumulative: the cache tiles the dispatched decode chunks read a
        # layer a step and the tiles their flushes wrote a layer a tensor,
        # and the tiles of the padded slab they did not have to
        from ray_tpu.ops.attention import DECODE_TILE

        self._tile = DECODE_TILE
        windows = gen.layer_windows(cfg)
        n_state = windows.count(gen.RECURRENT)
        self._layers = {"full": windows.count(0),
                        "window": len(windows) - windows.count(0) - n_state,
                        **({"state": n_state} if n_state else {})}
        # per layer of the kind: a full layer reads a slot's live tiles, a
        # window layer every row's whole ring (two tiles at the published
        # window), whatever the context
        self._ring_tiles = -(-gen.ring_positions(max(windows)) // DECODE_TILE)
        self._cache_tiles = {"read_full": 0, "read_window": 0, "padded": 0,
                             "flushed": 0}
        # cumulative, by prefill bucket: calls, the rows and tokens they were
        # wide, and the prompts and prompt tokens among those
        self._prefill = {b: {"calls": 0, "rows": 0, "padded_tokens": 0,
                             "prompts": 0, "live_tokens": 0}
                         for b in self.buckets}
        # cumulative routing counts of the dispatches drained so far, where
        # the family's layers count (leaves as the programs return them:
        # stacked over the layers that route); None until a first arrives
        self._routed = {"prefill": None, "decode": None, "decode_steps": 0,
                        "decode_dispatches": 0, "prefill_dispatches": 0}
        # one extra SCRATCH slot (index n_slots): a prefill call is its
        # bucket's fixed rows wide, and the rows no prompt fills park there
        self.cache = gen.init_cache(cfg, n_slots + 1, self._max_len)
        # bytes of one tile of a layer of each kind, from the cache's own
        # shapes (k and v per KV head, or one latent row a position, with its
        # index key where the layer selects), so that no reader of the
        # counters guesses a row's width
        row_bytes = lambda *names: sum(  # noqa: E731
            math.prod(self.cache[n].shape[2:4]) * self.cache[n].dtype.itemsize
            for n in names if n in self.cache)
        self._tile_bytes = {
            "full": DECODE_TILE * row_bytes(*gen.cached_tensors(cfg)),
            "window": DECODE_TILE * row_bytes(*gen.cached_tensors(cfg, True))}
        # a family with recurrent layers: cumulative, over the steps the
        # drained chunks really ran (host arithmetic, no device read): the
        # rows whose state a step HAD to move (``rows_live``: the chunk's
        # rows) and those the program's update touched (``rows_updated``: the
        # same where the decode program was lowered with the kernel that
        # walks them, every row of the cache where it runs the masked form);
        # the layers with a state, and the bytes a row holds a layer (state
        # and last inputs), from the cache's own shapes.  None: no such layer
        self._state: Optional[Dict[str, int]] = None
        if n_state:
            from ray_tpu.ops import ssm

            held, tails = self.cache["ssm"], self.cache["conv"]
            self._state_kernel = (jax.devices()[0].platform == "tpu"
                                  and ssm.kernel_shapes(held))
            self._state = {
                "rows_updated": 0, "rows_live": 0, "steps": 0, "dispatches": 0,
                "layers": n_state,
                "row_bytes": (held.nbytes // math.prod(held.shape[:2])
                              + tails.nbytes // (n_state * tails.shape[2]))}
        self._key = jax.random.PRNGKey(seed)

        self._prefill_jit, self._decode_jit, decode_cut_jit = engine_programs(
            cfg, decode_chunk_steps=decode_chunk_steps,
            temperature=temperature, top_k=top_k, eos_id=eos_id)
        # a named function, not a lambda: a device trace lists each program
        # under its function's name (jit_llm_first_tokens).  One program a
        # prefill width: it draws the call's key, samples the first tokens
        # and folds them into the device-resident last-token row, so neither
        # needs a host round trip
        def llm_first_tokens(logits, key, last, slots):
            key, sub = jax.random.split(key)
            firsts = gen.sample_logits(
                logits, sub, temperature=temperature, top_k=top_k)
            return firsts, last.at[slots].set(firsts), key

        self._first_jit = jax.jit(llm_first_tokens)

        self._slots: List[Optional[_Request]] = [None] * n_slots
        # device-resident last token per slot: decode chunk N+1 chains off
        # chunk N's output ON DEVICE, so dispatching N+1 never waits for
        # N's tokens to reach the host
        self._last_tok_dev = jnp.zeros((n_slots + 1,), jnp.int32)
        # the cut chunk is built AHEAD, for the shapes it will be called
        # with, on a thread of its own: its load is seconds for a family
        # whose layers are unrolled (PERF.md section 6, PR 40), and here it
        # runs beside the replica's start-up and the loading of the first
        # prefill and of the whole chunk, where the first cut would else
        # wait for all of it.  The future holds the compiled program (or
        # what building it raised)
        shapes = lambda tree: jax.tree.map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        cut_args = shapes((self.params, self.cache, self._last_tok_dev,
                           jnp.zeros((n_slots + 1,), bool), self._key,
                           jnp.int32(0)))
        pool = ThreadPoolExecutor(1, thread_name_prefix="llm-decode-cut")
        self._decode_cut: Future = pool.submit(
            lambda: decode_cut_jit.lower(*cut_args).compile())
        pool.shutdown(wait=False)  # the thread ends with the build
        self._pending: Optional[_PendingChunk] = None
        self._draining: Optional[_PendingChunk] = None  # mid-_drain record
        self._queue: List[_Request] = []
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # serving metrics (Serve data-plane observability)
        self.total_generated = 0
        self.total_requests = 0
        # decode-tail attribution: per-tick interference meter + small
        # sample reservoirs backing perf_stats() percentiles (the
        # histograms export to the TSDB; the reservoirs answer locally)
        import os as _os
        from collections import deque as _deque

        # pid + per-process seq: cross-NODE uniqueness comes from the
        # event origin (downstream keys on it), the seq covers two
        # engines inside one replica process
        self._ticks = _TickMeter(
            f"engine-{_os.getpid()}-{next(_ENGINE_SEQ)}")
        self._ticks.state = self._state
        self._ttft_samples: "_deque[float]" = _deque(maxlen=4096)
        self._itl_samples: "_deque[float]" = _deque(maxlen=4096)

    # -- public API ----------------------------------------------------
    def _submit_req(self, tokens: List[int], max_new: Optional[int]) -> _Request:
        """Validate + enqueue (shared by submit and stream)."""
        if not tokens:
            raise ValueError("empty prompt")
        if len(tokens) > self.buckets[-1]:
            raise ValueError(
                f"prompt length {len(tokens)} exceeds the largest prefill "
                f"bucket {self.buckets[-1]}")
        req = _Request(tokens, min(max_new or self.max_new_tokens,
                                   self.max_new_tokens))
        ctx = tracing.current_context()
        if ctx is not None and "t_exec" in ctx:
            # from where the worker's task.dispatch ended to the request
            # existing: argument decoding, handle_request, and for a
            # streamed request the stream thread's start
            tracing.emit_stage("serve.submit", tracing.since(ctx["t_exec"]),
                               ctx)
        with self._lock:
            self._queue.append(req)
            self.total_requests += 1
        self._work.set()
        return req

    def submit(self, tokens: List[int], max_new: Optional[int] = None) -> Future:
        return self._submit_req(tokens, max_new).future

    def generate(self, tokens: List[int], max_new: Optional[int] = None,
                 timeout: float = 300.0) -> List[int]:
        return self.submit(tokens, max_new).result(timeout)

    def stream(self, tokens: List[int], max_new: Optional[int] = None,
               timeout: float = 300.0):
        """Yield token ids AS THE ENGINE EMITS THEM (token streaming for
        serve's chunked responses).  Raises the request's error, if any."""
        req = self._submit_req(tokens, max_new)
        n = 0
        yielded_t = None  # when the newest token of a poll was handed over
        deadline = time.perf_counter() + timeout
        while True:
            # read BEFORE the snapshot: the engine resolves the future
            # after its last append, so a done request's tokens are all in
            done = req.future.done()
            emitted = req.emitted  # list append is atomic; len-snapshot safe
            m = len(emitted)
            while n < m:
                if n == 0 and req.first_host_t is not None:
                    # what this loop's poll adds to the first token
                    tracing.emit_stage(
                        "engine.stream_yield",
                        time.perf_counter() - req.first_host_t,
                        req.trace_ctx)
                if n == m - 1 and req.first_host_t is not None:
                    yielded_t = time.perf_counter()  # one read a poll
                yield emitted[n]
                n += 1
            if done:
                if yielded_t is not None:
                    # ... and what it added to the last: emitted here, where
                    # the request's stamps are final, whichever poll saw the
                    # last token (the engine resolves the future a little
                    # after appending it)
                    tracing.emit_stage(
                        "engine.last_yield", yielded_t - req.last_host_t,
                        req.trace_ctx,
                        ts=time.time() - (time.perf_counter() - yielded_t))
                req.future.result()  # surface engine errors
                return
            if time.perf_counter() > deadline:
                raise TimeoutError("token stream timed out")
            time.sleep(0.02)

    def start(self) -> "GenerationEngine":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="generation-engine")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        self._decode_cut.exception()  # a build still running ends first
        # flush the final interference numbers so a short engine run
        # still leaves the doctor/`ray_tpu perf` its last meter state
        self._ticks.emit_event()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            # cap-prediction frees slots at dispatch, so in-flight work
            # also lives in the undrained pipeline records — count unique
            # unresolved requests across both views
            inflight = {id(s): s for s in self._slots if s is not None}
            for rec in (self._pending, self._draining):
                if rec is not None:
                    inflight.update(
                        (id(r), r) for _, r in rec.rows
                        if not r.future.done())
            return {
                "active_slots": sum(s is not None for s in self._slots),
                "inflight_requests": len(inflight),
                "queued": len(self._queue),
                "total_requests": self.total_requests,
                "total_generated_tokens": self.total_generated,
            }

    @staticmethod
    def _pctiles(samples) -> Dict[str, Any]:
        vals = sorted(samples)
        n = len(vals)
        if not n:
            return {"count": 0, "p50_s": None, "p99_s": None,
                    "mean_s": None}
        return {
            "count": n,
            "p50_s": round(vals[n // 2], 6),
            "p99_s": round(vals[min(n - 1, int(n * 0.99))], 6),
            "mean_s": round(sum(vals) / n, 6),
        }

    def perf_stats(self) -> Dict[str, Any]:
        """Decode-tail attribution: TTFT/ITL percentiles over the recent
        sample reservoirs (both stamped where the tokens landed on the
        host) plus the tick meter (:class:`_TickMeter`: the periods
        between landings by class, the engine thread's time a tick, what
        the finished requests' decode spans held) — the numbers that
        explain (rather than just report) the decode tail."""
        import jax

        with self._lock:  # snapshot: the engine thread appends under
            # the same lock, so sorted() never sees a mutating deque
            ttft = list(self._ttft_samples)
            itl = list(self._itl_samples)
            cache_tiles = {**self._cache_tiles, "layers": dict(self._layers),
                           "tile_bytes": dict(self._tile_bytes)}
            prefill = {str(b): dict(v) for b, v in self._prefill.items()}
            moe = jax.tree.map(lambda a: np.asarray(a).tolist(), self._routed)
            state = dict(self._state) if self._state else None
            dsa = self._selection_stats()
        stages: Dict[str, Any] = {}
        if _events.ENABLED:
            stages = tracing.span_stats(STAGES + PER_GAP)
            stages["clock_skew"] = tracing.clock_skew()
        return {
            "ttft": self._pctiles(ttft),
            "itl": self._pctiles(itl),
            **self._ticks.snapshot(),
            # the request's stages from ingress to the last reply, as this
            # process closed them (cumulative: difference two calls for a
            # window, and take that many off the end of ``recent`` for the
            # window's own durations); empty with the observability layer off
            "stages": stages,
            # how much of the padded cache the decode steps read, in tiles a
            # layer of each kind (cumulative; counted at dispatch from prompt
            # lengths and scheduled tokens), and how much of it a chunk's
            # flush writes (``flushed``: tiles a full layer a tensor, against
            # ``padded``); how many layers of each kind (a latent layer is a
            # full one) and the bytes of one tile of each
            "cache_tiles": cache_tiles,
            # what the prefill calls were wide and what of it was prompt
            # (cumulative, by bucket)
            "prefill": prefill,
            # a family with expert layers: what its layers counted of the
            # routing in the dispatches drained so far, prefills and decode
            # chunks apart (``tokens [layer][held expert]``, ``touched
            # [layer]``: held experts with any token, summed over calls and
            # steps; ``decode_steps``: the chunk steps among them, in
            # ``decode_dispatches`` chunks, whole or cut).
            # Cumulative, like cache_tiles
            "moe": moe,
            # a family with recurrent layers: the rows of state the decode
            # steps moved against those they had to (``__init__``)
            **({"state": state} if state else {}),
            # a family whose full layers select the positions a query reads
            # (ray_tpu.ops.dsa): prefills and decode chunks apart, summed over
            # the full layers, the cached rows the selection had to score
            # (every position of a row's context), the rows it chose (``min(
            # context, top-k)``) and the latent rows the attention READ for
            # them (``rows_read / rows_selected`` is 1 for a read that
            # gathers what was chosen); the decode steps and the dispatches
            # they came in.  Counted on the device, beside the routing counts
            # (the same numbers as ``moe[phase]["dsa_*"]``).  Cumulative
            **({"dsa": dsa} if dsa else {}),
            "compiles": compile_cache.counts(),
            "device": _device_facts(),
        }

    # -- engine loop ---------------------------------------------------
    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                worked = self.step()
            except Exception as e:  # noqa: BLE001 — a kernel error (OOM,
                # bad request shape) must fail the affected requests, not
                # silently kill the engine thread and wedge the replica
                import jax.numpy as jnp

                with self._lock:
                    victims = [s for s in self._slots if s is not None]
                    victims += self._queue
                    # BOTH in-flight pipeline records: a drain failure must
                    # also fail cap-freed requests that live only in the
                    # record being drained (they are in neither _slots nor
                    # the newly dispatched _pending)
                    for rec in (self._pending, self._draining):
                        if rec is not None:
                            victims += [r for _, r in rec.rows]
                    self._slots = [None] * self.n_slots
                    self._queue.clear()
                    self._pending = None
                    self._draining = None
                for req in dict.fromkeys(victims):
                    if not req.future.done():
                        req.future.set_exception(e)
                # the donated cache lineage may be poisoned mid-pipeline;
                # restart from a fresh one so the engine survives
                self.cache = self._gen.init_cache(
                    self.cfg, self.n_slots + 1, self._max_len)
                self._last_tok_dev = jnp.zeros((self.n_slots + 1,), jnp.int32)
                worked = False
            if not worked:
                # ONE annotation an idle period, so that an idle gap of any
                # length in a device trace is covered by one host event;
                # ``submit`` and ``stop`` both set the event
                with self._annotate("engine.wait_work"):
                    self._work.wait()
                self._work.clear()

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _admit(self):
        """Prefill queued prompts into free slots: the queue's head, strictly
        in order, formed into calls greedily.  Consecutive requests of one
        bucket share a call up to the rows that bucket is wide (``_rows``);
        a request of another bucket, or a full call, starts the next, so a
        prompt is padded only to ITS bucket.  Admission stops when the free
        slots, the queue or the tick's budget of padded tokens
        (``_tick_tokens``; a tick's first call always goes) are used up.
        Every call is dispatched here, none is read back: returns the calls
        as ``_PendingChunk.prefills`` holds them, their first tokens ON
        DEVICE (merged into the last-token row there); the values, and the
        calls' routing counts, reach the host with the next chunk drain."""
        with self._lock:
            free = [i for i, s in enumerate(self._slots) if s is None]
            calls: List[tuple] = []  # (bucket, [(slot, _Request)])
            spent = 0
            for slot in free:
                if not self._queue:
                    break
                b = self._bucket(len(self._queue[0].tokens))
                if not (calls and calls[-1][0] == b
                        and len(calls[-1][1]) < self._rows[b]):
                    spent += self._rows[b] * b
                    if calls and spent > self._tick_tokens:
                        break
                    calls.append((b, []))
                req = self._queue.pop(0)
                self._slots[slot] = req
                calls[-1][1].append((slot, req))
            for b, batch in calls:
                tally = self._prefill[b]
                tally["calls"] += 1
                tally["rows"] += self._rows[b]
                tally["padded_tokens"] += self._rows[b] * b
                tally["prompts"] += len(batch)
                tally["live_tokens"] += sum(len(r.tokens) for _, r in batch)
        return [self._prefill_call(b, batch) for b, batch in calls]

    def _prefill_call(self, b: int, batch):
        """Dispatch one prefill call of bucket ``b`` for ``batch`` [(slot,
        _Request)]: the bucket's fixed rows wide (ONE compiled program a
        bucket: a width that varies recompiles mid-serving), the rows no
        prompt fills a 1-token dummy aimed at the scratch slot."""
        import jax.numpy as jnp

        n = self._rows[b]
        toks = np.zeros((n, b), np.int32)
        toks[:, 0] = 1  # padding rows: 1-token dummy prompt
        lens = np.ones((n,), np.int32)
        slots = np.full((n,), self.n_slots, np.int32)  # scratch slot
        for j, (slot, req) in enumerate(batch):
            toks[j, :len(req.tokens)] = req.tokens
            lens[j] = len(req.tokens)
            slots[j] = slot
        if _events.ENABLED:
            t_dispatch = time.perf_counter()
            hist = _llm_metrics()["admission"]
            for _, req in batch:
                req.dispatched_at = t_dispatch
                waited = t_dispatch - req.submitted_at
                hist.observe(waited)
                tracing.emit_stage("engine.queue", waited, req.trace_ctx)
        slots_dev = jnp.asarray(slots)
        last_logits, self.cache, routed_dev = self._prefill_jit(
            self.params, jnp.asarray(toks), jnp.asarray(lens),
            self.cache, slots_dev)
        firsts_dev, self._last_tok_dev, self._key = self._first_jit(
            last_logits, self._key, self._last_tok_dev, slots_dev)
        firsts_dev.copy_to_host_async()
        _to_host_async(routed_dev)
        for _, req in batch:
            req.scheduled = 1  # the prefill's sampled first token
        admissions = [(j, slot, req) for j, (slot, req) in enumerate(batch)]
        return admissions, firsts_dev, routed_dev

    def step(self) -> bool:
        """One engine iteration, software-pipelined against the device:

        1. admit queued prompts into free slots (as many narrow prefill
           calls as the queue's head, the free slots and the tick's token
           budget allow; no readback)
        2. dispatch decode chunk N (chains off device-side last tokens): as
           long as every live request has tokens left for it, else CUT to the
           fewest any has left (``max_new - scheduled``, which the host knows
           without a device read), so that the request that ends in it gets
           its last token at the step that made it, and every other row its
           tokens that much sooner; the next chunk is whole again
        3. free slots whose request deterministically finishes in chunk N
           (cap-based — the HOST knows completion timing without seeing
           token values), so the next iteration's admission reuses them
           with zero idle chunks
        4. drain chunk N-1 (its ``copy_to_host_async`` transfer has been
           streaming since last iteration), resolve finished futures

        The drain of N-1 thus overlaps chunk N's device compute.  With the
        observability layer on, the tick meter gets what this iteration
        cost the engine thread (admit, dispatch, the drain's bookkeeping:
        not the time blocked in its reads) and, from the drain, the record of
        tick N-1: the period between two landings is that tick's device
        time (:class:`_TickMeter`)."""
        import jax.numpy as jnp

        meter = self._ticks if _events.ENABLED else None
        t_tick0 = time.perf_counter() if meter else 0.0
        with self._annotate("engine.admit"):
            prefills = self._admit()
        t_admitted = time.perf_counter() if meter else 0.0
        with self._lock:
            rows = [(i, s) for i, s in enumerate(self._slots) if s is not None]
        dispatched = None
        if rows:
            with self._annotate("engine.decode_dispatch"):
                active = np.zeros((self.n_slots + 1,), bool)  # scratch inactive
                active[[i for i, _ in rows]] = True
                # the steps this chunk runs: up to where the nearest live
                # request ends (one with nothing left, its answer being its
                # prefill's token, is freed below and counts for nothing)
                left = [req.max_new - req.scheduled for _, req in rows]
                n = min([self.chunk] + [m for m in left if m > 0])
                args = (self.params, self.cache, self._last_tok_dev,
                        jnp.asarray(active), self._key)
                (chunk_dev, self.cache, self._last_tok_dev, self._key,
                 chunk_routed) = (
                    self._decode_jit(*args) if n == self.chunk
                    else self._decode_cut.result()(*args, np.int32(n)))
                chunk_dev.copy_to_host_async()
                _to_host_async(chunk_routed)
            dispatched = _PendingChunk(
                chunk_dev, n, rows, prefills, chunk_routed,
                chained=self._pending is not None)
            # cap-based predicted completion: these slots are free for the
            # NEXT admission even though their token values haven't landed
            # (completion timing is deterministic; EOS only finishes a
            # request EARLIER, confirmed at drain)
            with self._lock:
                # a slot stands at prompt + scheduled - 1 (the last sampled
                # token is not in the cache yet); EOS, which only the
                # device has seen, can only leave it lower
                stands = [len(req.tokens) + req.scheduled - 1 for _, req in rows]
                self._cache_tiles["read_full"] += sum(
                    -(-at // self._tile) for at in stands)
                # the chunk's flush writes, a full layer a tensor, the tile
                # a dispatched row's columns fall in, and the next where
                # the ``n`` it ran cross into it; a row that sits the chunk
                # out, none
                self._cache_tiles["flushed"] += sum(
                    1 + (at % self._tile > self._tile - n) for at in stands)
                if self._layers["window"]:
                    self._cache_tiles["read_window"] += (
                        (self.n_slots + 1) * self._ring_tiles)
                self._cache_tiles["padded"] += (
                    (self.n_slots + 1) * self._max_len // self._tile)
                for i, req in rows:
                    req.scheduled = min(req.max_new, req.scheduled + n)
                    if req.scheduled >= req.max_new:
                        self._slots[i] = None
        t_dispatched = time.perf_counter() if meter else 0.0
        prev, self._pending = self._pending, dispatched
        waited = 0.0
        if prev is not None:
            self._draining = prev  # visible to _loop's error recovery
            with self._annotate("engine.drain"):
                waited = self._drain(prev, meter)
            self._draining = None
        worked = dispatched is not None or prev is not None
        if meter is not None and worked:
            meter.tick_host(
                t_admitted - t_tick0, t_dispatched - t_admitted,
                time.perf_counter() - t_dispatched - waited)
        return worked

    def _count_routed(self, phase: str, counts, steps: int = 0) -> None:
        """Add one landed dispatch's routing counts (a prefill call's, or a
        chunk's with the ``steps`` it ran; None where the family counts
        nothing) to the totals."""
        if counts is None:
            return
        counts = {k: np.asarray(v, np.int64) for k, v in counts.items()}
        with self._lock:
            had = self._routed[phase]
            self._routed[phase] = counts if had is None else {
                k: had[k] + v for k, v in counts.items()}
            self._routed["decode_steps"] += steps
            self._routed[phase + "_dispatches"] += 1

    def _selection_stats(self) -> Optional[Dict[str, Any]]:
        """``perf_stats()["dsa"]`` from the landed dispatches' counts (call
        under the lock); None for a family that selects nothing."""
        if not self._gen.index_cache(self.cfg):
            return None
        out = {}
        for phase in ("prefill", "decode"):
            had = self._routed[phase] or {}
            out[phase] = {
                **{"rows_" + k: int(np.sum(had.get("dsa_" + k, 0)))
                   for k in ("scored", "selected", "read")},
                "dispatches": self._routed[phase + "_dispatches"]}
        out["decode"]["steps"] = self._routed["decode_steps"]
        return out

    def _read_back(self, dev) -> tuple:
        """``np.asarray(dev)`` (blocks until the device has produced it), the
        instant it returned, and the seconds it blocked; the wait carries
        its own annotation, so that in a device trace a gap under
        ``engine.drain`` and not under it is the drain's bookkeeping."""
        t = time.perf_counter()
        with self._annotate("engine.drain_wait"):
            host = np.asarray(dev)
        landed = time.perf_counter()
        return host, landed, landed - t

    def _drain(self, pending: _PendingChunk,
               meter: Optional[_TickMeter] = None) -> float:
        """Materialize one landed chunk: route first tokens + chunk rows to
        their requests, resolve futures, confirm EOS slot frees.  With a
        ``meter`` (the observability layer is on) every stamp is the instant
        the tokens it times were on the host, and the tick's record is made;
        returns the seconds blocked in the reads."""
        waited = 0.0
        if meter is not None:
            meter.begin(pending.chained)
        for admissions, firsts_dev, routed_dev in pending.prefills:
            firsts, landed, blocked = self._read_back(firsts_dev)
            waited += blocked
            if meter is not None:
                meter.call_landed(landed)
            for j, slot, req in admissions:
                if meter is not None:
                    req.first_host_t = req.last_host_t = landed
                    req.prefill_mark = (meter.prefill_s, meter.prefill_calls)
                    ttft = landed - req.submitted_at
                    _llm_metrics()["ttft"].observe(ttft)
                    with self._lock:  # perf_stats sorts a snapshot;
                        # an unlocked concurrent append would raise
                        # "deque mutated during iteration" there
                        self._ttft_samples.append(ttft)
                    if req.dispatched_at is not None:
                        tracing.emit_stage(
                            "engine.first_token",
                            landed - req.dispatched_at, req.trace_ctx)
                # after the stamps: the stream thread reads them the
                # moment it sees the token
                req.emitted.append(int(firsts[j]))
            self._count_routed("prefill", routed_dev)
        # the transfer is already in flight
        chunk, landed, blocked = self._read_back(pending.chunk_dev)
        waited += blocked
        if meter is not None:
            meter.chunk_landed(
                landed, sum(len(adm) for adm, _, _ in pending.prefills),
                len(pending.rows))
        self._count_routed("decode", pending.routed_dev, pending.steps)
        if self._state is not None:
            touched = (len(pending.rows) if self._state_kernel
                       else self.n_slots + 1)
            with self._lock:
                self._state["steps"] += pending.steps
                self._state["dispatches"] += 1
                self._state["rows_live"] += len(pending.rows) * pending.steps
                self._state["rows_updated"] += touched * pending.steps
        for i, req in pending.rows:
            if req.future.done():
                continue
            n_before = len(req.emitted)
            for t in chunk[i][:pending.steps]:
                # check BEFORE append: the prefill's first token may already
                # have satisfied max_new (or been EOS) for this request
                if len(req.emitted) >= req.max_new or (
                        self.eos_id is not None and req.emitted
                        and req.emitted[-1] == self.eos_id):
                    break
                req.emitted.append(int(t))
            k = len(req.emitted) - n_before
            if k > 0 and req.last_host_t is not None:
                # per-drain mean gap: chunked decode lands tokens in
                # bursts, so the steady-state per-token rate is the burst
                # interval (landing to landing) over the burst size
                itl = (landed - req.last_host_t) / k
                _llm_metrics()["itl"].observe(itl)
                with self._lock:
                    self._itl_samples.append(itl)
                req.last_host_t = landed
                req.chunks += 1
                req.chunk_steps += pending.steps
            done = len(req.emitted) >= req.max_new or (
                self.eos_id is not None and req.emitted
                and req.emitted[-1] == self.eos_id)
            if done:
                with self._lock:
                    if self._slots[i] is req:  # EOS finish: slot not yet
                        self._slots[i] = None  # freed by cap prediction
                self.total_generated += len(req.emitted)
                if _events.ENABLED:
                    self._emit_done(i, req, meter)
                req.future.set_result(req.emitted)
        return waited

    def _emit_done(self, slot: int, req: _Request,
                   meter: Optional[_TickMeter]) -> None:
        """A request's once-only emissions when it ends: the ``request
        complete`` event (the whole submit->completion window as a span of
        the submitter's trace) and, under it, ``engine.decode``: first token
        on the host -> last token on the host."""
        n = len(req.emitted)
        _llm_metrics()["tokens"].inc(n)
        now = time.perf_counter()
        if meter is not None and req.first_host_t is not None:
            span = req.last_host_t - req.first_host_t
            prefill_s = meter.prefill_s - req.prefill_mark[0]
            meter.request_done(n, req.chunk_steps, span, prefill_s)
            tracing.emit_stage(
                "engine.decode", span, req.trace_ctx,
                ts=time.time() - (now - req.last_host_t), tokens=n,
                chunks=req.chunks, chunk_steps=req.chunk_steps,
                prefill_calls=meter.prefill_calls - req.prefill_mark[1],
                prefill_s=round(prefill_s, 6))
            if n > 1:
                tracing.fold("engine.decode_per_token", span / (n - 1))
        tf = {}
        if req.trace_ctx is not None:
            tf = {"trace_id": req.trace_ctx["trace_id"],
                  "span_id": req.trace_ctx["span_id"],
                  "parent_span_id": req.trace_ctx["parent_span_id"],
                  "phase": "llm_generate"}
        _events.emit(
            "serve_llm", "request complete", severity="DEBUG",
            entity_id=str(slot), tokens=n,
            span_dur=now - req.submitted_at, **tf)


def engine_programs(cfg, *, decode_chunk_steps: int, temperature: float = 0.0,
                    top_k: int = 0, eos_id: Optional[int] = None):
    """The engine's three device programs, ``(prefill, decode_chunk, the cut
    decode chunk)``: one prefill per prompt bucket (compiled lazily), the
    whole chunk of ``decode_chunk_steps``, and the same steps under a runtime
    bound (a sixth argument, ``n``: one program whatever it is).  cfg is
    closed over (hashable frozen dataclass).  A function of the config alone,
    so the compile test (``tests/test_chip_compile.py``) lowers exactly what
    a replica runs."""
    import jax

    from ray_tpu.models import generate as gen

    def llm_prefill(params, toks, lens, cache, slots):
        logits, cache = gen.prefill_at(params, cfg, toks, lens, cache, slots)
        # the dispatch's routing counts come out beside the donated cache, so
        # that the host can read them (None: the family counts nothing)
        return logits, cache, cache.pop("routed", None)

    prefill = jax.jit(
        llm_prefill,
        donate_argnums=(3,),  # scatter into the cache in place
    )
    # the decode program keeps the name XLA gives a jitted partial
    # (jit__unknown): the benchmark's cell files find it by that name
    decode = jax.jit(
        partial(
            _decode_chunk_wrapper, gen, cfg,
            steps=decode_chunk_steps, temperature=temperature,
            top_k=top_k, eos_id=eos_id,
        ),
        donate_argnums=(1,),  # cache buffers reused in place
    )

    # a name of its own (jit_llm_decode_cut): a device trace keeps its runs
    # apart from the whole chunk's, whose time is read as that of
    # ``decode_chunk_steps`` steps
    def llm_decode_cut(params, cache, tokens, active, key, n):
        return _decode_chunk_wrapper(
            gen, cfg, params, cache, tokens, active, key, n=n,
            steps=decode_chunk_steps, temperature=temperature, top_k=top_k,
            eos_id=eos_id)

    return prefill, decode, jax.jit(llm_decode_cut, donate_argnums=(1,))


def _decode_chunk_wrapper(gen, cfg, params, cache, tokens, active, key, *,
                          steps, temperature, top_k, eos_id, n=None):
    emitted, cache, _active, key = gen.decode_chunk(
        params, cfg, cache, tokens, active, key, steps=steps, n=n,
        temperature=temperature, top_k=top_k, eos_id=eos_id)
    # chain the NEXT chunk off this one's final tokens without a host
    # round trip (inactive slots carry their input token through, so
    # emitted[:, -1] is correct for every slot); the chunk's routing counts
    # as in llm_prefill
    return emitted, cache, emitted[:, -1], key, cache.pop("routed", None)


def _to_host_async(tree) -> None:
    import jax

    for leaf in jax.tree.leaves(tree):
        leaf.copy_to_host_async()


def _device_facts() -> Dict[str, Any]:
    """What this process's JAX runs on, and the most device memory it has
    held, read now (None where the backend keeps no such count)."""
    import jax

    from ray_tpu.util import perf as _perf

    devs = jax.devices()
    sample = _perf.sample_device_memory(devs[0]) or {}
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": sample.get("peak_bytes_in_use")}


def _default_init(cfg, seed: int):
    import jax

    from ray_tpu.models import generate as gen

    return gen.family_of(cfg).init(cfg, jax.random.PRNGKey(seed))


def make_config(family: str = "gpt2", size: str = "tiny", **kw):
    from ray_tpu.models import generate as gen

    if family not in gen.FAMILIES:
        raise ValueError(f"unknown model family {family!r}")
    sizes = gen.FAMILIES[family].SIZES
    return sizes.get(size, sizes["tiny"])(**kw)


def prefill_decode_graph(
    family: str = "gpt2",
    size: str = "tiny",
    *,
    max_new_tokens: int = 8,
    prefill_bucket: int = 64,
    num_tpus: float = 0,
    seed: int = 0,
    config_kwargs: Optional[Dict[str, Any]] = None,
    **compile_kwargs,
):
    """Disaggregated prefill→decode serving as a 2-stage compiled graph.

    The splitwise/DistServe shape on this runtime's compiled execution
    graphs (``dag/compiled.py``): a prefill actor runs the prompt and
    ships the KV cache + first sampled token over a pre-allocated channel
    to a decode actor, which runs the fused decode loop — so the
    per-request hop between the stages is a channel write, not a
    scheduler round trip + object seal (at decode step times the dynamic
    path's per-hop dispatch would dominate).

    Returns a :class:`~ray_tpu.dag.compiled.CompiledDAG`:
    ``graph.execute([tok, ...])`` (or ``{"tokens": [...],
    "max_new_tokens": n}``) → ref whose ``get()`` yields the generated
    token ids.  Greedy decoding, so a given prompt is deterministic.
    Both stages initialize weights from the same ``seed`` (a production
    deployment would load the same checkpoint into both).  Call
    ``graph.teardown()`` when done.
    """
    import ray_tpu

    ckw = dict(config_kwargs or {})
    actor_opts: Dict[str, Any] = {}
    if num_tpus:
        actor_opts["num_tpus"] = num_tpus

    @ray_tpu.remote(**actor_opts)
    class PrefillStage:
        def __init__(self):
            from ray_tpu.models import generate as gen

            self.gen = gen
            self.cfg = make_config(family, size, **ckw)
            self.params = _default_init(self.cfg, seed)
            self.max_len = prefill_bucket + max_new_tokens + 1

        def prefill(self, request):
            import jax
            import jax.numpy as jnp

            if not isinstance(request, dict):
                request = {"tokens": list(request)}
            toks = list(request["tokens"])
            if not toks:
                raise ValueError("empty prompt")
            if len(toks) > prefill_bucket:
                raise ValueError(
                    f"prompt length {len(toks)} exceeds the prefill "
                    f"bucket {prefill_bucket}")
            # fixed prompt width: one compiled prefill serves every request
            arr = np.zeros((1, prefill_bucket), np.int32)
            arr[0, :len(toks)] = toks
            cache = self.gen.init_cache(self.cfg, 1, self.max_len)
            last, cache = self.gen.prefill(
                self.params, self.cfg, jnp.asarray(arr),
                jnp.asarray(np.array([len(toks)], np.int32)), cache,
                jnp.int32(0))
            first = int(np.asarray(jnp.argmax(last[0])))  # greedy
            # KV state ships host-side through the channel (the
            # disaggregation transfer)
            return {
                "cache": jax.tree.map(np.asarray, cache),
                "first": first,
                "max_new": int(request.get("max_new_tokens")
                               or max_new_tokens),
            }

    @ray_tpu.remote(**actor_opts)
    class DecodeStage:
        def __init__(self):
            from ray_tpu.models import generate as gen

            self.gen = gen
            self.cfg = make_config(family, size, **ckw)
            self.params = _default_init(self.cfg, seed)

        def decode(self, state):
            import jax
            import jax.numpy as jnp

            m = state["max_new"]
            if m > max_new_tokens:
                # the KV cache is sized for the factory's budget — reject
                # loudly (like the prompt-length check) instead of
                # silently truncating the generation
                raise ValueError(
                    f"max_new_tokens {m} exceeds the graph's budget "
                    f"{max_new_tokens}")
            out = [state["first"]]
            if m > 1:
                cache = jax.tree.map(jnp.asarray, state["cache"])
                emitted, _cache, _active, _key = self.gen.decode_chunk(
                    self.params, self.cfg, cache,
                    jnp.asarray(np.array([state["first"]], np.int32)),
                    jnp.ones((1,), bool), jax.random.PRNGKey(0),
                    steps=m - 1, temperature=0.0)
                out += [int(t) for t in np.asarray(emitted)[0]]
            return out[:m]

    from ray_tpu.dag import InputNode

    with InputNode() as inp:
        dag = DecodeStage.bind().decode.bind(
            PrefillStage.bind().prefill.bind(inp))
    compile_kwargs.setdefault("max_inflight", 4)
    return dag.experimental_compile(**compile_kwargs)


def llm_deployment(
    family: str = "gpt2",
    size: str = "tiny",
    *,
    name: str = "llm",
    num_replicas: int = 1,
    num_tpus: float = 0,
    engine_kwargs: Optional[Dict[str, Any]] = None,
    config_kwargs: Optional[Dict[str, Any]] = None,
    max_concurrent_queries: int = 64,
):
    """Build a Serve deployment serving token generation with continuous
    batching (the ``num_tpus=1`` replica shape of BASELINE config 5, with
    the engine replacing the plain forward)."""
    from ray_tpu import serve

    ekw = dict(engine_kwargs or {})
    ckw = dict(config_kwargs or {})
    actor_opts: Dict[str, Any] = {"max_concurrency": max_concurrent_queries}
    if num_tpus:
        actor_opts["num_tpus"] = num_tpus

    @serve.deployment(
        name=name,
        num_replicas=num_replicas,
        max_concurrent_queries=max_concurrent_queries,
        ray_actor_options=actor_opts,
    )
    class LLMServer:
        def __init__(self):
            cfg = make_config(family, size, **ckw)
            self.engine = GenerationEngine(cfg, **ekw).start()

        def __call__(self, request):
            """request: {"tokens": [int, ...], "max_new_tokens": int,
            "stream": bool} -> {"tokens": generated ids}, or a token-per-
            line StreamingResponse when ``stream`` is set.  Blocks this
            replica thread; the engine interleaves all in-flight requests
            between chunks."""
            from ray_tpu.serve._private.http_util import Request as _HttpReq

            if isinstance(request, _HttpReq):
                request = request.json()
            if isinstance(request, (list, tuple)):
                request = {"tokens": list(request)}
            if request.get("stream"):
                from ray_tpu import serve as _serve

                gen = self.engine.stream(
                    request["tokens"], request.get("max_new_tokens"))
                return _serve.StreamingResponse(
                    (f"{t}\n" for t in gen), content_type="text/plain")
            toks = self.engine.generate(
                request["tokens"], request.get("max_new_tokens"))
            return {"tokens": toks}

        def stats(self):
            return self.engine.stats()

        def perf_stats(self):
            return self.engine.perf_stats()

        def trace(self, logdir: str, seconds: float) -> str:
            """Take a device trace of this replica for ``seconds`` into
            ``logdir`` (on the replica's host) while it keeps serving;
            the engine thread's phases are in it by name."""
            from ray_tpu.util import profiling

            with profiling.profile_trace(logdir):
                time.sleep(seconds)
            return logdir

    return LLMServer
