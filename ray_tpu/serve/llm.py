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
  whatever the bound), so no answer waits for steps nobody needs.  A family
  whose cache COMPACTS itself (``generate.summary_cache``: an exact window
  pooled into summaries when it fills) gives the cut a second reason: a chunk
  also ends where the nearest live slot's WINDOW ends, because the roll-over
  (pool, append, restart at place 0) runs with the chunk's flush and no chunk
  may straddle it.  A prompt
  longer than one PART (:data:`PREFILL_PART_TOKENS`) goes into its slot a
  part at a time, each part attending what the earlier ones left in the cache
  (``generate.prefill_at``'s ``offsets``: one further program whatever the
  offset), at most one part between two decode chunks while any row decodes:
  a pasted document stalls no live stream for longer than one part takes.
  Buckets above a part are never called, so their programs are never built; a
  family with recurrent layers keeps whole prompts (``generate.can_continue``)
  unless the state a part leaves in the slot is the state the next part starts
  from (``cfg.state_carried_in``: Mamba-1, :mod:`ray_tpu.models.phi4_flash`).
  A family whose upper layers read ONE lower layer's slab
  (``generate.shared_cache``) is told which rows END a prompt (``final``): a
  part that ends none never runs the layers above the slab.
  Parts start at position 0 and every part but a prompt's last is WHOLE, so a
  part's offset is a multiple of the part: a compacting family's windows (a
  part is one or more whole windows: checked where the engine is built) line
  up with the parts for free, and only because of that.
  A family with a VISION TOWER in front of its text path
  (:mod:`ray_tpu.models.keye_vl`) is the first whose requests are not token
  ids alone: a request may carry a video's patches, the tower is a program of
  its own (``jit_llm_vision_encode``: :data:`VISION_CALL_PATCHES` patches a
  call, one program a frame grid) run for the frames a prompt's NEXT prefill
  call needs, in the tick that dispatches that call, its rows handed over on
  the device (no buffer a slot, no pixels on the host once the last frame is
  dispatched; a frame whose rows straddle a part's end is encoded once: the
  tower's last result rides to the next part), and a slot's rotary position
  is its cached length plus the offset its prompt left (``rope_delta``).
  Tower calls count against the tick's token budget by their patches, and the
  tick meter books their time as prefill.
- :func:`llm_deployment` — wraps the engine in a Serve deployment on a
  ``num_tpus`` replica; requests block on a future the engine thread
  resolves, so Serve's threaded replica concurrency (not the engine)
  bounds in-flight requests.
"""

from __future__ import annotations

import contextlib
import operator
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from functools import partial
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu._private import events as _events
from ray_tpu._private import sampling_profiler
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
# ... and, of a request that carries a video, the device seconds its tower
# calls took (a part of ``engine.first_token``: dispatch -> first token)
VISION_STAGES = ("engine.vision_encode",)
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


# Tokens of a prompt one prefill call takes: a longer prompt goes into its slot
# a PART of this many at a time, each part attending what the earlier ones left
# in the cache (``generate.prefill_at``'s ``offsets``), at most one part between
# two decode chunks while any row decodes: what a live stream may wait for
# somebody else's document.  The chip's sweep (PERF.md section 6, PR 45; the
# serve cells' fixed traces, warm runs): dots3-note's cell (prompts 2,048 -
# 16,384) at 1,024 / 2,048 / 4,096 reads ``tpot_p95_ms`` 12.0 / 13.0 / 19.7 for
# the whole prompts' 30.3, but at 1,024 the parts' own queue grows (260.2
# tokens/s for the 280.3 offered, the first token's p95 5.2 s for 2.6 s at
# 2,048 and 1.7 s at 4,096): a part costs what a whole call as wide costs and,
# on top, what the cached positions BEFORE it cost, so halving it halves
# nothing.  One part alone on the chip (PR 50; the 2,048 bucket's whole call
# 49.0 ms): 65.3 / 73.3 / 90.0 / 113.8 ms at offsets 2,048 / 4,096 / 8,192 /
# 14,336, ~8.2 ms a further 2,048 cached positions (the masked kernel over
# them ~6, their up-projection, concatenation and index scores ~2.2: those
# are prepared a block of 2,048 a trip, the blocks below the part's end alone,
# ``ops.attention.live_blocks``), and ~7.5 ms for being a part at all (offset
# 0: 56.5).  Before PR 50 every part prepared all 16,384 positions of the
# program's static bound, ~24 ms whatever its offset: 82.0 / 87.9 / 100.0 /
# 117.0 at the same offsets.  Kimi-K2's
# cell (prompts 256 - 8,192, half of them split) hardly tells the sizes apart:
# 9.7 - 10.4 / 9.4 - 9.6 / 9.4 for the whole prompts' 9.15 - 9.20, the first
# token's p95 1.6 - 2.4 s / 0.95 s / 0.7 s: its whole calls were short enough
# already, so the size is dots3-note's.  (A prompt's FIRST part runs the 2,048
# bucket's own program: ``_part_call``.)
PREFILL_PART_TOKENS = 2048

# Patches one call of a vision tower takes (frames a call: this many over the
# patches of a frame, at least one; 16 frames of 16 x 16 patches): frames are
# independent, so a call is a batch, and at 4,096 patches the tower's matmuls
# are far past the chip's ridge while a call stays ~a part's own time, which
# is what a live stream waits between two chunks.
VISION_CALL_PATCHES = 4096


class RequestRefused(ValueError):
    """A request the engine refuses at submission, in the caller's thread: a
    client's error, named (``reason``: the key it is counted under in
    ``stats()["refused"]``)."""

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason


def _llm_metrics():
    global _LLM_METRICS
    if _LLM_METRICS is None:
        from ray_tpu.util.metrics import Counter, Histogram

        _LLM_METRICS = {
            # a family with a vision tower: what its calls encoded, and the
            # requests refused at submission (any family), by reason
            "vision_patches": Counter(
                "ray_tpu_llm_vision_patches_total",
                "patches the vision tower's calls encoded (padding apart)"),
            "vision_frames": Counter(
                "ray_tpu_llm_vision_frames_total",
                "video frames the vision tower's calls encoded"),
            "refused": Counter(
                "ray_tpu_llm_requests_refused_total",
                "requests refused at submission (a client's error)",
                tag_keys=("reason",)),
            "admission": Histogram(
                "ray_tpu_llm_slot_admission_latency_s",
                "request submit -> decode-slot admission latency (s)",
                boundaries=[0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 30]),
            # decode-tail attribution: TTFT + inter-token latency per
            # request, TSDB-exported via the push path
            "ttft": Histogram(
                "ray_tpu_llm_ttft_s",
                "request submit -> first generated token (s)",
                boundaries=[0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1, 5, 30]),
            "itl": Histogram(
                "ray_tpu_llm_itl_s",
                "inter-token latency (per-drain mean per request, s)",
                boundaries=[0.0005, 0.001, 0.005, 0.01, 0.025, 0.05,
                            0.1, 0.5, 1]),
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
    prefill call while other slots were mid-decode: a whole prompt's, or one
    PART of a long one, whether or not the tick admitted a row to its chunk) /
    ``prefill_only``; an interleaved period's prefill part is *prefill
    interference*: what the requests that were decoding waited for other
    requests' prompts.

    Also summed here: what a tick cost the engine thread, by kind of time
    (``host``, a :class:`ray_tpu.util.tracing.StallRecorder` over the phases
    ``HOST_PHASES``: wall and CPU seconds, context switches, GC pauses, a
    histogram of the ticks' host time and the records of the slow ones), and,
    over finished requests, what their decode spans held (``decode``).

    Owned by the engine thread (no locking needed on the hot path);
    ``snapshot()`` reads are torn-tolerant counters."""

    EMIT_EVERY = 32  # interleaved ticks per flight-recorder event
    # a tick's host phases: admission (the prefill calls' dispatch), the
    # chunk's dispatch, the drain less the time blocked in its reads
    HOST_PHASES = ("admit", "dispatch", "drain_book")

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
        self.host = tracing.StallRecorder(entity_id, self.HOST_PHASES)
        self.decode = {"requests": 0, "gaps": 0, "chunk_steps_paid": 0,
                       "span_s": 0.0, "prefill_s": 0.0}
        self._landed: Optional[float] = None  # the previous landing
        self._period_s = 0.0    # of the tick being drained, so far
        self._counted = False   # it has a previous landing to start from
        self._calls = 0         # its prefill calls that have landed
        self._mode: Optional[str] = None  # its class, None if left out
        self._since_emit = 0
        # the engine's recurrent-state counters (its own dict, or None): they
        # ride this meter's event to `ray_tpu perf`; so does the engine's
        # tally of the prompts that went in parts (its own dict too)
        self.state: Optional[Dict[str, int]] = None
        self.parts: Optional[Dict[str, int]] = None
        # an engine with a vision tower: seconds and count of the tower calls
        # that landed behind another landing, and the part of them that fell
        # in interleaved periods (booked as prefill everywhere above); None:
        # no tower
        self.vision: Optional[Dict[str, float]] = None
        self._vision_period_s = 0.0

    def begin(self, chained: bool) -> None:
        """The drain of one tick begins; ``chained``: it was dispatched
        behind a tick not yet drained, whose landing its period starts at."""
        self._counted = chained and self._landed is not None
        if not self._counted:
            self._landed = None
        self._period_s = 0.0
        self._vision_period_s = 0.0
        self._calls = 0

    def vision_landed(self, t: float) -> float:
        """One of the tick's TOWER calls landed at ``t`` (ahead of the prefill
        call its rows feed): prefill time like that call's own.  Returns the
        seconds it is booked."""
        took = 0.0
        if self._landed is not None:
            took = t - self._landed
            self._period_s += took
            self.prefill_s += took
            self._vision_period_s += took
            self.vision["tower_s"] += took
            self.vision["tower_calls_timed"] += 1
        self._landed = t
        return took

    def call_landed(self, t: float) -> None:
        """One of the tick's prefill calls landed at ``t``."""
        self._calls += 1
        if self._landed is not None:
            self._period_s += t - self._landed
            self.prefill_s += t - self._landed
            self.prefill_calls += 1
        self._landed = t

    def chunk_landed(self, t: float, n_admitted: int, n_rows: int) -> None:
        """The tick's chunk landed at ``t``: its record is made
        (``n_admitted`` of the ``n_rows`` it decoded were prefilled in it; a
        tick whose calls were parts that completed no prompt admitted none, and
        one that decoded no row has its last call's landing for ``t``)."""
        prefill_part = self._period_s
        if self._landed is not None:
            self._period_s += t - self._landed
        self._landed = t
        self._mode = None
        if not self._counted:
            return
        if not (self._calls or n_admitted):
            mode = "decode_only"
        elif n_rows > n_admitted:
            mode = "interleaved"
        else:
            mode = "prefill_only"
        self._mode = mode
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
            if self.vision is not None:
                self.vision["tower_interference_s"] += self._vision_period_s
            self._since_emit += 1
            if self._since_emit >= self.EMIT_EVERY:
                self.emit_event()

    def tick_host(self, admit, dispatch, drain_book, began: float = 0.0,
                  cpu_now: float = 0.0, **facts) -> Optional[dict]:
        """What one tick that dispatched or drained anything cost the
        engine thread, apart from blocking in the drain's reads: a phase's
        wall seconds, or its ``tracing.clocks_between`` tuple (wall, CPU,
        switches of both kinds, GC seconds).  ``began``, ``cpu_now``: the
        tick's first wall clock read and the thread's CPU clock at its last;
        ``facts``: what it dispatched.  A slow tick's record
        (returned, kept in ``host.slow`` and emitted once) also says what the
        tick it DRAINED was: its class and device period."""
        deltas = [d if isinstance(d, tuple) else (d, 0.0, 0, 0, 0.0)
                  for d in (admit, dispatch, drain_book)]
        return self.host.add(deltas, began, cpu_now,
                             drained_class=self._mode,
                             drained_period_s=round(self._period_s, 6),
                             **facts)

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
            # the engine thread's time a tick by phase and kind of time,
            # the ticks' host time by bucket, the slow ticks' records
            # (``host_s``, ``host_cpu_s``, ``host_switches``, ``host_gc_s``,
            # ``host_hist``, ``slow_ticks``: StallRecorder.snapshot)
            **self.host.snapshot(),
            "ticks_live": self.host.count,
            "decode": dict(self.decode),
            **({"vision_ticks": {k: round(v, 6) for k, v in self.vision.items()}}
               if self.vision is not None else {}),
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
            host=self.host.summary(),
            **({"state": dict(self.state)} if self.state else {}),
            **({"parts": dict(self.parts)} if self.parts else {}))


def cache_positions(largest_bucket: int, max_new_tokens: int,
                    chunk_steps: int) -> int:
    """Cache positions an engine gives a slot: the longest prompt (in whole
    parts, where it is longer than one: :func:`part_bound`), the
    longest answer and one chunk of slack for the flush, rounded UP to whole
    tiles of the decode kernel — a cache of whole tiles is what lets the
    decode step read only a slot's live tiles
    (:func:`ray_tpu.ops.attention.ragged_decode_attention`)."""
    from ray_tpu.ops.attention import DECODE_TILE

    need = part_bound(largest_bucket) + max_new_tokens + chunk_steps
    return -(-need // DECODE_TILE) * DECODE_TILE


def cache_counts(cfg, max_len: int) -> Dict[str, Any]:
    """What an engine's counters need to know of its cache, summed over the
    rows of ``generate.cache_layout(cfg)`` for slots of ``max_len`` positions:
    ``layers`` (how many a kind; no ``state`` key where there is none),
    ``ring_tiles`` (tiles of one window layer's ring a slot), ``slab_tiles``
    (tiles of a full layer's padded slab a slot: of the first of its tensors,
    and of the summaries that pool it), ``tile_bytes`` (bytes of one tile of
    a layer, every tensor that holds its positions, by kind), ``state`` (the
    tensor that holds the recurrent state, slots second; None) and
    ``state_row_bytes`` (what a slot holds a recurrent layer)."""
    from ray_tpu.models import generate as gen
    from ray_tpu.ops.attention import DECODE_TILE

    tiles = lambda row: -(-row.positions(max_len) // DECODE_TILE)  # noqa: E731
    by_position = {
        gen.FULL_LAYERS: gen.cache_rows(
            cfg, gen.SLAB, gen.WINDOW, layers=gen.FULL_LAYERS),
        gen.WINDOW_LAYERS: gen.cache_rows(cfg, gen.RING)}
    first = by_position[gen.FULL_LAYERS][0]
    states = gen.cache_rows(cfg, gen.STATE)
    layers = {kind: rows[0].count if rows else 0
              for kind, rows in by_position.items()}
    if states:
        layers[gen.STATE_LAYERS] = states[0].count
    return {
        "layers": layers,
        "ring_tiles": sum(map(tiles, by_position[gen.WINDOW_LAYERS][:1])),
        "slab_tiles": tiles(first) + sum(
            tiles(r) for r in gen.cache_rows(cfg, gen.SUMMARY)
            if r.pools == first.name),
        "tile_bytes": {kind: DECODE_TILE * sum(r.row_bytes() for r in rows)
                       for kind, rows in by_position.items()},
        "state": next((r.name for r in states if r.slot_axis == 1), None),
        "state_row_bytes": sum(r.row_bytes() for r in states)}


def part_bound(largest_bucket: int) -> int:
    """The cache positions a prompt's part may attend, itself included: the
    largest bucket in whole parts (the part program's static bound on the
    keys; 16,384 for dots3's cell, where the buckets are whole parts).  A
    bound on shapes only: the program prepares and reads the positions below
    ``offset + part``, a part's worth a trip, so what a part costs follows its
    offset and not this (``perf_stats()["prefill"]["parts"]``:
    ``blocks_prepared`` of ``blocks_bound``)."""
    part = PREFILL_PART_TOKENS
    return largest_bucket if largest_bucket <= part else (
        -(-largest_bucket // part) * part)


class _Request:
    __slots__ = ("tokens", "max_new", "future", "emitted", "scheduled",
                 "prefilled", "submitted_at", "trace_ctx", "dispatched_at",
                 "first_host_t", "last_host_t", "chunks", "chunk_steps",
                 "prefill_mark", "video", "vision_s")

    def __init__(self, tokens: List[int], max_new: int, video=None):
        self.tokens = list(tokens)
        # a request that carries a video (``GenerationEngine._checked``): its
        # grid, its patches on the host until the last frame is dispatched,
        # where its rows stand among the tokens, its rotary positions and the
        # offset its slot decodes at; and the device seconds its tower calls
        # took
        self.video: Optional[Dict[str, Any]] = video
        self.vision_s = 0.0
        self.max_new = int(max_new)
        self.future: Future = Future()
        self.emitted: List[int] = []
        # tokens DISPATCHED for this request (prefill + chunks), maintained
        # at dispatch time — emitted lags one chunk behind in the pipeline,
        # so completion prediction must count scheduled, not emitted
        self.scheduled = 0
        # prompt tokens dispatched in PARTS so far (a prompt longer than one
        # part; a whole prompt's call leaves it 0).  A request that holds a
        # slot with nothing scheduled is mid-prefill: it sits the chunks out
        self.prefilled = 0
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
        # [n_slots+1, chunk] device; None: a tick whose calls were parts
        # and no row decoded, so that no chunk was dispatched
        self.chunk_dev = chunk_dev
        self.steps = steps                  # the steps it ran: chunk, or its cut
        self.rows = rows                    # [(slot, _Request)] active in chunk
        # this iteration's prefill calls, in dispatch order: (admissions
        # [(row_j, slot, _Request)], first tokens [rows] device, the call's
        # routing counts: device, None where the family counts nothing; the
        # padded tokens it was wide; the tower calls dispatched ahead of it,
        # [(a value of the call's result, its _Request)]).  A
        # part that completes no prompt admits nobody, and in the first
        # tokens' place has where its row stands now (read for its landing)
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
        tracing.listen_gc()  # ... and every collection (["process"]["gc"])
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

        params = jax.tree.map(
            lambda x: x.astype(cfg.dtype)
            if hasattr(x, "dtype") and x.dtype == jnp.float32 else x,
            params)
        # ... and laid out as the step programs read them, ONCE and before the
        # cache exists: a family's held experts' gate and up matrices side by
        # side (one grouped matmul where there were two), a layer at a time
        # with its two sources let go (the cast tree's containers are the
        # engine's own, and ``params`` was rebound so that nothing else holds
        # them), never in a step
        self.params = gen.serving_layout(cfg, params)
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
        # a prompt longer than this goes in parts of it, where the family's
        # caches can be read back by a later part (None: whole prompts only:
        # recurrent layers, or no bucket above a part)
        self._part: Optional[int] = PREFILL_PART_TOKENS if (
            gen.can_continue(cfg)
            and self.buckets[-1] > PREFILL_PART_TOKENS) else None
        # a family whose cache compacts itself: (window, chunk), or None.  Its
        # chunks end where the nearest live slot's window does (``step``), and
        # a part is whole windows (parts are whole and start at 0, so their
        # offsets are multiples of the window: ``generate.prefill_at``)
        self._compact = gen.summary_cache(cfg)
        assert not (self._compact and self._part
                    and self._part % self._compact[0]), (
            "a part has to be whole windows", self._part, self._compact)
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
        # per layer of the kind: a full layer reads a slot's live tiles; a
        # window layer every row's whole ring (two tiles at the published
        # window; ``held_window``), whatever the context, or, where the rings
        # hold latent rows in whole tiles, the tiles of the entries a
        # dispatched row's ring holds (``generate.decode_chunk``'s ring plan)
        counts = cache_counts(cfg, self._max_len)
        self._layers = counts["layers"]
        self._ring_tiles = counts["ring_tiles"]
        self._cache_tiles = {"read_full": 0, "read_window": 0,
                             "held_window": 0, "padded": 0, "flushed": 0}
        # a family whose upper layers read ONE lower layer's slab: the layers
        # that read it (its owner and the readers), or None
        self._shared = gen.shared_cache(cfg)
        if self._shared is not None:
            # its own, cumulative, counted on the host at dispatch (they ride
            # HERE for the traced replica's sake, as ``eva_*`` do): over the
            # steps each dispatch ran, the slab tiles read TIMES the layers
            # that read them, the ring tiles read times the window layers, the
            # rows of state moved times the recurrent layers, the live rows;
            # the prompt positions prefilled and the positions (rows that
            # ended a prompt) run through the layers above the slab
            self._slab_readers = 1 + sum(
                gen.reads_layer(w) is not None for w in windows)
            self._cache_tiles.update(dict.fromkeys((
                "yoco_slab_tile_steps", "yoco_ring_tile_steps",
                "yoco_state_row_steps", "yoco_row_steps", "yoco_steps",
                "yoco_dispatches", "yoco_prefill_positions",
                "yoco_upper_positions"), 0))
        if self._compact:
            # a compacting family's own, cumulative like the rest and counted
            # at dispatch on the host (they ride HERE because a traced
            # replica of the benchmark reads this key at the trace's two
            # ends; ``perf_stats()["eva"]`` is the same numbers): tiles of the
            # exact window and of the summaries the decode steps read (a layer
            # a step: ``read_full`` is their sum a dispatch; ``tile_steps``:
            # the same times the steps each dispatch ran, beside the live rows
            # times those steps, ``row_steps``), the window
            # places and summary rows those tiles hold and the ones the
            # queries MAY attend (summed over the steps), the windows the
            # decode rolled over and the chunks that pooled, the windows the
            # prefills pooled, the chunks cut for a window's end, and the
            # steps and dispatches all of it is over
            self._cache_tiles.update(dict.fromkeys((
                "eva_window_tiles", "eva_summary_tiles", "eva_tile_steps",
                "eva_row_steps", "eva_read_positions",
                "eva_attendable_positions", "eva_rollovers",
                "eva_chunks_pooled", "eva_prefill_windows_pooled",
                "eva_window_cuts", "eva_steps", "eva_dispatches"), 0))
        # cumulative, by prefill bucket: calls, the rows and tokens they were
        # wide, and the prompts and prompt tokens among those
        # ... and, under "parts" for an engine that splits prompts, the same
        # of those that went in PARTS: the prompts split, the part calls (one
        # row each), and the tokens they took; then the blocks of cached
        # positions (a part wide) the calls prepared for their keys, those
        # below each part's end, beside what the program's static bound holds
        self._prefill = {b: {"calls": 0, "rows": 0, "padded_tokens": 0,
                             "prompts": 0, "live_tokens": 0}
                         for b in (*self.buckets, *(("parts",) if self._part else ()))}
        if self._part:
            self._prefill["parts"].update(blocks_prepared=0, blocks_bound=0)
        # cumulative routing counts of the dispatches drained so far, where
        # the family's layers count (leaves as the programs return them:
        # stacked over the layers that route); None until a first arrives
        self._routed = {"prefill": None, "decode": None, "decode_steps": 0,
                        "decode_dispatches": 0, "prefill_dispatches": 0}
        # one extra SCRATCH slot (index n_slots): a prefill call is its
        # bucket's fixed rows wide, and the rows no prompt fills park there
        self.cache = gen.init_cache(cfg, n_slots + 1, self._max_len)
        self._ring_by_tile = gen.ring_read_by_tile(self.cache, cfg)
        # tiles of the padded slab a full layer a slot (a compacting family:
        # its window's and its summaries'), and bytes of one tile of a layer
        # of each kind, from the table's own rows, so that no reader of the
        # counters guesses a row's width
        self._slab_tiles = counts["slab_tiles"]
        self._tile_bytes = counts["tile_bytes"]
        # a family with recurrent layers: cumulative, over the steps the
        # drained chunks really ran (host arithmetic, no device read): the
        # rows whose state a step HAD to move (``rows_live``: the chunk's
        # rows) and those the program's update touched (``rows_updated``: the
        # same where the decode program was lowered with the kernel that
        # walks them, every row of the cache where it runs the masked form);
        # the layers with a state, and the bytes a row holds a layer (state
        # and last inputs).  None: no such layer
        self._state: Optional[Dict[str, int]] = None
        if counts["state"]:
            from ray_tpu.ops import ssm

            self._state_kernel = jax.devices()[0].platform == "tpu" and (
                ssm.kernel_shapes if self._shared is None
                else ssm.selective_kernel_shapes)(self.cache[counts["state"]])
            self._state = {
                "rows_updated": 0, "rows_live": 0, "steps": 0, "dispatches": 0,
                "layers": self._layers[gen.STATE_LAYERS],
                "row_bytes": counts["state_row_bytes"]}
        self._key = jax.random.PRNGKey(seed)
        # requests refused at submission, by reason (``stats()["refused"]``)
        self._refused: Dict[str, int] = {}
        # a family with a vision tower in front (``vision_program``; None:
        # requests are token ids alone): the tower's jitted call, and,
        # cumulative and counted on the host at dispatch, the frames and
        # patches the calls encoded, the patches of the frames a call was
        # padded with past a video's end, the rows handed to prefill calls
        # (``visual_tokens``), the calls, the requests with a video; they ride
        # ``cache_tiles`` too (``vision_*``), where a traced replica of the
        # benchmark reads its counters at the trace's two ends
        self._vision_jit = vision_program(cfg)
        self._vision: Optional[Dict[str, int]] = None
        self._vision_zeros: Dict[tuple, Any] = {}
        if self._vision_jit is not None:
            self._vision = dict.fromkeys(
                ("frames", "patches", "padded_patches", "visual_tokens",
                 "calls", "requests"), 0)
            self._cache_tiles.update(
                {"vision_" + k: 0 for k in self._vision})

        (self._prefill_jit, self._decode_jit, decode_cut_jit,
         self._part_jit) = engine_programs(
            cfg, decode_chunk_steps=decode_chunk_steps,
            temperature=temperature, top_k=top_k, eos_id=eos_id,
            part_bound=part_bound(self.buckets[-1]) if self._part else None)
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
        # the slot-holders whose prompt is going in parts, oldest first
        self._splitting: List[tuple] = []  # (slot, _Request)
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
        # what ``stream`` waits on: notified by a drain after each prefill
        # call's first tokens' appends and after its chunk rows' and the
        # futures it resolved (a call or a chunk, never a token), by the
        # failure path and by ``stop``; its lock guards nothing else
        self._landed = threading.Condition()
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
        self._ticks.parts = self._prefill.get("parts")
        if self._vision is not None:
            self._ticks.vision = {"tower_s": 0.0, "tower_calls_timed": 0,
                                  "tower_interference_s": 0.0}
        # the process's sampler looks at the tick's phases between its bursts
        # (where the continuous profiler is off nobody looks: no stacks)
        sampling_profiler.watch(self._ticks.host)
        self._ttft_samples: "_deque[float]" = _deque(maxlen=4096)
        self._itl_samples: "_deque[float]" = _deque(maxlen=4096)

    # -- public API ----------------------------------------------------
    def checked(self, tokens, max_new=None, video=None) -> tuple:
        """What a request may carry, checked in the CALLER's thread: ``(tokens,
        max_new, video)`` as ``_Request`` takes them, or :class:`RequestRefused`
        (a ``ValueError`` that names the fault; counted by reason in
        ``stats()["refused"]``; nothing refused is ever admitted).

        - ``tokens``: a non-empty list of ints, at most the largest prefill
          bucket long;
        - ``max_new``: None or an int >= 0 (None and 0: the engine's cap; held
          to the cap);
        - ``video`` (None: a text request; only a family with a vision tower
          takes one): ``{"grid": [F, gh, gw], "patches": uint8 [F x gh x gw,
          patch values] (an array, or its bytes in base64)}``: ``F`` frames of
          ``gh x gw`` patches, both even, row-major in a frame.  The tower's
          ``F x gh/2 x gw/2`` rows stand where ``tokens`` hold the family's
          video placeholder id, which has to be ONE run of exactly that many
          (a text request holds none)."""
        try:
            return self._checked(tokens, max_new, video)
        except RequestRefused as e:
            with self._lock:
                self._refused[e.reason] = self._refused.get(e.reason, 0) + 1
            _llm_metrics()["refused"].inc(1, {"reason": e.reason})
            raise

    def _checked(self, tokens, max_new, video) -> tuple:
        if not isinstance(tokens, (list, tuple)) or not all(
                isinstance(t, (int, np.integer)) and not isinstance(t, bool)
                for t in tokens):
            raise RequestRefused("tokens", "tokens: not a list of ints")
        if not tokens:
            raise RequestRefused("tokens", "empty prompt")
        if len(tokens) > self.buckets[-1]:
            raise RequestRefused(
                "tokens", f"prompt length {len(tokens)} exceeds the largest "
                f"prefill bucket {self.buckets[-1]}")
        if max_new is not None and (
                isinstance(max_new, bool)
                or not isinstance(max_new, (int, np.integer)) or max_new < 0):
            raise RequestRefused(
                "max_new_tokens", f"max_new_tokens: {max_new!r} is no int >= 0")
        max_new = min(int(max_new) or self.max_new_tokens
                      if max_new is not None else self.max_new_tokens,
                      self.max_new_tokens)
        placeholder = getattr(self.cfg, "video_token_id", None)
        held = [i for i, t in enumerate(tokens) if t == placeholder
                ] if placeholder is not None else []
        if video is None:
            if held:
                raise RequestRefused(
                    "video", f"{len(held)} video placeholders and no video")
            return list(tokens), max_new, None
        if self._vision is None:
            raise RequestRefused("video", "this model takes token ids alone")
        if not isinstance(video, dict) or "grid" not in video:
            raise RequestRefused("video", "video: no grid")
        grid = video["grid"]
        if not (isinstance(grid, (list, tuple)) and len(grid) == 3 and all(
                isinstance(g, (int, np.integer)) and g > 0 for g in grid)):
            raise RequestRefused(
                "video", "video.grid: not [frames, rows, columns] of patches")
        F, gh, gw = (int(g) for g in grid)
        if gh % 2 or gw % 2:
            raise RequestRefused(
                "video", f"video.grid: {gh} x {gw} patches, an odd side")
        tpf = (gh // 2) * (gw // 2)
        one_run = bool(held) and held[-1] - held[0] == len(held) - 1
        if len(held) != F * tpf or not one_run:
            raise RequestRefused(
                "video", f"{len(held)} video placeholders (one run: {one_run}) "
                f"for {F} x {gh // 2} x {gw // 2} = {F * tpf} rows")
        patches = video.get("patches")
        if isinstance(patches, (str, bytes, memoryview)):
            try:
                patches = _base64_in_pieces(patches)
            except ValueError:  # (binascii.Error is one)
                raise RequestRefused(
                    "video", "video.patches: not base64") from None
        values = self._gen.family_of(self.cfg).vision_config(
            self.cfg).patch_values
        if not isinstance(patches, np.ndarray) or patches.dtype != np.uint8 \
                or patches.size != F * gh * gw * values:
            raise RequestRefused(
                "video", f"video.patches: not {F * gh * gw} x {values} bytes")
        positions, delta = self._gen.family_of(self.cfg).rope_index(
            len(tokens), held[0], (F, gh // 2, gw // 2))
        return list(tokens), max_new, {
            "grid": (F, gh, gw), "patches": patches.reshape(F, gh * gw, values),
            "first": held[0], "tpf": tpf, "n_vis": F * tpf,
            "positions": positions, "delta": delta,
            # frames planned into tower calls so far; the calls due with each
            # of the request's planned prefill calls (their first frames, a
            # list a call); the last tower result on the device and the frame
            # it starts at
            "planned": 0, "due": [], "last": None}

    def _submit_req(self, tokens: List[int], max_new: Optional[int],
                    video=None, ready: bool = False) -> _Request:
        """Validate + enqueue (shared by submit and stream).  ``ready``: the
        three are what :meth:`checked` returned (a caller that refused in its
        own thread first)."""
        req = _Request(*((tokens, max_new, video) if ready
                         else self.checked(tokens, max_new, video)))
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
            if req.video is not None:
                self._count_vision(requests=1)
        self._work.set()
        return req

    def submit(self, tokens: List[int], max_new: Optional[int] = None,
               video=None, ready: bool = False) -> Future:
        """Enqueue one request -> a Future of its generated token ids.  What a
        request may carry, and what is refused here, in the caller's thread:
        :meth:`checked` (``video``: a family with a vision tower only;
        ``ready``: the arguments are what :meth:`checked` returned)."""
        return self._submit_req(tokens, max_new, video, ready).future

    def generate(self, tokens: List[int], max_new: Optional[int] = None,
                 timeout: float = 300.0, video=None,
                 ready: bool = False) -> List[int]:
        return self.submit(tokens, max_new, video, ready).result(timeout)

    def stream(self, tokens: List[int], max_new: Optional[int] = None,
               timeout: float = 300.0, video=None, ready: bool = False):
        """Yield token ids AS THE ENGINE EMITS THEM (token streaming for
        serve's chunked responses).  Raises the request's error, if any.
        Between two looks it waits on the drain's signal (``_landed``), the
        ``timeout`` its only clock.  What a request may carry: :meth:`checked`
        (a generator: the check runs at its first ``next``; a caller that
        wants the refusal before it streams calls :meth:`checked` itself and
        hands over what it returned, ``ready``, as ``llm_deployment`` does)."""
        req = self._submit_req(tokens, max_new, video, ready)
        n = 0
        yielded_t = None  # when the newest token of a look was handed over
        deadline = time.perf_counter() + timeout
        while True:
            with self._landed:
                # read BEFORE the snapshot: the engine resolves the future
                # after its last append, so a done request's tokens are all in
                done = req.future.done()
                emitted = req.emitted  # list append is atomic
                m = len(emitted)
                if m == n and not done:  # nothing new: wait for a drain
                    self._landed.wait(
                        max(0.0, deadline - time.perf_counter()))
                    done = req.future.done()
                    m = len(emitted)
            while n < m:
                if n == 0 and req.first_host_t is not None:
                    # what the wake-up adds to the first token
                    tracing.emit_stage(
                        "engine.stream_yield",
                        time.perf_counter() - req.first_host_t,
                        req.trace_ctx)
                if n == m - 1 and req.first_host_t is not None:
                    yielded_t = time.perf_counter()  # one read a look
                yield emitted[n]
                n += 1
            if done:
                if yielded_t is not None:
                    # ... and what it added to the last: emitted here, where
                    # the request's stamps are final, whichever look saw the
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

    def _wake_streams(self) -> None:
        with self._landed:
            self._landed.notify_all()

    def start(self) -> "GenerationEngine":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="generation-engine")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._work.set()
        self._wake_streams()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        self._decode_cut.exception()  # a build still running ends first
        sampling_profiler.unwatch(self._ticks.host)
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
                # refused at submission, by what was wrong (never admitted)
                "refused": dict(self._refused),
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
            compacting = self._compaction_stats()
            vision = None if self._vision is None else {
                **self._vision,
                "requests_refused": self._refused.get("video", 0)}
        stages: Dict[str, Any] = {}
        if _events.ENABLED:
            stages = tracing.span_stats(STAGES + PER_GAP + (
                VISION_STAGES if vision is not None else ()))
            stages["clock_skew"] = tracing.clock_skew()
        return {
            # the wall clock of this read: every difference of two calls has
            # its denominator, and a slow tick's ``t`` its place in it
            "t": time.time(),
            "ttft": self._pctiles(ttft),
            "itl": self._pctiles(itl),
            **self._ticks.snapshot(),
            # what the PROCESS did meanwhile, read here and never on the
            # engine thread's path (``tracing.process_stats``): every
            # thread's CPU seconds, the engine thread's own, collections and
            # their pauses, CPU seconds by thread name
            "process": tracing.process_stats(self._thread),
            # the request's stages from ingress to the last reply, as this
            # process closed them (cumulative: difference two calls for a
            # window, and take that many off the end of ``recent`` for the
            # window's own durations); empty with the observability layer off
            "stages": stages,
            # how much of the padded cache the decode steps read, in tiles a
            # layer of each kind (cumulative; counted at dispatch from prompt
            # lengths and scheduled tokens; ``read_window`` of every row's
            # ring, ``held_window``), and how much of it a chunk's
            # flush writes (``flushed``: tiles a full layer a tensor, against
            # ``padded``); how many layers of each kind (a latent layer is a
            # full one) and the bytes of one tile of each
            "cache_tiles": cache_tiles,
            # what the prefill calls were wide and what of it was prompt
            # (cumulative, by bucket), and under ``parts``, where the engine
            # splits prompts, the same of the prompts longer than one part:
            # the prompts split, their part calls (a row each), the tokens
            # those took, padded and live
            "prefill": prefill,
            # a family with expert layers: what its layers counted of the
            # routing in the dispatches drained so far, prefills and decode
            # chunks apart (``tokens [layer][held expert]``, ``touched
            # [layer]``: held experts with any token, summed over calls and
            # steps; ``decode_steps``: the chunk steps among them, in
            # ``decode_dispatches`` chunks, whole or cut; ``prefill`` also
            # ``rows_computed``: the rows the calls' grouped matmuls were
            # handed, summed over the layers, so that ``tokens`` summed over
            # it is how full the prefill dispatches were, and ``trips``: the
            # loop trips those calls ran, a layer each; 0 for a dispatch that
            # went as one block: a narrow call's, and any call's of a stage
            # that holds every expert of its layers).
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
            # a family whose cache compacts itself (ray_tpu.ops.eva): the
            # window and summary tiles the decode steps read, the positions
            # in them against those the queries may attend, the roll-overs
            # and the chunks they pooled, the windows the prefills pooled,
            # the chunks cut for a window's end (``__init__``).  Cumulative
            **({"eva": compacting} if compacting else {}),
            # a family with a vision tower: the frames and patches its calls
            # encoded, the patches they were padded with, the rows handed to
            # prefill calls, the calls, the requests with a video and those
            # refused for theirs (cumulative, counted at dispatch; the calls'
            # device seconds: the tick meter's ``vision_ticks`` above)
            **({"vision": vision} if vision is not None else {}),
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

                self._ticks.host.now = None  # no phase is on any more
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
                    self._splitting.clear()  # (they held slots: failed above)
                    self._queue.clear()
                    self._pending = None
                    self._draining = None
                for req in dict.fromkeys(victims):
                    if not req.future.done():
                        req.future.set_exception(e)
                self._wake_streams()  # each waiting ``stream`` raises it
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

        A prompt longer than one PART (``_part``) takes its slot and stays
        out of the decode chunks until it is all in (``_splitting``): its
        parts are calls of this tick and the next ones, the oldest such
        prompt's first: ONE part a tick while any row decodes, parts back to
        back under the budget while none does.  Its last part's first token
        is a whole call's.

        Every call is dispatched here, none is read back: returns the calls
        as ``_PendingChunk.prefills`` holds them, their first tokens ON
        DEVICE (merged into the last-token row there); the values, and the
        calls' routing counts, reach the host with the next chunk drain."""
        with self._lock:
            free = [i for i, s in enumerate(self._slots) if s is None]
            # in dispatch order: (bucket, [(slot, _Request)]), or (None,
            # (slot, _Request, first token of the part, its tokens))
            calls: List[tuple] = []
            spent = 0
            decoding = any(s is not None and s.scheduled for s in self._slots)

            def parts_of(slot, req) -> None:
                # this tick's parts of one prompt: the tick's first call
                # always goes; a further one only while no row decodes, and
                # under the budget
                nonlocal spent
                while req.prefilled < len(req.tokens) and (
                        not calls or (not decoding and spent + self._part
                                      <= self._tick_tokens)):
                    n = min(self._part, len(req.tokens) - req.prefilled)
                    calls.append((None, (slot, req, req.prefilled, n)))
                    # (the part's tower calls, by their patches)
                    spent += self._part + self._plan_frames(
                        req, req.prefilled, n)
                    req.prefilled += n
                    for name, more in (
                            ("calls", 1), ("rows", 1), ("live_tokens", n),
                            ("padded_tokens", self._part),
                            ("blocks_prepared", -(-req.prefilled // self._part)),
                            ("blocks_bound", part_bound(self.buckets[-1])
                             // self._part)):
                        self._prefill["parts"][name] += more
                if req.prefilled >= len(req.tokens):
                    self._splitting.remove((slot, req))

            for slot, req in list(self._splitting):
                parts_of(slot, req)
            for slot in free:
                if not self._queue:
                    break
                if self._part and len(self._queue[0].tokens) > self._part:
                    req = self._queue.pop(0)
                    self._slots[slot] = req
                    self._splitting.append((slot, req))
                    self._prefill["parts"]["prompts"] += 1
                    parts_of(slot, req)  # (none where an older prompt waits)
                    continue
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
                spent += self._plan_frames(req, 0, len(req.tokens))
            if self._shared is not None:  # the prompt positions these calls take
                self._cache_tiles["yoco_prefill_positions"] += sum(
                    batch[3] if b is None
                    else sum(min(len(r.tokens), b) for _, r in batch)
                    for b, batch in calls)
            for b, batch in calls:
                if b is None:
                    continue  # (a part: tallied where it was planned)
                tally = self._prefill[b]
                tally["calls"] += 1
                tally["rows"] += self._rows[b]
                tally["padded_tokens"] += self._rows[b] * b
                tally["prompts"] += len(batch)
                tally["live_tokens"] += sum(len(r.tokens) for _, r in batch)
            if self._compact:  # the windows these calls fill and pool
                window = self._compact[0]
                # (first position, tokens) of every row: a part's, a prompt's
                spans = [batch[2:] if b is None else (0, min(len(r.tokens), b))
                         for b, batch in calls
                         for _, r in ([(None, None)] if b is None else batch)]
                self._cache_tiles["eva_prefill_windows_pooled"] += sum(
                    (first + n) // window - first // window for first, n in spans)
        return [self._part_call(*batch) if b is None
                else self._prefill_call(b, batch) for b, batch in calls]

    def _stamp_dispatch(self, reqs) -> None:
        """The requests' prompts start going in now: their queue wait ends."""
        if not _events.ENABLED:
            return
        t_dispatch = time.perf_counter()
        hist = _llm_metrics()["admission"]
        for req in reqs:
            req.dispatched_at = t_dispatch
            waited = t_dispatch - req.submitted_at
            hist.observe(waited)
            tracing.emit_stage("engine.queue", waited, req.trace_ctx)

    def _first_tokens(self, last_logits, slots_dev, routed_dev, reqs):
        """Sample the first tokens of a call that completed its prompts, on
        the device, into the last-token row there; nothing is read back."""
        firsts_dev, self._last_tok_dev, self._key = self._first_jit(
            last_logits, self._key, self._last_tok_dev, slots_dev)
        firsts_dev.copy_to_host_async()
        _to_host_async(routed_dev)
        for req in reqs:
            req.scheduled = 1  # the prefill's sampled first token
        return firsts_dev

    def _prefill_call(self, b: int, batch, first_part: bool = False):
        """Dispatch one prefill call of bucket ``b`` for ``batch`` [(slot,
        _Request)]: the bucket's fixed rows wide (ONE compiled program a
        bucket: a width that varies recompiles mid-serving), the rows no
        prompt fills a 1-token dummy aimed at the scratch slot.
        ``first_part``: the prompts are longer than the bucket and this is
        their first ``b`` tokens (``_part_call``): nobody is admitted, and
        the drain reads the logits for the call's landing."""
        import jax.numpy as jnp

        n = self._rows[b]
        toks = np.zeros((n, b), np.int32)
        toks[:, 0] = 1  # padding rows: 1-token dummy prompt
        lens = np.ones((n,), np.int32)
        slots = np.full((n,), self.n_slots, np.int32)  # scratch slot
        for j, (slot, req) in enumerate(batch):
            own = req.tokens[:b]
            toks[j, :len(own)] = own
            lens[j] = len(own)
            slots[j] = slot
        self._stamp_dispatch(req for _, req in batch)
        slots_dev = jnp.asarray(slots)
        visual, towers = self._visual(
            [(j, req, 0, int(lens[j])) for j, (_, req) in enumerate(batch)],
            n, b)
        last_logits, self.cache, routed_dev = self._prefill_jit(
            self.params, jnp.asarray(toks), jnp.asarray(lens),
            self.cache, slots_dev, *self._final(n, batch, not first_part),
            *visual)
        if first_part:
            _to_host_async((last_logits, routed_dev))
            return [], last_logits, routed_dev, n * b, towers
        firsts_dev = self._first_tokens(
            last_logits, slots_dev, routed_dev, [req for _, req in batch])
        admissions = [(j, slot, req) for j, (slot, req) in enumerate(batch)]
        return admissions, firsts_dev, routed_dev, n * b, towers

    def _count_vision(self, **more) -> None:
        """Add to the tower's counters, both places (call under the lock)."""
        for name, n in more.items():
            self._vision[name] += n
            self._cache_tiles["vision_" + name] += n

    @staticmethod
    def _video_rows(video, first: int, n: int) -> tuple:
        """``(lo, hi)``: which of the video's rows (counted from its first)
        stand among the prompt's tokens ``[first, first + n)``; ``hi <= lo``:
        none."""
        return (max(first, video["first"]) - video["first"],
                min(first + n, video["first"] + video["n_vis"]) - video["first"])

    def _frames_a_call(self, video) -> int:
        _, gh, gw = video["grid"]
        return max(1, VISION_CALL_PATCHES // (gh * gw))

    def _plan_frames(self, req: _Request, first: int, n: int) -> int:
        """Plan the tower calls that the prefill call over ``req``'s tokens
        ``[first, first + n)`` needs (an entry of ``video["due"]``, one a
        planned call in call order: the tower calls' first frames):
        the frames its rows stand for that no earlier call encoded, whole
        calls of real frames (a call encodes ahead of the need rather than
        pad; only a video's end is padded).  Returns the patches those calls
        are wide, for the tick's budget.  Call under the lock."""
        video = req.video
        if video is None:
            return 0
        due: List[int] = []
        video["due"].append(due)  # one entry a prefill call, in call order
        lo, hi = self._video_rows(video, first, n)
        if hi <= lo:
            return 0
        per, frames = self._frames_a_call(video), video["grid"][0]
        size = video["grid"][1] * video["grid"][2]
        need = -(-hi // video["tpf"])  # frames [0, need) have to be encoded
        real = 0
        while video["planned"] < need:
            due.append(video["planned"])
            real += min(per, frames - video["planned"])
            video["planned"] += per
        self._count_vision(
            frames=real, patches=real * size, calls=len(due),
            padded_patches=(len(due) * per - real) * size,
            visual_tokens=hi - lo)
        _llm_metrics()["vision_frames"].inc(real)
        _llm_metrics()["vision_patches"].inc(real * size)
        return len(due) * per * size

    def _visual(self, spans, rows: int, width: int) -> tuple:
        """A prefill call's ``visual`` argument and the tower calls dispatched
        for it.  ``spans``: ``(row, _Request, first token, tokens)`` of the
        call's real rows; the call is ``rows x width``.  Returns ``((visual,),
        [(a value of a tower call's result, its request)])``; ``((), [])`` for
        a family without a tower; ``((None,), [])`` where no row carries a
        video (the program for token ids alone).

        For every row with a video: its planned tower calls are dispatched
        (``video["due"]``), each over :func:`_frames_a_call` frames from the
        request's host patches, and the rows of the frames this call's tokens
        stand for are named by ``index`` into the results laid side by side:
        the request's LAST result first where an earlier call encoded some of
        them (a frame that straddles a part's end; frames encoded ahead), then
        the new ones; the tuple is filled up with zeros to the count a call of
        this width can need, so that one program serves every part."""
        if self._vision_jit is None:
            return (), []
        if not any(req.video is not None for _, req, _, _ in spans):
            return (None,), []
        import jax.numpy as jnp

        index = np.full((rows, width), -1, np.int32)
        positions = np.broadcast_to(
            np.arange(width, dtype=np.int32), (rows, 3, width)).copy()
        delta = np.zeros((rows,), np.int32)
        results, towers = [], []   # [(first frame, device rows [per, tpf, D])]
        most = 0
        for j, req, first, n in spans:
            video = req.video
            if video is None:
                continue
            positions[j, :, :n] = video["positions"][:, first:first + n]
            delta[j] = video["delta"]
            per, tpf = self._frames_a_call(video), video["tpf"]
            grid = video["grid"][1:]
            most = max(most, 1 + -(-(width // tpf + 1) // per))
            mine = [video["last"]] if video["last"] is not None else []
            for base in video["due"].pop(0):
                chunk = video["patches"][base:base + per]
                if len(chunk) < per:  # past the video's end
                    chunk = np.concatenate([chunk, np.zeros(
                        (per - len(chunk), *chunk.shape[1:]), np.uint8)])
                out, mark = self._vision_jit(
                    self.params, jnp.asarray(chunk), grid=grid)
                mark.copy_to_host_async()
                towers.append((mark, req))
                mine.append((base, out))
            if mine:
                video["last"] = mine[-1]
            if video["planned"] >= video["grid"][0] and not any(video["due"]):
                video["patches"] = None  # every frame is on the device
            shape = (per, tpf, self.cfg.d_model)
            lo, hi = self._video_rows(video, first, n)
            if hi <= lo:  # (a part of text after the video's last row)
                continue
            frame, within = np.divmod(np.arange(lo, hi), tpf)
            mine_index = index[j, video["first"] + lo - first:
                               video["first"] + hi - first]
            for base, out in mine:
                here = (frame >= base) & (frame < base + per)
                mine_index[here] = (len(results) * per * tpf
                                    + (frame[here] - base) * tpf + within[here])
                results.append(out)
            assert (mine_index >= 0).all(), (
                "a frame no tower call encoded", first, n)
        while len(results) < max(most, 1):
            results.append(self._tower_zeros(shape))
        return ({"rows": tuple(results), "index": jnp.asarray(index),
                 "positions": jnp.asarray(positions),
                 "delta": jnp.asarray(delta)},), towers

    def _tower_zeros(self, shape: tuple):
        """A tower result's worth of zeros on the device (kept a shape)."""
        import jax.numpy as jnp

        if shape not in self._vision_zeros:
            self._vision_zeros[shape] = jnp.zeros(shape, self.cfg.dtype)
        return self._vision_zeros[shape]

    def _final(self, rows: int, batch, ends: bool) -> tuple:
        """A prefill call's last argument for a family that shares one slab
        (none for any other): which of its ``rows`` END a prompt, i.e. run the
        layers above the slab (``ends``: the call is no prompt's earlier
        part).  Counts the call's prompt positions and the positions it runs
        through those layers."""
        if self._shared is None:
            return ()
        import jax.numpy as jnp

        final = np.zeros((rows,), bool)
        final[:len(batch)] = ends
        with self._lock:
            self._cache_tiles["yoco_upper_positions"] += int(final.sum())
        return (jnp.asarray(final),)

    def _part_call(self, slot: int, req: _Request, first: int, n: int):
        """Dispatch one PART of ``req``'s prompt, its tokens ``[first, first +
        n)``, into ``slot``: one row, a part wide (ONE compiled program
        whatever ``first`` is: the offset is a runtime value).  The part that
        ends the prompt samples its first token as a whole call does; any
        other admits nobody to the chunk, and hands the drain where its row
        stands instead of first tokens.  A FIRST part, where a part is one of
        the buckets, is that bucket's own program over the prompt's first
        tokens (a slot from scratch: it reads nothing of the cache, where the
        part program places its rows among the slot's and goes one trip: 7.5 ms
        more at offset 0; measured: the constant's comment)."""
        import jax.numpy as jnp

        if first == 0 and self._part in self._rows:
            return self._prefill_call(self._part, [(slot, req)], first_part=True)
        toks = np.zeros((1, self._part), np.int32)
        toks[0, :n] = req.tokens[first:first + n]
        if first == 0:
            self._stamp_dispatch([req])
        slots_dev = jnp.asarray(np.array([slot], np.int32))
        visual, towers = self._visual([(0, req, first, n)], 1, self._part)
        last_logits, self.cache, routed_dev, stands_dev = self._part_jit(
            self.params, jnp.asarray(toks),
            jnp.asarray(np.array([n], np.int32)), self.cache, slots_dev,
            jnp.asarray(np.array([first], np.int32)),
            *self._final(1, [(slot, req)], first + n >= len(req.tokens)),
            *visual)
        if first + n < len(req.tokens):
            stands_dev.copy_to_host_async()
            _to_host_async(routed_dev)
            return [], stands_dev, routed_dev, self._part, towers
        firsts_dev = self._first_tokens(
            last_logits, slots_dev, routed_dev, [req])
        return [(0, slot, req)], firsts_dev, routed_dev, self._part, towers

    def step(self) -> bool:
        """One engine iteration, software-pipelined against the device:

        1. admit queued prompts into free slots (as many narrow prefill
           calls as the queue's head, the free slots and the tick's token
           budget allow; no readback).  A prompt longer than one part goes
           in PARTS, one a tick while any row decodes (``_admit``), and its
           slot joins the chunks when its last part has gone
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
        with self._annotate("engine.tick"):
            return self._tick(self._ticks if _events.ENABLED else None)

    @contextlib.contextmanager
    def _locked(self):
        """``with self._lock`` on the engine thread, the WAIT for it under a
        name of its own in a device trace (submitters and ``perf_stats()``
        take the same lock)."""
        with self._annotate("engine.lock_wait"):
            self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    def _tick(self, meter: Optional[_TickMeter]) -> bool:
        import jax.numpy as jnp

        # with the meter: the thread's clocks by kind of time where a host
        # phase begins (``tracing.thread_clocks``), and the phase itself
        # published for the sampler (``StallRecorder.now``)
        if meter:
            c_tick0 = tracing.thread_clocks()
            meter.host.now = ("admit", c_tick0[0])
        with self._annotate("engine.admit"):
            prefills = self._admit()
        if meter:
            c_admitted = tracing.thread_clocks()
            meter.host.now = ("dispatch", c_admitted[0])
        with self._locked():
            # (a slot-holder with nothing scheduled is mid-prefill)
            rows = [(i, s) for i, s in enumerate(self._slots)
                    if s is not None and s.scheduled]
        dispatched = None
        if prefills and not rows:
            # parts of a prompt that is not all in yet, and nobody to decode
            dispatched = _PendingChunk(
                None, 0, rows, prefills, None,
                chained=self._pending is not None)
        if rows:
            with self._annotate("engine.decode_dispatch"):
                active = np.zeros((self.n_slots + 1,), bool)  # scratch inactive
                active[[i for i, _ in rows]] = True
                # the steps this chunk runs: up to where the nearest live
                # request ends (one with nothing left, its answer being its
                # prefill's token, is freed below and counts for nothing)
                left = [req.max_new - req.scheduled for _, req in rows]
                n = min([self.chunk] + [m for m in left if m > 0])
                # a slot stands at prompt + scheduled - 1 (the last sampled
                # token is not in the cache yet); EOS, which only the
                # device has seen, can only leave it lower
                stands = [len(req.tokens) + req.scheduled - 1 for _, req in rows]
                if self._compact:
                    # ... and to where the nearest live slot's window ends:
                    # the roll-over runs with the flush (a row that EOS
                    # stopped stands lower and is nobody's answer any more)
                    window = self._compact[0]
                    ends = min(window - at % window for at in stands)
                    self._cache_tiles["eva_window_cuts"] += ends < n
                    n = min(n, ends)
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
            with self._locked():
                if self._compact:
                    stands = self._count_compacting(stands, n)
                self._cache_tiles["read_full"] += sum(
                    -(-at // self._tile) for at in stands)
                # the chunk's flush writes, a full layer a tensor, the tile
                # a dispatched row's columns fall in, and the next where
                # the ``n`` it ran cross into it; a row that sits the chunk
                # out, none
                self._cache_tiles["flushed"] += sum(
                    1 + (at % self._tile > self._tile - n) for at in stands)
                if self._layers["window"]:
                    held = (self.n_slots + 1) * self._ring_tiles
                    self._cache_tiles["held_window"] += held
                    self._cache_tiles["read_window"] += sum(
                        min(-(-at // self._tile), self._ring_tiles)
                        for at in stands) if self._ring_by_tile else held
                self._cache_tiles["padded"] += (
                    (self.n_slots + 1) * self._slab_tiles)
                if self._shared is not None:
                    self._count_shared(stands, n)
                for i, req in rows:
                    req.scheduled = min(req.max_new, req.scheduled + n)
                    if req.scheduled >= req.max_new:
                        self._slots[i] = None
        if meter:
            c_dispatched = tracing.thread_clocks()
            meter.host.now = ("drain_book", c_dispatched[0])
        prev, self._pending = self._pending, dispatched
        waited = tracing.NO_CLOCKS
        if prev is not None:
            self._draining = prev  # visible to _loop's error recovery
            with self._annotate("engine.drain"):
                waited = self._drain(prev, meter)
            self._draining = None
        worked = dispatched is not None or prev is not None
        if meter:
            meter.host.now = None
            if worked:
                between, c_end = tracing.clocks_between, tracing.thread_clocks()
                drain = between(c_dispatched, c_end)
                meter.tick_host(
                    between(c_tick0, c_admitted),
                    between(c_admitted, c_dispatched),
                    tuple(d - w for d, w in zip(drain, waited)),
                    began=c_tick0[0], cpu_now=c_end[1], rows=len(rows),
                    steps=dispatched.steps if dispatched else 0,
                    prefill_calls=len(prefills))
        return worked

    def _count_shared(self, stands, n: int) -> None:
        """A dispatched chunk of ``n`` steps of a family that shares one slab,
        rows at positions ``stands``: the ``yoco_*`` counters (``__init__``;
        call under the lock)."""
        tally, tile = self._cache_tiles, self._tile
        tally["yoco_slab_tile_steps"] += n * self._slab_readers * sum(
            -(-at // tile) for at in stands)
        tally["yoco_ring_tile_steps"] += n * self._layers["window"] * sum(
            min(-(-at // tile), self._ring_tiles) for at in stands
        ) if self._ring_by_tile else n * self._layers["window"] * (
            self.n_slots + 1) * self._ring_tiles
        tally["yoco_state_row_steps"] += n * self._layers.get("state", 0) * (
            len(stands) if self._state_kernel else self.n_slots + 1)
        tally["yoco_row_steps"] += n * len(stands)
        tally["yoco_steps"] += n
        tally["yoco_dispatches"] += 1

    def _count_compacting(self, stands, n: int):
        """A dispatched chunk of ``n`` steps of a compacting family, rows at
        positions ``stands``: the counters (``__init__``; call under the
        lock).  Returns where the rows stand IN THEIR WINDOWS, which is what
        the flush's tiles are counted from."""
        window, chunk = self._compact
        tile, tally = self._tile, self._cache_tiles
        places = [at % window for at in stands]
        rows = [at // window * (window // chunk) for at in stands]
        near = sum(-(-p // tile) for p in places)
        far = sum(-(-r // tile) for r in rows)
        tally["eva_window_tiles"] += near
        tally["eva_summary_tiles"] += far
        tally["eva_tile_steps"] += n * (near + far)
        tally["eva_row_steps"] += n * len(stands)
        # a step reads the tiles and the chunk's own columns up to its own;
        # its query may attend its window up to itself and every summary row
        own = n * (n + 1) // 2 * len(stands)
        tally["eva_read_positions"] += n * (near + far) * tile + own
        tally["eva_attendable_positions"] += (
            n * (sum(places) + sum(rows)) + own)
        rolled = sum((p + n) % window == 0 for p in places)
        tally["eva_rollovers"] += rolled
        tally["eva_chunks_pooled"] += rolled * (window // chunk)
        tally["eva_steps"] += n
        tally["eva_dispatches"] += 1
        # (a summary tile is a tile of read_full too)
        tally["read_full"] += far
        return places

    def _compaction_stats(self) -> Optional[Dict[str, Any]]:
        """``perf_stats()["eva"]`` (call under the lock); None for a family
        that compacts nothing."""
        if not self._compact:
            return None
        return {"window": self._compact[0], "chunk": self._compact[1],
                **{k[4:]: v for k, v in self._cache_tiles.items()
                   if k.startswith("eva_")}}

    def _count_routed(self, phase: str, counts, steps: int = 0,
                      padded: int = 0) -> None:
        """Add one landed dispatch's routing counts (a prefill call's with the
        ``padded`` tokens it was wide, or a chunk's with the ``steps`` it ran;
        None where the family counts nothing) to the totals.  A prefill call
        also counts the rows its grouped matmuls were handed
        (``rows_computed``; the held pairs, ``tokens``, over it is how full
        the dispatch was) and the loop trips they took (``trips``): host
        arithmetic over what the call returned, by the function the device's
        loop takes its trips from."""
        if counts is None:
            return
        counts = {k: np.asarray(v, np.int64) for k, v in counts.items()}
        if padded:
            from ray_tpu.ops.moe import dispatch_trips

            held = counts["tokens"].sum(-1)  # a sparse layer each
            pairs = padded * self.cfg.experts_per_token
            block, trips = dispatch_trips(
                pairs, held, getattr(self.cfg, "all_experts_held", False))
            counts["rows_computed"] = np.broadcast_to(
                block * trips, held.shape).sum()
            # (a block that is all the pairs went at once: no loop ran)
            counts["trips"] = np.broadcast_to(
                trips if block < pairs else 0, held.shape).sum()
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

    def _read_back(self, dev, meter: Optional[_TickMeter]) -> tuple:
        """``np.asarray(dev)`` (blocks until the device has produced it), the
        instant it returned, and what the thread's clocks moved by while it
        blocked (``tracing.clocks_between``: the wait is by design, so its
        seconds and its voluntary switch stay out of the tick's host phases,
        and the sampler is told no phase is on); the wait carries its own
        annotation, so that in a device trace a gap under ``engine.drain`` and
        not under it is the drain's bookkeeping."""
        before = tracing.thread_clocks()
        if meter:
            meter.host.now = None
        with self._annotate("engine.drain_wait"):
            host = np.asarray(dev)
        after = tracing.thread_clocks()
        if meter:
            meter.host.now = ("drain_book", after[0])
        return host, after[0], tracing.clocks_between(before, after)

    def _drain(self, pending: _PendingChunk,
               meter: Optional[_TickMeter] = None) -> tuple:
        """Materialize one landed chunk: route first tokens + chunk rows to
        their requests, resolve futures, confirm EOS slot frees.  With a
        ``meter`` (the observability layer is on) every stamp is the instant
        the tokens it times were on the host, and the tick's record is made;
        returns what the thread's clocks moved by while blocked in the reads."""
        waited = tracing.NO_CLOCKS
        if meter is not None:
            meter.begin(pending.chained)
        for admissions, firsts_dev, routed_dev, padded, towers in pending.prefills:
            for mark, req in towers:  # the tower calls ahead of this call
                _, landed, blocked = self._read_back(mark, meter)
                waited = tuple(map(operator.add, waited, blocked))
                if meter is not None:
                    req.vision_s += meter.vision_landed(landed)
            firsts, landed, blocked = self._read_back(firsts_dev, meter)
            waited = tuple(map(operator.add, waited, blocked))
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
                        if req.video is not None:
                            tracing.emit_stage(
                                "engine.vision_encode", req.vision_s,
                                req.trace_ctx)
                # after the stamps: the stream thread reads them the
                # moment it sees the token
                req.emitted.append(int(firsts[j]))
            if admissions:
                # a call's first tokens' wake, BEFORE the next read blocks:
                # they were on the host a call, or a whole chunk, ahead of
                # what the drain reads next
                self._wake_streams()
            self._count_routed("prefill", routed_dev, padded=padded)
        if pending.chunk_dev is None:  # a tick of parts alone: no chunk
            if meter is not None:
                meter.chunk_landed(landed, 0, 0)
            return waited
        # the transfer is already in flight
        chunk, landed, blocked = self._read_back(pending.chunk_dev, meter)
        waited = tuple(map(operator.add, waited, blocked))
        if meter is not None:
            meter.chunk_landed(
                landed, sum(len(adm) for adm, *_ in pending.prefills),
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
        self._wake_streams()  # the chunk rows' wake, after the futures
        return waited

    def _emit_done(self, slot: int, req: _Request,
                   meter: Optional[_TickMeter]) -> None:
        """A request's once-only emissions when it ends: the ``request
        complete`` event (the whole submit->completion window as a span of
        the submitter's trace) and, under it, ``engine.decode``: first token
        on the host -> last token on the host."""
        n = len(req.emitted)
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
                    top_k: int = 0, eos_id: Optional[int] = None,
                    part_bound: Optional[int] = None):
    """The engine's device programs, ``(prefill, decode_chunk, the cut decode
    chunk, the prefill of a prompt's part)``: one prefill per prompt bucket
    (compiled lazily), the whole chunk of ``decode_chunk_steps``, the same
    steps under a runtime bound (a sixth argument, ``n``: one program whatever
    it is), and ONE program for every part of every long prompt (a sixth
    argument, the rows' offsets: one program whatever they are; it reads a
    slot's first ``part_bound`` cached positions at most; None: an engine that
    splits no prompt has none).  cfg is closed over (hashable frozen
    dataclass).  A function of the config and the bound alone, so the compile
    test (``tests/test_chip_compile.py``) lowers exactly what a replica
    runs."""
    import jax

    from ray_tpu.models import generate as gen

    def llm_prefill(params, toks, lens, cache, slots):
        logits, cache = gen.prefill_at(params, cfg, toks, lens, cache, slots)
        # the dispatch's routing counts come out beside the donated cache, so
        # that the host can read them (None: the family counts nothing)
        return logits, cache, cache.pop("routed", None)

    if gen.shared_cache(cfg) is not None:
        # a family that shares one slab: a further argument, the rows that END
        # a prompt (a first part through a bucket's own program ends none)
        def llm_prefill(params, toks, lens, cache, slots, final):  # noqa: F811
            logits, cache = gen.prefill_at(params, cfg, toks, lens, cache,
                                           slots, final=final)
            return logits, cache, cache.pop("routed", None)

    if gen.rope_offset(cfg):
        # a family with a tower in front: a further argument, ``visual`` (the
        # tower's rows, where they stand, every token's rotary position, the
        # slots' offsets; None: token ids alone, which is another program)
        def llm_prefill(params, toks, lens, cache, slots, visual):  # noqa: F811
            logits, cache = gen.prefill_at(params, cfg, toks, lens, cache,
                                           slots, visual=visual)
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

    # ``jit_llm_prefill_part``: a trace's readers sum the prefill programs by
    # the name they share; beside a whole call's results, where the rows stand
    # now (what a part that samples no token is read back by)
    def llm_prefill_part(params, toks, lens, cache, slots, offsets):
        logits, cache = gen.prefill_at(params, cfg, toks, lens, cache, slots,
                                       offsets, part_bound)
        return logits, cache, cache.pop("routed", None), cache["pos"][slots]

    if gen.shared_cache(cfg) is not None:
        def llm_prefill_part(params, toks, lens, cache, slots, offsets,  # noqa: F811
                             final):
            logits, cache = gen.prefill_at(params, cfg, toks, lens, cache,
                                           slots, offsets, part_bound, final)
            return logits, cache, cache.pop("routed", None), cache["pos"][slots]

    if gen.rope_offset(cfg):
        def llm_prefill_part(params, toks, lens, cache, slots, offsets,  # noqa: F811
                             visual):
            logits, cache = gen.prefill_at(params, cfg, toks, lens, cache,
                                           slots, offsets, part_bound,
                                           visual=visual)
            return logits, cache, cache.pop("routed", None), cache["pos"][slots]

    part = None if part_bound is None else jax.jit(
        llm_prefill_part, donate_argnums=(3,))
    return prefill, decode, jax.jit(llm_decode_cut, donate_argnums=(1,)), part


def vision_program(cfg):
    """The tower call of a family with a vision tower in front of its text
    path (its module has ``encode_video``; None for any other family):
    ``jit_llm_vision_encode(params, patches [frames, patches a frame, values]
    uint8, grid=(rows, columns))`` -> ``(rows [frames, rows a frame, D], one
    value of them)``: one program a frame grid and frames a call; the single
    value is what the drain reads for the call's landing.  A function of the
    config alone, as :func:`engine_programs`."""
    import jax

    from ray_tpu.models import generate as gen

    fam = gen.family_of(cfg)
    if not hasattr(fam, "encode_video"):
        return None

    def llm_vision_encode(params, patches, grid):
        rows = fam.encode_video(params, cfg, patches, grid)
        return rows, rows[0, 0, 0].astype("float32")

    return jax.jit(llm_vision_encode, static_argnames=("grid",))


def _base64_in_pieces(data, piece: int = 1 << 18) -> np.ndarray:
    """``data`` (base64 as str, bytes or a memoryview of them) -> its bytes as
    uint8, decoded ``piece`` characters at a time.  A video's patches are tens
    of megabytes, and ONE ``a2b_base64`` over them holds the interpreter lock
    for a tenth of a second and more, which the engine thread, in the same
    process, then waits out between two decode chunks (every live stream's
    pace); between two pieces the lock can change hands."""
    import binascii

    if isinstance(data, str):
        data = data.encode("ascii")
    out = bytearray(len(data) // 4 * 3 + 3)
    n = 0
    for lo in range(0, len(data), piece):
        part = binascii.a2b_base64(data[lo:lo + piece], strict_mode=True)
        out[n:n + len(part)] = part
        n += len(part)
    return np.frombuffer(out, np.uint8, n)


def _json_beside_patches(body: bytes):
    """A request's JSON body -> ``(the request, parsed)`` with the VALUE of
    its ``"patches"`` key (a string of base64: no quote, no escape inside)
    left out of the parse and put back as a memoryview of the body's own
    bytes: ``json.loads`` over a 48 MB string is another tenth of a second
    under the interpreter lock (:func:`_base64_in_pieces`).  A body without
    the key, or one this cannot split, is parsed whole."""
    import json

    key = body.find(b'"patches"')
    first = body.find(b'"', body.find(b":", key + 9) + 1) if key >= 0 else -1
    last = body.find(b'"', first + 1) if first >= 0 else -1
    if last < 0:
        return json.loads(body or b"null")
    request = json.loads(body[:first + 1] + body[last:])
    if isinstance(request, dict) and isinstance(request.get("video"), dict) \
            and request["video"].get("patches") == "":
        request["video"]["patches"] = memoryview(body)[first + 1:last]
        return request
    return json.loads(body)


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
            self.params = gen.serving_layout(
                self.cfg, _default_init(self.cfg, seed))
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
            self.params = gen.serving_layout(
                self.cfg, _default_init(self.cfg, seed))

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
    the engine replacing the plain forward).  What a request may carry:
    ``LLMServer.__call__``."""
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
            "stream": bool, "video": {"grid": [F, gh, gw], "patches":
            base64 of uint8 [F x gh x gw, patch values]}} -> {"tokens":
            generated ids}, or a token-per-line StreamingResponse when
            ``stream`` is set.  ``video``: a family with a vision tower only;
            its ``F x gh/2 x gw/2`` rows stand where ``tokens`` hold the video
            placeholder id; without it a request is token ids alone.  Unknown
            keys are ignored.  A malformed body (``tokens`` no list of ints, a
            negative ``max_new_tokens``, a ``video`` without ``grid``, a
            placeholder count that is not the grid's, an odd grid, ...) is
            refused HERE, in the caller's thread, before anything is queued:
            over HTTP a 400 whose ``error`` names the fault, through a handle
            a ``ValueError`` (``GenerationEngine.checked``; counted in
            ``stats()["refused"]``).  Blocks this replica thread; the engine
            interleaves all in-flight requests between chunks."""
            from ray_tpu.serve._private.http_util import Request as _HttpReq
            from ray_tpu.serve._private.http_util import Response as _HttpRes

            over_http = isinstance(request, _HttpReq)
            try:
                if over_http:
                    try:
                        request = _json_beside_patches(request.body or b"")
                    except ValueError:
                        raise RequestRefused("body", "the body is no JSON") from None
                if isinstance(request, (list, tuple)):
                    request = {"tokens": list(request)}
                if not isinstance(request, dict):
                    raise RequestRefused("body", "the request is no object")
                args = self.engine.checked(
                    request.get("tokens"), request.get("max_new_tokens"),
                    request.get("video"))
            except RequestRefused as e:
                if over_http:
                    return _HttpRes({"error": str(e)}, status_code=400)
                raise
            if request.get("stream"):
                from ray_tpu import serve as _serve

                gen = self.engine.stream(*args[:2], video=args[2], ready=True)
                return _serve.StreamingResponse(
                    (f"{t}\n" for t in gen), content_type="text/plain")
            return {"tokens": self.engine.generate(
                *args[:2], video=args[2], ready=True)}

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
