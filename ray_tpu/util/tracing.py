"""Distributed trace-context propagation across task/actor boundaries.

Analog of the reference's ``python/ray/util/tracing/tracing_helper.py``
(monkey-patched remote calls inject OpenTelemetry span contexts into task
metadata; workers resume the trace).  Here propagation is first-class
instead of patched on: when tracing is enabled, every task spec carries the
submitter's trace context, the executing worker adopts it for the duration
of the task (so nested submissions chain), and the head records it on
TaskInfo — ``ray_tpu timeline`` then emits chrome-trace flow arrows linking
parents to children.  If the OpenTelemetry SDK is importable, real spans
are started as well (the reference's lazy-import pattern).

Beyond task specs, the context crosses every runtime boundary: serve HTTP
ingress opens a root trace per request, the router's admission wait becomes
a child span the replica task chains under, compiled-graph ``execute()``
rides the channel payloads (``dag/compiled.py`` ``_Traced``) so per-node
loop spans join the caller's trace, the streaming pump adopts its
consumer's context, and long ``ray_tpu.get`` waits emit ``get_wait``
spans.  Timed spans land in the flight recorder (``_private/events.py``)
under the ``trace`` source, so shipping to the head, crash-dump JSONL, and
the chrome-trace merge all come for free; the head folds them into a
per-trace :class:`~ray_tpu._private.events.TraceTable` served by
``experimental.state.api.get_trace`` / ``ray_tpu trace <id>``.

Also here, because a stalled host phase is the one thing no span names: the
process's garbage-collection pauses (:func:`listen_gc`), a thread's clocks by
kind of time (:func:`thread_clocks`), and :class:`StallRecorder`, which sums
what a recurring period (an engine tick, a train loop's step) cost its thread
by kind of time and keeps the record of the few that took far longer than the
rest.  ``serve/llm.py``'s tick meter and ``air/session.py`` both report to it.

Presence of a context IS the enable signal: outside any ``trace()`` block
nothing is recorded and task specs stay clean, so the disabled path costs
one contextvar read per submission.

Contexts carry wall-clock start times (``t``, and the root's as ``t_root``),
so a stage that crosses a process boundary is computed by the RECEIVER from
the context alone (:func:`since`).  Every closed span is also folded, by
``phase``, into a per-process aggregate (:func:`span_stats`): the one
emission feeds both the per-request tree and the numbers a process reports
about itself (``GenerationEngine.perf_stats()["stages"]``).
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import os
import resource
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence

from ray_tpu._private import events as _events
from ray_tpu._private import log_plane as _log_plane

# flight-recorder source for span events (one row per closed span)
TRACE_SOURCE = "trace"

_current: contextvars.ContextVar[Optional[Dict[str, str]]] = contextvars.ContextVar(
    "ray_tpu_trace", default=None
)


def _ctx_set(ctx):
    """``_current.set`` + log-plane stamp-cache invalidation: every line
    a thread prints while a context is active must carry its trace id."""
    token = _current.set(ctx)
    _log_plane.bump_context_epoch()
    return token


def _ctx_reset(token):
    _current.reset(token)
    _log_plane.bump_context_epoch()

# --- id generation --------------------------------------------------------
# NOT uuid4 per span: uuid4 reads os.urandom every call, and on this
# kernel one urandom read costs ~200us — per-task span ids at that price
# ate ~30% of task throughput.  Instead: one urandom read per PROCESS
# (22 hex chars of prefix + a random-start counter).  Forked children
# (the forkserver's warm template) re-seed via the at-fork hook instead
# of a per-call getpid() — this kernel charges ~16us per getpid too.
_id_lock = threading.Lock()
_id_prefix = ""
_id_n = 0

# --- per-process span aggregate -------------------------------------------
# phase -> [count, sum_s, reservoir of the last durations]: count and sum
# are cumulative, so after-minus-before is exact for a window; the
# reservoir (bounded like the engine's TTFT deque) backs the percentiles
STATS_RESERVOIR = 4096
# how many of the reservoir's newest durations a row hands out as they
# closed (``recent``): what a reader needs for a percentile over a window
STATS_RECENT = 512
_stats_lock = threading.Lock()
_stats: Dict[str, list] = {}
# cross-process stages that came out negative (the sender's clock ahead of
# the receiver's) and were clamped to 0
_clock_skew = 0


# --- the process's garbage collections --------------------------------------
# summed by ONE ``gc.callbacks`` entry a process (:func:`listen_gc`).  A
# collection holds the GIL from its "start" to its "stop", so every thread of
# the process stands still for it: what a phase of any thread lost to one is
# the difference of ``_gc_pause_s`` over the phase.
GC_RECENT = 32
_gc_pause_s = 0.0
_gc_collections = [0, 0, 0]  # by generation
# (t, generation, seconds), newest last; REPLACED a pause, never mutated, so a
# reader on another thread holds a whole one
_gc_recent: tuple = ()
_gc_began = 0.0
_gc_listening = False


def _reseed_ids() -> None:
    # fresh lock too: the fork may have happened while another thread of
    # the parent held _id_lock — the child inherits it locked forever
    global _id_lock, _id_prefix, _id_n, _stats_lock, _clock_skew
    global _gc_pause_s, _gc_recent
    _id_lock = threading.Lock()
    _id_prefix = ""
    _id_n = 0
    # the span aggregate is per process: a forked child starts its own
    _stats_lock = threading.Lock()
    _stats.clear()
    _clock_skew = 0
    # ... and its own collections (the callback itself is inherited)
    _gc_pause_s = 0.0
    _gc_collections[:] = [0, 0, 0]
    _gc_recent = ()


os.register_at_fork(after_in_child=_reseed_ids)


def _next_id() -> int:
    global _id_prefix, _id_n
    with _id_lock:
        if not _id_prefix:
            _id_prefix = os.urandom(11).hex()  # raylint: disable=R3 (one-shot, off the per-task path)
            _id_n = int.from_bytes(os.urandom(5), "big")  # raylint: disable=R3 (one-shot, off the per-task path)
        _id_n += 1
        return _id_n


def new_trace_id() -> str:
    """32 hex chars, globally unique (22-hex process prefix + counter)."""
    n = _next_id()  # first: seeds the prefix for this process
    return _id_prefix + format(n & 0xFFFFFFFFFF, "010x")


def new_span_id() -> str:
    """16 hex chars, unique in-process by counter and cross-process by
    the random prefix + random counter start."""
    n = _next_id()
    return _id_prefix[:6] + format(n & 0xFFFFFFFFFF, "010x")


def current_context() -> Optional[Dict[str, str]]:
    """The active trace context, or None (outside any trace).  Presence of
    a context IS the enable signal — specs stay clean when tracing is
    unused, and workers propagate whenever a spec carries one."""
    return _current.get()


def _make_context(name: str, parent: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """A fresh span context under ``parent`` (a root without one), stamped
    with the wall-clock time of its creation (``t``) and of its root's
    (``t_root``): what a receiving process computes cross-process stages
    from."""
    now = time.time()
    ctx = {
        "trace_id": parent["trace_id"] if parent else new_trace_id(),
        "span_id": new_span_id(),
        "parent_span_id": parent["span_id"] if parent else "",
        "name": name,
        "t": now,
        "t_root": parent.get("t_root", now) if parent else now,
    }
    job = parent.get("job") if parent else _current_job()
    if job:
        # tenant identity rides the context: every span of the trace can
        # be attributed to the submitting job (multi-tenant trace audit)
        ctx["job"] = job
    return ctx


@contextlib.contextmanager
def trace(name: str, attributes: Optional[dict] = None,
          phase: str = "span") -> Iterator[Dict[str, str]]:
    """Open a span.  Tasks submitted inside the block carry its context;
    their workers continue the same trace.  On exit the timed span is
    emitted into the flight recorder (``trace`` source), which is what
    the head's TraceTable assembles per-trace span trees from."""
    ctx = _make_context(name, _current.get())
    token = _ctx_set(ctx)
    otel_cm = _otel_span(name, attributes)
    t0 = time.perf_counter()
    try:
        with otel_cm:
            yield ctx
    finally:
        _ctx_reset(token)
        emit_span(name, time.perf_counter() - t0, ctx, phase=phase,
                  attributes=attributes)


def _current_job() -> Optional[str]:
    """The running process's tenant job id (driver identity or the
    executing task's), for root-span attribution.  Lazy import: tracing
    must stay importable before the worker runtime is."""
    try:
        from ray_tpu._private.worker import global_worker
    except ImportError:
        return None
    return global_worker.current_job_id or global_worker.job_id


def _otel_span(name: str, attributes: Optional[dict]):
    """A real OpenTelemetry span when the SDK is importable, else a no-op
    (``tracing_helper.py:53-59`` lazy import)."""
    try:
        from opentelemetry import trace as otel  # type: ignore
    except ImportError:
        return contextlib.nullcontext()
    tracer = otel.get_tracer("ray_tpu")
    return tracer.start_as_current_span(name, attributes=attributes or {})


def child_context(name: str) -> Optional[Dict[str, Any]]:
    """A fresh span context chained under the caller's (None when tracing
    is off).  Used for outgoing task specs, router admissions, compiled
    ``execute()`` payloads — anything that continues the trace in another
    process."""
    parent = current_context()
    if parent is None:
        return None
    return _make_context(name, parent)


def root_context(name: str) -> Dict[str, Any]:
    """A root context that is NOT made current: for work that must be
    traceable though its caller brought no context (the LLM engine's
    requests from a plain ``DeploymentHandle`` caller)."""
    return _make_context(name, None)


# outgoing-task alias kept for the original call sites (worker.py)
def child_context_for_task(task_name: str) -> Optional[Dict[str, str]]:
    """Context to embed in an outgoing task spec: a fresh span chained
    under the caller's (None when tracing is off — specs stay clean)."""
    return child_context(task_name)


def adopt(ctx: Optional[Dict[str, str]]) -> Any:
    """Make ``ctx`` the current context on this thread (the executing
    worker resuming a submitter's trace).  Returns a token for
    :func:`restore`; pass None to clear (a pooled worker must not leak
    the previous task's context)."""
    return _ctx_set(ctx)


def restore(token: Any) -> None:
    """Undo a matching :func:`adopt` (public inverse — callers must not
    reach into the module's contextvar)."""
    _ctx_reset(token)


# attribute keys that would collide with emit parameters or span lineage;
# user attributes with these names are prefixed, never dropped or crashed on
_RESERVED_KEYS = frozenset((
    "source", "message", "severity", "entity_id", "span_dur",
    "trace_id", "span_id", "parent_span_id", "phase", "name",
))


def span_fields(ctx: Optional[Dict[str, str]], phase: str,
                span_id: Optional[str] = None) -> Dict[str, str]:
    """Span-lineage kwargs for a raw ``events.emit``: a fresh child span
    of ``ctx`` (or the explicit ``span_id``).  {} without a context, so
    call sites can splat it unconditionally."""
    if ctx is None:
        return {}
    return {"trace_id": ctx["trace_id"],
            "span_id": span_id or new_span_id(),
            "parent_span_id": ctx["span_id"], "phase": phase}


def emit_span(name: str, dur_s: float, ctx: Optional[Dict[str, str]],
              phase: str = "span", severity: str = "DEBUG",
              attributes: Optional[dict] = None, ts: Optional[float] = None,
              **data) -> None:
    """Record one closed span [now - dur_s, now] in the flight recorder
    (``[ts - dur_s, ts]`` for a span emitted after the fact: ``ts`` is its
    wall-clock end), tagged with its trace lineage so the head's TraceTable
    can assemble the tree.  No-op without a context or with the
    observability layer disabled — callers can invoke it unconditionally.
    User attribute keys shadowing span/emit fields are prefixed ``attr_``
    instead of crashing or clobbering the lineage."""
    if ctx is None or not _events.ENABLED:
        return
    merged = dict(attributes or ())
    merged.update(data)
    if ctx.get("job"):
        merged.setdefault("job", ctx["job"])
    safe = {(f"attr_{k}" if k in _RESERVED_KEYS else k): v
            for k, v in merged.items()}
    _events.emit(
        TRACE_SOURCE, name, severity=severity, entity_id=ctx["trace_id"],
        span_dur=dur_s, ts=ts, trace_id=ctx["trace_id"],
        span_id=ctx["span_id"],
        parent_span_id=ctx.get("parent_span_id", ""), phase=phase, **safe)
    fold(phase, dur_s)


def fold(phase: str, dur_s: float) -> None:
    """Add one closed span to this process's aggregate without drawing it
    in any tree (``emit_span`` does both).  Nothing with the observability
    layer disabled."""
    if not _events.ENABLED:
        return
    with _stats_lock:
        row = _stats.get(phase)
        if row is None:
            row = _stats[phase] = [0, 0.0, deque(maxlen=STATS_RESERVOIR)]
        row[0] += 1
        row[1] += dur_s
        row[2].append(dur_s)


def span_stats(phases: Optional[Iterable[str]] = None) -> Dict[str, dict]:
    """``{phase: {count, sum_s, p50_s, p95_s, recent}}`` of the spans this
    process closed (every phase seen, or those of ``phases`` that were).
    ``count`` and ``sum_s`` are cumulative since the process started; the
    percentiles are over the last ``STATS_RESERVOIR`` spans of the phase;
    ``recent`` is the newest ``STATS_RECENT`` of those durations in the
    order they closed, so a reader that differences ``count`` over a window
    takes that many off its end and has the window's own durations."""
    wanted = None if phases is None else set(phases)
    with _stats_lock:
        rows = {p: (r[0], r[1], list(r[2])) for p, r in _stats.items()
                if wanted is None or p in wanted}
    out = {}
    for p, (count, sum_s, samples) in rows.items():
        recent = samples[-STATS_RECENT:]
        samples.sort()
        n = len(samples)
        out[p] = {"count": count, "sum_s": sum_s,
                  "p50_s": samples[n // 2],
                  "p95_s": samples[min(n - 1, int(n * 0.95))],
                  "recent": recent}
    return out


def since(t: float, now: Optional[float] = None) -> float:
    """Seconds from a context's wall-clock stamp ``t`` (another process's
    ``time.time()``) to ``now`` on this process's clock: how the receiver
    times a stage that crossed a process boundary.  A negative reading is
    clock skew, not time: clamped to 0 and counted (:func:`clock_skew`)."""
    global _clock_skew
    d = (time.time() if now is None else now) - t
    if d < 0:
        with _stats_lock:
            _clock_skew += 1
        return 0.0
    return d


def clock_skew() -> int:
    """How many cross-process stages this process clamped to 0."""
    return _clock_skew


def emit_stage(phase: str, dur_s: float,
               parent: Optional[Dict[str, Any]], ts: Optional[float] = None,
               **data) -> None:
    """One closed stage [now - dur_s, now] (or ending at wall-clock ``ts``),
    named by its phase, as a fresh child span of ``parent``.  No-op without
    a parent context."""
    if parent is None or not _events.ENABLED:
        return
    ctx = {"trace_id": parent["trace_id"], "span_id": new_span_id(),
           "parent_span_id": parent["span_id"]}
    if parent.get("job"):
        ctx["job"] = parent["job"]
    emit_span(phase, dur_s, ctx, phase=phase, ts=ts, **data)


def task_arrived(ctx: Dict[str, Any], now: float) -> Dict[str, Any]:
    """The executing worker's half of a task hop.  Emits ``task.dispatch``
    (``.remote()``, the spec context's ``t``, to ``now``, when the worker
    reaches the task) and returns the context to adopt: the same one
    carrying ``now`` as ``t_exec``, so that whatever stage comes next
    starts exactly where this one ended."""
    t = ctx.get("t")
    if t is None:  # a hand-made context carries no clock
        return ctx
    emit_stage("task.dispatch", since(t, now), ctx)
    return dict(ctx, t_exec=now)


@contextlib.contextmanager
def span(name: str, phase: str = "span", **data) -> Iterator[Optional[dict]]:
    """Child-span context manager: times the block and emits it as a child
    of the current context.  Unlike :func:`trace` it never STARTS a trace
    — outside any context it is a pure no-op (no uuid, no event)."""
    ctx = child_context(name)
    if ctx is None:
        yield None
        return
    token = _ctx_set(ctx)
    t0 = time.perf_counter()
    try:
        yield ctx
    finally:
        _ctx_reset(token)
        emit_span(name, time.perf_counter() - t0, ctx, phase=phase, **data)


# --- what a period cost its thread, by kind of time -------------------------

def _on_gc(phase: str, info: dict) -> None:
    global _gc_pause_s, _gc_began, _gc_recent
    if not _events.ENABLED:
        return
    if phase == "start":
        _gc_began = time.perf_counter()
    elif _gc_began:
        pause = time.perf_counter() - _gc_began
        _gc_began = 0.0
        _gc_pause_s += pause
        _gc_collections[info["generation"]] += 1
        _gc_recent = (_gc_recent + (
            (time.time(), info["generation"], pause),))[-GC_RECENT:]


def listen_gc() -> None:
    """Start summing this process's garbage collections (once a process;
    nothing is summed with the observability layer off)."""
    global _gc_listening
    if not _gc_listening:
        _gc_listening = True
        gc.callbacks.append(_on_gc)


def gc_stats() -> Dict[str, Any]:
    """Collections by generation, their pause seconds (cumulative since
    :func:`listen_gc`) and the newest ``GC_RECENT`` pauses ``(t, generation,
    seconds)``, ``t`` the wall clock at the pause's end."""
    return {"collections": list(_gc_collections),
            "pause_s": _gc_pause_s,
            "recent": [list(p) for p in _gc_recent]}


def thread_clocks() -> tuple:
    """``(wall, cpu, voluntary, involuntary, gc)`` of the CALLING thread:
    ``perf_counter``; the thread's CPU clock (``time.thread_time()``: exact to
    the nanosecond on Linux, where ``getrusage``'s own CPU fields move a timer
    tick at a time); its context switches of both kinds, from
    ``getrusage(RUSAGE_THREAD)`` (a voluntary switch: it blocked, on the GIL,
    a lock, a device read; an involuntary one: the scheduler took its core);
    the process's GC pause seconds so far.  Two reads bracket a phase;
    :func:`clocks_between` differences them.  What the kernel does not keep
    reads 0 and still: a sandboxed kernel may count no switches and move the
    CPU clock a timer tick at a time (the chip machine of PERF.md section 6,
    PR 55).  ~0.8 us a call here (CHANGES.md, PR 55)."""
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return (time.perf_counter(), time.thread_time(), ru.ru_nvcsw,
            ru.ru_nivcsw, _gc_pause_s)


def clocks_between(a: tuple, b: tuple) -> tuple:
    return (b[0] - a[0], b[1] - a[1], b[2] - a[2], b[3] - a[3], b[4] - a[4])


NO_CLOCKS = (0.0, 0.0, 0, 0, 0.0)


def _task_cpu_s(tid: str, tick: int) -> float:
    """One thread's CPU seconds from /proc: the scheduler's own nanoseconds
    (``schedstat``) where the kernel keeps them, else user + system time of
    ``stat``, the 14th and 15th field of the line, which a kernel that
    accounts by timer tick SAMPLES (two threads passing the GIL to and fro
    every 5 ms each read as a whole core here)."""
    try:
        with open(f"/proc/self/task/{tid}/schedstat") as f:
            return int(f.read().split()[0]) / 1e9
    except (OSError, ValueError, IndexError):
        with open(f"/proc/self/task/{tid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / tick


def thread_cpu_by_name() -> Dict[str, float]:
    """CPU seconds of this process's LIVE threads by thread name: /proc's
    count a task (``/proc/self/task/*/``: ``schedstat``, else ``stat``) joined
    to ``threading.enumerate()`` by ``native_id``; threads Python did not
    start (the runtime's, XLA's, the TPU driver's) under one row,
    ``(native)``.  Threads of one name are summed; a thread that has ended is
    in ``process_time()`` and in no row.  A read of /proc a thread: for
    ``perf_stats()``, never for a tick."""
    names = {t.native_id: t.name for t in threading.enumerate()}
    tick = os.sysconf("SC_CLK_TCK")
    out: Dict[str, float] = {}
    try:
        tids = os.listdir("/proc/self/task")
    except OSError:
        return out
    for tid in tids:
        try:
            cpu = _task_cpu_s(tid, tick)
        except (OSError, IndexError, ValueError):
            continue  # it ended between the listing and the read
        name = names.get(int(tid), "(native)")
        out[name] = out.get(name, 0.0) + cpu
    return out


def process_stats(thread: Optional[threading.Thread] = None) -> Dict[str, Any]:
    """What the PROCESS did, read at the call: ``cpu_s`` (every thread's,
    ended ones too), ``engine_thread_cpu_s`` (``thread``'s own CPU clock, None
    without a live thread), ``gc`` (:func:`gc_stats`) and ``threads``
    (:func:`thread_cpu_by_name`)."""
    own = None
    if thread is not None and thread.ident is not None and thread.is_alive():
        try:
            own = time.clock_gettime(time.pthread_getcpuclockid(thread.ident))
        except OSError:
            own = None  # it ended under the read
    return {"cpu_s": time.process_time(), "engine_thread_cpu_s": own,
            "gc": gc_stats(), "threads": thread_cpu_by_name()}


# A period of at least this many times the median so far is SLOW and keeps its
# record: THREE power-of-two buckets above the median's.  Not 4: in XL's cell
# the median tick is a decode-only one of 1 - 2 ms and a tick that dispatches a
# prefill call takes 4 - 8 by design, 7 % of all ticks (my chip runs, PR 55);
# at 8 a healthy run keeps 2 - 4 records, and both stalls on record are far
# above it (45 ms a tick against 7; 2 s).
SLOW_TICK_FACTOR = 8
# ... once the median rests on a few periods (a meter's first ones are the
# warm-up's, each alone on the device)
SLOW_TICK_MIN_COUNT = 8
SLOW_TICKS_KEPT = 32
# at most one "slow tick" event a recorder in this many seconds (the ring
# keeps every record; an event that was held back is counted in the next)
SLOW_EVENT_EVERY_S = 1.0
# host time by power-of-two bucket of milliseconds: <1, 1-2, ... 512-1024,
# >= 1024.  Bucket i's lower edge is 2 ** (i - 1) ms (0 for the first)
HIST_BUCKETS = 12


def hist_bucket(seconds: float) -> int:
    return min(int(seconds * 1e3).bit_length(), HIST_BUCKETS - 1)


def _median_bucket(ticks: Sequence[int]) -> int:
    half, seen = sum(ticks) / 2, 0
    for bucket, n in enumerate(ticks):
        seen += n
        if seen >= half:
            break
    return bucket


# What one involuntary switch is taken to cost the thread it was done to: a
# time slice of the scheduler's under contention (a few ms).  The counts alone
# cannot say: a 300 ms wait for a lock is ONE voluntary switch, and the two
# preemptions that happened around it are no reason to blame the scheduler.
PREEMPTED_SLICE_S = 0.004


def stall_cause(record: Dict[str, Any]) -> str:
    """Which of four causes a slow period's record names, in one word:
    ``gc`` (a collection took half of it or more), ``cpu`` (the thread ran
    long on its core: CPU half of the wall or more), ``preempted`` (off its
    core and the scheduler's doing: its involuntary switches, at
    ``PREEMPTED_SLICE_S`` each, account for half of the time off the core) or
    ``waiting`` (off its core because it blocked: the GIL under another
    thread, which the record's ``lateness_frac`` and ``stacks`` then show, or
    a lock)."""
    wall = sum(record["wall_s"].values())
    cpu = sum(record["cpu_s"].values())
    if record["gc_s"] >= 0.5 * wall > 0:
        return "gc"
    if cpu >= 0.5 * wall:
        return "cpu"
    taken = record["involuntary"] * PREEMPTED_SLICE_S
    return "preempted" if taken >= 0.5 * (wall - cpu) else "waiting"


class StallRecorder:
    """What a recurring period of ONE thread (an engine tick's host phases, a
    train loop's step) cost that thread, by kind of time, cumulative: wall
    seconds, the thread's CPU seconds, context switches of both kinds and GC
    pause seconds, each by the period's ``phases``; the periods' summed wall
    time by power-of-two bucket of milliseconds (``hist_ticks``, ``hist_s``);
    and the records of the newest ``SLOW_TICKS_KEPT`` periods that took
    ``SLOW_TICK_FACTOR`` times the median so far (``slow``; each also ONE
    ``perf`` / ``slow tick`` event, rate-limited).  A SUSTAINED slowdown raises
    the median and fills no ring: it is what the cumulative sums are for.

    Fed by its one thread, no lock; ``snapshot()`` reads are torn-tolerant.
    The same thread publishes the phase it is in (``now``) for the process's
    sampler, which hands back the stacks of every thread while a phase is
    overdue (``caught``; ``sampling_profiler.watch``)."""

    def __init__(self, entity_id: str, phases: Sequence[str],
                 what: str = "tick"):
        self.entity_id = entity_id
        self.phases = tuple(phases)
        self.what = what
        self.count = 0
        self.wall_s = dict.fromkeys(self.phases, 0.0)
        self.cpu_s = dict.fromkeys(self.phases, 0.0)
        self.voluntary = dict.fromkeys(self.phases, 0)
        self.involuntary = dict.fromkeys(self.phases, 0)
        self.gc_s = dict.fromkeys(self.phases, 0.0)
        self.hist_ticks = [0] * HIST_BUCKETS
        self.hist_s = [0.0] * HIST_BUCKETS
        self.slow: tuple = ()  # replaced a record, never mutated
        self.thread_cpu_s = 0.0  # the feeding thread's CPU clock, newest
        self._last_event_t = 0.0
        self._held_back = 0
        # for the sampler: the phase this thread is in and when it began
        # (``(name, perf_counter)`` or None: ONE store a phase, no lock), from
        # what age a phase is overdue, and what the sampler caught
        # (``(began, phase, stacks, lateness_frac)``, appended by its thread)
        self.now: Optional[tuple] = None
        self.threshold_s = float("inf")
        self.caught: List[tuple] = []
        self.sampled: Optional[tuple] = None  # the sampler's: what it burst for

    def add(self, deltas: Sequence[tuple], began: float = 0.0,
            cpu_now: float = 0.0, **facts) -> Optional[dict]:
        """One period ended: ``deltas`` a :func:`clocks_between` tuple a
        phase, ``began`` the ``perf_counter`` it started at, ``cpu_now`` the
        thread's CPU clock at its end.  Returns the period's record if it was
        slow (``facts`` ride in it), else None."""
        total = 0.0
        for phase, (wall, cpu, vol, invol, gc_s) in zip(self.phases, deltas):
            self.wall_s[phase] += wall
            self.cpu_s[phase] += cpu
            self.voluntary[phase] += vol
            self.involuntary[phase] += invol
            self.gc_s[phase] += gc_s
            total += wall
        self.thread_cpu_s = cpu_now
        slow = total >= self.threshold_s  # ... of the periods BEFORE this
        self.count += 1
        bucket = hist_bucket(total)
        self.hist_ticks[bucket] += 1
        self.hist_s[bucket] += total
        if self.count >= SLOW_TICK_MIN_COUNT:
            median = _median_bucket(self.hist_ticks)
            # SLOW_TICK_FACTOR x the lower edge of the median's bucket (half
            # a millisecond for the first, whose edge is 0): with a factor
            # that is a power of two, a bucket's lower edge again
            self.threshold_s = SLOW_TICK_FACTOR * 2.0 ** (median - 1) / 1e3
        caught = self.caught
        if caught:
            self.caught = []
        return self._keep(deltas, total, began, caught, facts) if slow else None

    def _keep(self, deltas, total, began, caught, facts) -> dict:
        now = time.time()
        record = {
            "t": now, "what": self.what,
            "wall_s": {p: d[0] for p, d in zip(self.phases, deltas)},
            "cpu_s": {p: d[1] for p, d in zip(self.phases, deltas)},
            "voluntary": sum(d[2] for d in deltas),
            "involuntary": sum(d[3] for d in deltas),
            "gc_s": sum(d[4] for d in deltas),
            **facts,
            # what the sampler caught of THIS period (an older catch is of a
            # period that was not slow after all: a wait by design)
            "stacks": [{"phase": phase, "lateness_frac": late, "top": top}
                       for t0, phase, top, late in caught
                       if t0 >= began],
        }
        record["cause"] = stall_cause(record)
        self.slow = (self.slow + (record,))[-SLOW_TICKS_KEPT:]
        if now - self._last_event_t < SLOW_EVENT_EVERY_S:
            self._held_back += 1
        else:
            _events.emit("perf", "slow tick", severity="DEBUG",
                         entity_id=self.entity_id, span_dur=total, ts=now,
                         held_back=self._held_back, **record)
            self._last_event_t, self._held_back = now, 0
        return record

    def summary(self) -> Dict[str, Any]:
        """The cumulative sums over the phases with the process's clocks
        beside them, small enough to ride an event: two of them are what
        :func:`host_readings` differences (``ray_tpu perf``'s HOST table)."""
        return {
            "t": time.time(), "count": self.count,
            "wall_s": sum(self.wall_s.values()),
            "cpu_s": sum(self.cpu_s.values()),
            "involuntary": sum(self.involuntary.values()),
            "hist_ticks": list(self.hist_ticks),
            "hist_s": [round(x, 6) for x in self.hist_s],
            "thread_cpu_s": self.thread_cpu_s,
            "process_cpu_s": time.process_time(),
            "gc_pause_s": _gc_pause_s,
        }

    def snapshot(self) -> Dict[str, Any]:
        return {
            "host_s": dict(self.wall_s),
            "host_cpu_s": dict(self.cpu_s),
            "host_switches": {"voluntary": dict(self.voluntary),
                              "involuntary": dict(self.involuntary)},
            "host_gc_s": dict(self.gc_s),
            "host_hist": {"ticks": list(self.hist_ticks),
                          "seconds": list(self.hist_s)},
            "slow_ticks": list(self.slow),
        }


def host_readings(before: Dict[str, Any], after: Dict[str, Any]) -> dict:
    """Between two :meth:`StallRecorder.summary` reads, what the benchmark's
    six host metrics read between two ``perf_stats()`` (PERF.md section 3):
    the worst period's bucket, the seconds in periods two buckets or more
    above the interval's median, the thread's off-core share and preemptions
    a host second, the process's GC pauses and its OTHER threads' CPU as
    shares of the interval.  None for what the interval cannot say."""
    d = lambda k: after[k] - before[k]  # noqa: E731
    ticks = [a - b for a, b in zip(after["hist_ticks"], before["hist_ticks"])]
    secs = [a - b for a, b in zip(after["hist_s"], before["hist_s"])]
    n, wall, dt = sum(ticks), d("wall_s"), d("t")
    out = dict.fromkeys(("tick_host_max_ms", "slow_ticks_s",
                         "thread_offcore_pct", "thread_preempted_per_s",
                         "gc_pause_pct", "other_threads_cpu_pct"))
    if n:
        top = max(i for i, k in enumerate(ticks) if k)
        # the last bucket has no upper edge: its mean period
        out["tick_host_max_ms"] = (2.0 ** top if top < HIST_BUCKETS - 1
                                   else 1e3 * secs[top] / ticks[top])
        out["slow_ticks_s"] = sum(secs[_median_bucket(ticks) + 2:])
    if wall > 0:
        out["thread_offcore_pct"] = 100.0 * (wall - d("cpu_s")) / wall
        out["thread_preempted_per_s"] = d("involuntary") / wall
    if dt > 0:
        out["gc_pause_pct"] = 100.0 * d("gc_pause_s") / dt
        out["other_threads_cpu_pct"] = 100.0 * (
            d("process_cpu_s") - d("thread_cpu_s")) / dt
    return out
