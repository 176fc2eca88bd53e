"""In-process sampling profilers — the py-spy analog of the reference's
``dashboard/modules/reporter/profile_manager.py``, in two duty cycles:

- :class:`SamplingProfiler`: dense on-demand sampling (the dashboard's
  ``/api/profile`` endpoint and ``RAY_TPU_SAMPLE_PROFILE`` ad-hoc worker
  profiling).  ~1-2% overhead at the default 2 ms period — fine for a
  bounded window.
- :class:`ContinuousProfiler`: the always-on mode.  Short sample bursts
  (~50 ms) every couple of seconds, with the inter-burst interval backing
  off while the process's stacks stay static, keep the duty cycle (and
  therefore the overhead) in the 0.1% range.  Folded stacks are
  time-bucketed and batch-shipped over the control connection to the
  head's :class:`~ray_tpu.util.profile_store.ProfileStore`, so every
  process in the cluster has a queryable flamegraph history by default.

Both sample ``sys._current_frames()`` on a timer thread, aggregating
``file:function`` call stacks across all threads of the process.  Pure
Python and dependency-free (py-spy is not in the image).
"""

from __future__ import annotations

import collections
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

# absolute frame-walk ceiling: stacks deeper than this are pathological
# (runaway recursion) and sampling them whole would make the sampler the
# hot spot the profile reports
_HARD_DEPTH = 128

# mid-stack truncation marker: deep stacks keep their root-most AND
# leaf-most frames around it, so collapsed output still merges at the
# real roots (main/_loop) instead of at fabricated mid-call roots
TRUNCATION_MARKER = "..."


def fold_frame(frame, max_depth: int) -> str:
    """One thread's stack as a ``|``-joined root→leaf frame string.

    ``max_depth`` bounds the OUTPUT, not the walk: the walk always
    reaches the root (up to ``_HARD_DEPTH``), and an over-deep stack is
    truncated in the MIDDLE — root-most frames kept (they name the call
    tree), leaf-most frames kept (they name the hot spot), a ``...``
    marker between.  Truncating leaf→root (the old behaviour) dropped
    the roots of deep stacks, merging unrelated call trees at whatever
    mid-call frame happened to land at the cut."""
    stack: List[str] = []
    f = frame
    while f is not None and len(stack) < _HARD_DEPTH:
        code = f.f_code
        stack.append(f"{code.co_filename.rsplit('/', 1)[-1]}:{code.co_name}")
        f = f.f_back
    stack.reverse()  # walked leaf→root; folded form reads root→leaf
    if len(stack) > max_depth:
        head = max(1, max_depth // 2)
        tail = max(1, max_depth - head - 1)
        stack = stack[:head] + [TRUNCATION_MARKER] + stack[-tail:]
    return "|".join(stack)


def is_idle_leaf(frame) -> bool:
    """True when the frame is parked in a blocking wait (consuming no
    core) — the sampler-side twin of the store's idle classification."""
    from ray_tpu.util.profile_store import _IDLE_LEAF_FILES, _IDLE_LEAF_FUNCS

    code = frame.f_code
    return (code.co_name in _IDLE_LEAF_FUNCS
            or code.co_filename.rsplit("/", 1)[-1] in _IDLE_LEAF_FILES)


def sample_stacks(exclude: frozenset, max_depth: int,
                  counter: "collections.Counter[str]") -> int:
    """One sampling tick: fold every thread's current stack (except the
    excluded sampler threads) into ``counter``.  Returns the number of
    threads caught OFF a blocking wait — the per-tick core-occupancy
    signal behind the duty-cycle ledger's utilization estimate."""
    busy = 0
    for tid, frame in sys._current_frames().items():
        if tid in exclude:
            continue
        if not is_idle_leaf(frame):
            busy += 1
        counter[fold_frame(frame, max_depth)] += 1
    return busy


class SamplingProfiler:
    def __init__(self, period_s: float = 0.002, max_depth: int = 16):
        self.period_s = period_s
        self.max_depth = max_depth
        self.samples: "collections.Counter[str]" = collections.Counter()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "SamplingProfiler":
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="sampling-profiler"
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)
            self._thread = None

    def _loop(self) -> None:
        me = frozenset((threading.get_ident(),))
        while not self._stop.wait(self.period_s):
            sample_stacks(me, self.max_depth, self.samples)

    def report(self, top: int = 40) -> List[Dict]:
        total = sum(self.samples.values()) or 1
        return [
            {"stack": stack, "samples": n, "pct": round(100.0 * n / total, 2)}
            for stack, n in self.samples.most_common(top)
        ]

    def report_text(self, top: int = 40) -> str:
        lines = [f"{r['samples']:6d} {r['pct']:5.1f}%  {r['stack']}"
                 for r in self.report(top)]
        return "\n".join(lines)

    def report_collapsed(self) -> str:
        """Folded-stack lines (``frame;frame;frame N``) — the format
        speedscope and Brendan Gregg's flamegraph.pl consume directly."""
        return collapsed_from_report(
            [{"stack": stack, "samples": n}
             for stack, n in self.samples.most_common()])


def collapsed_from_report(report: List[Dict]) -> str:
    """Convert ``report()``-shaped rows (``{stack, samples, ...}`` —
    what workers ship back over the control connection) into
    folded-stack lines.  The single formatting site for the collapsed
    format."""
    return "\n".join(
        f"{r['stack'].replace('|', ';')} {r['samples']}" for r in report)


def profile_for(duration_s: float, period_s: float = 0.002,
                top: int = 40) -> List[Dict]:
    """Blocking one-shot profile of this process (dashboard endpoint body)."""
    p = SamplingProfiler(period_s=period_s).start()
    time.sleep(duration_s)
    p.stop()
    return p.report(top)


# ---------------------------------------------------------------------------
# always-on continuous mode
# ---------------------------------------------------------------------------

def _env_float(name: str, default: float) -> float:
    import os

    try:
        return float(os.environ.get(name, str(default)))
    except ValueError:
        return default


def continuous_enabled() -> bool:
    """Continuous profiling is ON by default; RAY_TPU_CONT_PROFILE=0
    disables it cluster-wide (the env is inherited by spawned workers)."""
    import os

    return os.environ.get("RAY_TPU_CONT_PROFILE", "1") not in (
        "0", "false", "no")


# --- overdue host phases -----------------------------------------------------
# A thread whose recurring period has host phases (the serve engine's tick:
# ``util.tracing.StallRecorder``) publishes the phase it is in, and the
# process's ONE sampler looks at it between its bursts: a phase that has lasted
# as long as a slow period gets one burst at once, while whatever holds the
# process is still holding it (the period itself is known to be slow only at
# its end, when nothing is left to sample).  Only in a process where somebody
# registered: every other process sleeps as it did.
_WATCHED: List = []
# how often the sampler looks while anything is registered.  A phase is
# overdue from its 10th ms at the earliest, so a 45 ms one is caught whenever a
# look falls in the 35 ms after that: always, at 20 ms; a 2 s one always.  Not
# the 10 ms ISSUE 55 named: on the chip machine (a sandboxed kernel, where a
# timed wait is a timer of the sandbox's own) 100 looks a second cost the
# replica ~10 % of a core and read ~1 % in XL's `tpot_p95_ms` (PERF.md section
# 6, PR 55); on Linux proper a look is ~27 us of CPU
WATCH_EVERY_S = 0.020


def watch(recorder) -> None:
    """``recorder.now`` (``(phase, began)`` or None), ``.threshold_s`` and
    ``.sampled`` are read, ``.caught`` is appended to, by the sampler."""
    if recorder not in _WATCHED:
        _WATCHED.append(recorder)


def unwatch(recorder) -> None:
    if recorder in _WATCHED:
        _WATCHED.remove(recorder)


class ContinuousProfiler:
    """Low-duty-cycle burst sampler with adaptive backoff.

    Every ``interval_s`` it samples for ``burst_s`` at ``period_s``
    (default duty cycle 50ms / 2s = 2.5%, at a 5 ms period — ~0.05% CPU
    given the per-tick cost is ~20-40 us).  When consecutive bursts see
    an identical stack fingerprint (an idle process parked on the same
    waits), the interval doubles up to ``max_interval_s``; any change
    snaps it back — a process that starts working is re-sampled at full
    cadence within one backed-off interval.

    Samples fold into per-``bucket_s`` time buckets; ``ship()`` (called
    from the burst loop every ``ship_every_s``) drains finished buckets
    to ``send_fn`` as a ``profile_report`` control frame, or hands them
    to ``ingest_fn`` directly (the head profiles itself without a
    loopback connection).

    The sampler doubles as the process's GIL-pressure probe: each burst
    compares the wall time its ticks actually took against the schedule
    they asked for.  Tick lateness beyond the timer period means this
    thread sat runnable-but-unscheduled — on a CPython process that is
    GIL wait, and the published ``ray_tpu_gil_lateness_frac`` gauge is
    the "core-bound" number the doctor's ``gil_saturation`` rule reads.
    """

    def __init__(self, origin: str,
                 send_fn: Optional[Callable[[dict], None]] = None,
                 ingest_fn: Optional[Callable] = None, *,
                 burst_s: float = 0.05, interval_s: float = 2.0,
                 period_s: float = 0.005, max_depth: int = 24,
                 bucket_s: float = 60.0, ship_every_s: Optional[float] = None,
                 max_interval_s: Optional[float] = None,
                 closed_fn: Optional[Callable[[], bool]] = None):
        self.origin = origin
        self._send = send_fn
        self._ingest = ingest_fn
        self.burst_s = _env_float("RAY_TPU_CONT_PROFILE_BURST_S", burst_s)
        self.interval_s = _env_float("RAY_TPU_CONT_PROFILE_INTERVAL_S",
                                     interval_s)
        self.period_s = _env_float("RAY_TPU_CONT_PROFILE_PERIOD_S", period_s)
        self.max_depth = max_depth
        self.bucket_s = bucket_s
        if ship_every_s is None:
            from ray_tpu.util.metrics import push_interval_s

            ship_every_s = push_interval_s()
        self.ship_every_s = ship_every_s
        self.max_interval_s = (max_interval_s if max_interval_s is not None
                               else 8 * self.interval_s)
        self._closed = closed_fn
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        # bucket start ts -> Counter[folded stack]
        self._buckets: Dict[float, "collections.Counter[str]"] = {}
        # bucket start ts -> [ticks, busy_ticks]: the per-bucket duty
        # denominators the ledger divides by (a tick is "busy" when at
        # least one thread was caught off a blocking wait — process
        # core-occupancy, immune to GIL-inflated thread counts)
        self._bucket_ticks: Dict[float, List[float]] = {}
        self._ticks = 0          # sampling ticks taken (duty accounting)
        self._cur_interval = self.interval_s
        self._last_fingerprint: Optional[frozenset] = None
        self._static_bursts = 0
        self._last_ship = 0.0
        self._ship_failures = 0
        self.lateness_frac = 0.0  # last burst's GIL-pressure estimate

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "ContinuousProfiler":
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="cont-profiler")
        self._thread.start()
        from ray_tpu._private import events

        events.emit("profile", "continuous profiler started",
                    severity="DEBUG", entity_id=self.origin,
                    burst_s=self.burst_s, interval_s=self.interval_s,
                    period_s=self.period_s)
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        self.ship(final=True)
        from ray_tpu._private import events

        events.emit("profile", "continuous profiler stopped",
                    severity="DEBUG", entity_id=self.origin)

    # -- sampling ----------------------------------------------------------
    def _burst(self, tag: Optional[str] = None,
               live: Optional[Callable[[], bool]] = None):
        """One sampling burst; also refreshes the GIL-lateness estimate.
        ``tag``: a root frame for the burst's stacks (an overdue phase's);
        ``live``: the burst ends when it turns false.  Returns the stacks it
        folded."""
        exclude = frozenset((threading.get_ident(),))
        counter: "collections.Counter[str]" = collections.Counter()
        t0 = time.perf_counter()
        deadline = t0 + self.burst_s
        ticks = 0
        busy_ticks = 0
        while time.perf_counter() < deadline and not self._stop.is_set():
            if sample_stacks(exclude, self.max_depth, counter):
                busy_ticks += 1
            ticks += 1
            self._stop.wait(self.period_s)
            if live is not None and not live():
                break
        elapsed = time.perf_counter() - t0
        if ticks:
            # expected wall for the burst is ticks * period (+ sample
            # bodies, already inside elapsed); the excess is time this
            # thread waited for the GIL / the scheduler
            expected = ticks * self.period_s
            self.lateness_frac = max(
                0.0, min(1.0, (elapsed - expected) / max(elapsed, 1e-9)))
        if not counter:
            return counter
        folded = counter if tag is None else collections.Counter(
            {f"[{tag}]|{stack}": n for stack, n in counter.items()})
        bucket = (time.time() // self.bucket_s) * self.bucket_s
        with self._lock:
            cur = self._buckets.setdefault(bucket, collections.Counter())
            cur.update(folded)
            bt = self._bucket_ticks.setdefault(bucket, [0.0, 0.0])
            bt[0] += ticks
            bt[1] += busy_ticks
            self._ticks += ticks
        if tag is None:  # an overdue phase's burst says nothing of idleness
            self._adapt(counter)
        self._publish_gauges()
        return counter

    def _adapt(self, counter) -> None:
        """Interval backoff: static stacks across bursts double the
        interval (idle process); any change resets it."""
        fp = frozenset(counter)
        if fp == self._last_fingerprint:
            self._static_bursts += 1
            if (self._static_bursts >= 2
                    and self._cur_interval < self.max_interval_s):
                self._cur_interval = min(self.max_interval_s,
                                         self._cur_interval * 2)
                from ray_tpu._private import events

                events.emit("profile", "profiler backoff",
                            severity="DEBUG", entity_id=self.origin,
                            interval_s=self._cur_interval)
        else:
            if self._cur_interval != self.interval_s:
                from ray_tpu._private import events

                events.emit("profile", "profiler backoff reset",
                            severity="DEBUG", entity_id=self.origin)
            self._cur_interval = self.interval_s
            self._static_bursts = 0
        self._last_fingerprint = fp

    def _publish_gauges(self) -> None:
        from ray_tpu.util.metrics import Gauge

        Gauge("ray_tpu_gil_lateness_frac",
              "fraction of the profiler burst wall spent waiting for the "
              "GIL/scheduler (off-GIL pressure estimate)").set(
            round(self.lateness_frac, 4))
        # duty cycle: what share of wall the profiler spends sampling at
        # its CURRENT (backed-off) interval — the overhead meter the
        # grafana profiling row charts
        Gauge("ray_tpu_profiler_duty_frac",
              "profiler sampling duty cycle (burst wall / interval)").set(
            round(self.burst_s / max(self._cur_interval, 1e-9), 5))
        # named-lock wait/hold gauges ride the same publish tick so the
        # lock-timing plane needs no thread of its own
        from ray_tpu._private import locks

        locks.publish_lock_metrics()

    def _sleep(self) -> bool:
        """Wait out the interval between two bursts (True: stopped), looking
        at the watched phases every ``WATCH_EVERY_S`` where there are any."""
        if not _WATCHED:
            return self._stop.wait(self._cur_interval)
        deadline = time.monotonic() + self._cur_interval
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                return self._stop.is_set()
            if self._stop.wait(min(left, WATCH_EVERY_S)):
                return True
            self._sample_overdue()

    def _sample_overdue(self) -> None:
        """ONE burst for each watched phase that is older than its
        recorder's slow threshold (and than two sampling periods: a burst
        shorter than that is no burst), over every thread but this one, for
        as long as the phase lasts and a burst at most; the top five stacks
        and the burst's own lateness (how long this thread, runnable, waited
        for the GIL meanwhile) go to the recorder."""
        from ray_tpu.util.profile_store import classify_stack

        now = time.perf_counter()
        for rec in list(_WATCHED):
            cur = rec.now
            if cur is None or cur is rec.sampled:
                continue
            phase, began = cur
            if now - began < max(rec.threshold_s, 2 * self.period_s):
                continue
            rec.sampled = cur
            try:
                counter = self._burst(
                    tag=f"overdue {phase}", live=lambda: rec.now is cur)
            except Exception:  # noqa: BLE001 — never the host process's end
                continue
            # the top five by samples, the threads caught off a blocking
            # wait first (a replica parks a dozen threads in polls)
            top = sorted(counter.items(), key=lambda kv: (
                classify_stack(kv[0]) == "idle", -kv[1]))[:5]
            rec.caught.append((began, phase, [list(kv) for kv in top],
                               round(self.lateness_frac, 4)))

    def _loop(self) -> None:
        while not self._sleep():
            if self._closed is not None and self._closed():
                return
            try:
                self._burst()
                now = time.monotonic()
                if now - self._last_ship >= self.ship_every_s:
                    self._last_ship = now
                    self.ship()
            except Exception:
                # the profiler must never take its host process down
                pass

    # -- shipping ----------------------------------------------------------
    def drain(self) -> tuple:
        """Take the accumulated buckets + duty meta (resets the rings)."""
        with self._lock:
            buckets_map, self._buckets = self._buckets, {}
            ticks_map, self._bucket_ticks = self._bucket_ticks, {}
            ticks, self._ticks = self._ticks, 0
        buckets = [
            {"ts": ts, "folded": dict(c),
             "ticks": ticks_map.get(ts, [0.0, 0.0])[0],
             "busy_ticks": ticks_map.get(ts, [0.0, 0.0])[1]}
            for ts, c in sorted(buckets_map.items())]
        meta = {"period_s": self.period_s, "burst_s": self.burst_s,
                "interval_s": self._cur_interval, "ticks": ticks,
                "lateness_frac": round(self.lateness_frac, 4)}
        return buckets, meta

    def ship(self, final: bool = False) -> None:
        """Drain buckets to the head (send_fn) or straight into a local
        ProfileStore (ingest_fn).  A failed send drops this batch — the
        next burst re-fills; profiles are advisory, never worth a
        backlog on the control connection."""
        buckets, meta = self.drain()
        if not buckets:
            return
        try:
            if self._ingest is not None:
                self._ingest(self.origin, buckets, meta)
            elif self._send is not None:
                self._send({"type": "profile_report", "origin": self.origin,
                            "buckets": buckets, "meta": meta})
        except Exception:
            self._ship_failures += 1
            if not final:
                from ray_tpu._private import events

                events.emit("profile", "profile ship failed",
                            severity="DEBUG", entity_id=self.origin,
                            failures=self._ship_failures)
