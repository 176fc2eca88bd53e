"""From a jax profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy union, per-program durations, collective time not
hidden behind compute, the operations that took most time, and the longest
idle gaps named by what the host was doing.

Two steps, so that the arithmetic can be checked on a small recorded trace
without a chip: ``load_events`` turns the profiler's file into plain lists
(``{plane: {line: [[name, start_ns, dur_ns], ...]}}``), ``reduce_events`` is
pure Python over those lists.  Both run in the chip-holding process (the
Train worker, the traced Serve replica), after the window: the benchmark's
parent never imports jax.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast", re.I)
CONTAINER = re.compile(r"^(while|conditional|call)[.\d]*$")
# the least gap worth a name: shorter ones are launch latency between ops
MIN_GAP_NS = 20_000
# only the longest gaps are matched against the host's events (each match
# walks all of them); the rest are summed under one name
NAMED_GAPS = 300
CLOCK_SLACK_NS = 2_000_000
WINDOW = "bench.window"  # the drivers' span around the traced window


def start_trace(trace_dir: str) -> None:
    """``jax.profiler.start_trace`` into an emptied ``trace_dir``, with the
    profiler's python tracer OFF.  It hooks every Python call of the process
    and slows a host-bound engine thread severalfold: the first traced serve
    runs read 48-52 % device idle with it and 0 % without.
    ``ray_tpu.util.profiling.profile_trace`` cannot turn it off, which is
    why it is not used here.  Host events that remain: the benchmark's own
    ``TraceAnnotation`` spans and JAX's ``PjitFunction(...)`` launches."""
    import shutil

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "host" not in name.lower()


def short_name(name: str) -> str:
    """A device op's event is named by its whole HLO text (kilobytes for a
    while loop): keep the instruction's own name, ``%fusion.12 = ...`` ->
    ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def load_events(xplane_path: str, host_lines: bool = True) -> dict:
    """The profiler's file as plain lists.  Device planes keep every line;
    the host plane keeps its thread lines (python tracer and TraceMe
    events), which is what idle gaps are attributed to."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    planes: dict = {}
    for plane in data.planes:
        device = is_device_plane(plane.name)
        if not device and not (host_lines and plane.name.startswith("/host:CPU")):
            continue
        lines = {}
        for line in plane.lines:
            events = [[short_name(e.name) if device else e.name,
                       int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            planes[plane.name] = lines
    return {"planes": planes}


def read_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def merge_intervals(intervals) -> list:
    """Sorted, disjoint ``[start, end]`` pairs covering the same points."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Points of merged ``a`` that no interval of merged ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _name_gap(gap, host_lines) -> str:
    """What the host was doing in ``gap``.  A gap ends when some host thread
    launches the next program, so the thread that matters is the one whose
    ``PjitFunction(...)`` event starts last before the gap's end (the device
    clock and the host's differ by about a millisecond, hence the slack);
    any other thread that happens to be blocked also "covers" the gap and
    says nothing.  On that thread, the most specific event (the shortest)
    covering at least half of the gap names it; the benchmark's own
    ``bench.*`` annotations win over the profiler's python frames."""
    gs, ge = gap
    launcher, latest = None, None
    for key, events in host_lines.items():
        for name, s, _ in events:
            if name.startswith("PjitFunction") and gs < s <= ge + CLOCK_SLACK_NS:
                if latest is None or s > latest:
                    launcher, latest = key, s
    lines = [host_lines[launcher]] if launcher is not None else host_lines.values()
    best = None
    for events in lines:
        for name, s, d in events:
            e = s + d
            if e <= gs or s >= ge or name == WINDOW:
                continue
            cover = min(e, ge) - max(s, gs)
            if 2 * cover < ge - gs:
                continue
            key = (0 if name.startswith("bench.") else 1, d)
            if best is None or key < best[0]:
                best = (key, name)
    return best[1] if best else "(no host event)"


def reduce_events(events: dict, window_ns=None) -> dict:
    """Pure arithmetic over ``load_events``' lists.  Times in seconds.

    ``window_ns`` is ``(start, end)`` of the traced window on the trace's
    clock; left out, it is the span of every event read.  Busy time is
    the union of the operation intervals of a device, clipped to the
    window, averaged over the devices that ran anything."""
    planes = events["planes"]
    dev_names = sorted(p for p in planes if is_device_plane(p))
    # a host event can name a gap only if it covers half of it (or is the
    # launch that ends it)
    host_lines = {
        (p, ln): [ev for ev in evs if 2 * ev[2] >= MIN_GAP_NS
                  or ev[0].startswith("PjitFunction")]
        for p, lines in planes.items() if not is_device_plane(p)
        for ln, evs in lines.items()}
    if window_ns is None:
        starts = [s for lines in planes.values() for evs in lines.values()
                  for _, s, _ in evs]
        ends = [s + d for lines in planes.values() for evs in lines.values()
                for _, s, d in evs]
        if not starts:
            return {"devices": [], "window_s": 0.0, "busy_s": 0.0}
        window_ns = (min(starts), max(ends))
    w0, w1 = window_ns
    clip = lambda iv: [[max(s, w0), min(e, w1)] for s, e in iv
                       if e > w0 and s < w1]

    per_device, op_seconds, modules = [], {}, {}
    first_busy = None
    for p in dev_names:
        lines = planes[p]
        # a loop or call spans its body, whose ops are listed too: left in,
        # it would hide every gap and every exposed collective inside it
        ops = [ev for ev in lines.get(OPS_LINE, [])
               if not CONTAINER.match(ev[0])]
        busy = merge_intervals(clip([[s, s + d] for _, s, d in ops]))
        if not busy:
            continue
        coll = merge_intervals(clip(
            [[s, s + d] for n, s, d in ops if COLLECTIVE.search(n)]))
        compute = merge_intervals(clip(
            [[s, s + d] for n, s, d in ops if not COLLECTIVE.search(n)]))
        exposed = subtract(coll, compute)
        per_device.append({
            "plane": p, "busy_s": total(busy) / 1e9,
            "collective_s": total(coll) / 1e9,
            "collective_exposed_s": total(exposed) / 1e9,
        })
        if first_busy is None:
            first_busy = busy
        for n, s, d in ops:
            if s + d > w0 and s < w1:
                op_seconds[n] = op_seconds.get(n, 0) + d
        for n, s, d in lines.get(MODULES_LINE, []):
            if s >= w0 and s + d <= w1:
                modules.setdefault(re.sub(r"\(\d+\)$", "", n), []).append(d / 1e9)

    n_dev = max(1, len(per_device))
    gaps: dict = {}
    if first_busy:
        edges = [[w0, w0]] + first_busy + [[w1, w1]]
        found = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _)
                        in zip(edges, edges[1:]) if s1 - e0 >= MIN_GAP_NS),
                       reverse=True)
        for i, (length, e0, s1) in enumerate(found):
            name = (_name_gap((e0, s1), host_lines) if i < NAMED_GAPS
                    else "(shorter gaps, not named)")
            gaps[name] = gaps.get(name, 0) + length
    # ops are summed over devices: divide, so that the list reads per device
    top_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": per_device,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device) / n_dev,
        "collective_s": sum(d["collective_s"] for d in per_device) / n_dev,
        "collective_exposed_s":
            sum(d["collective_exposed_s"] for d in per_device) / n_dev,
        "modules": {n: {"count": len(v), "total_s": sum(v),
                        "median_s": sorted(v)[len(v) // 2]}
                    for n, v in modules.items()},
        "device_ops": [[n, s / 1e9 / n_dev] for n, s in top_ops],
        "idle_gaps": [[n, s / 1e9] for n, s in top_gaps],
    }


def program_seconds(trace: dict, name_part: str):
    """Median device time of the most-run program whose XLA module name
    contains ``name_part``, from a reduction; None if there is none."""
    found = [m for name, m in (trace or {}).get("modules", {}).items()
             if name_part in name]
    return max(found, key=lambda m: m["count"])["median_s"] if found else None


def idle_pct(trace: dict):
    """1 - busy union / traced window, in percent; None without a device
    that ran anything."""
    if not trace or not trace.get("devices"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def window_of(events: dict, annotation: str):
    """``(start, end)`` of the host event named ``annotation`` (the drivers
    wrap their traced window in one), or None."""
    for p, lines in events["planes"].items():
        if is_device_plane(p):
            continue
        for evs in lines.values():
            for n, s, d in evs:
                if n == annotation:
                    return (s, s + d)
    return None
