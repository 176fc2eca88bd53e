"""Node resource detection, with TPU chips first-class.

Analog of ``python/ray/_private/resource_spec.py`` — its
``_autodetect_num_gpus`` (``resource_spec.py:268``) counts GPUs; here we
autodetect **TPU chips** instead, per SURVEY §2.1's TPU-port note.  A v5e
host hands each chip to user space as one VFIO group, ``/dev/vfio/<n>``
(one on the one-chip machine, four on the 2x2 host), so counting those
finds the chips without loading libtpu — the head must never touch the
device.  A ``TPU_VISIBLE_CHIPS`` restriction on the node's own
environment is honored the way the reference honors
``CUDA_VISIBLE_DEVICES``.

This module is also the one seat of the per-process device environment
(:func:`chip_env`): which variables make a worker own exactly the chips it
was granted, and which keep a worker that was granted none off the device.
"""

from __future__ import annotations

import glob
import os
import sys
from typing import Dict, List, Optional, Tuple


def autodetect_tpus() -> Tuple[int, List[int]]:
    """(chip count, chip ids) from one consistent source — the count and the
    id list must never disagree (the ids become TPU_VISIBLE_CHIPS grants)."""
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        ids = [int(c) for c in visible.split(",") if c.strip()]
        return len(ids), ids
    # libtpu numbers the host's chips 0..n-1 whatever the group numbers are
    # (the one-chip machine exposes /dev/vfio/1 and calls its chip 0)
    n = len(glob.glob("/dev/vfio/[0-9]*"))
    return n, list(range(n))


# libtpu's view of the host, for a process that owns only part of it: the
# chips form a (x, y, z) block.  Without these a process given one chip of
# a 2x2 host still claims the whole host and the second one fails on
# libtpu's lockfile.  Shapes are the ones a v5e host can be cut into.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


def chip_env(tpu_ids: Optional[List[int]]) -> Dict[str, str]:
    """Environment that decides which device a worker process may claim.

    ``None`` — the worker was granted no chip: JAX is held to the CPU, so
    nothing it runs (a rollout worker importing jax, a ``map_batches`` UDF)
    can take the chip from the worker that was granted it.  A list of chip
    ids — the process owns exactly those chips: the visible-chips list plus
    the bounds that tell libtpu the process is a slice of that shape."""
    if tpu_ids is None:
        return {"JAX_PLATFORMS": "cpu"}
    ids = ",".join(str(i) for i in sorted(tpu_ids))
    env = {"TPU_VISIBLE_CHIPS": ids, "RAY_TPU_ASSIGNED_TPUS": ids}
    bounds = _CHIP_BOUNDS.get(len(tpu_ids))
    if bounds is not None:
        env["TPU_CHIPS_PER_HOST_BOUNDS"] = bounds
        env["TPU_HOST_BOUNDS"] = "1,1,1"
    return env


def jax_backend_initialized() -> bool:
    """Has THIS process started a JAX backend (and so, on a chip host,
    claimed the device)?  Importing jax does not; the first array does.
    Drivers and other chipless processes assert this stays False."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def autodetect_resources(
    num_cpus: Optional[int],
    num_tpus: Optional[int],
    resources: Optional[Dict[str, float]],
) -> Tuple[Dict[str, float], List[int]]:
    """Returns (resource totals, tpu chip ids)."""
    total: Dict[str, float] = dict(resources or {})
    total["CPU"] = float(num_cpus if num_cpus is not None else os.cpu_count() or 1)
    if num_tpus is not None:
        n_tpus, ids = num_tpus, list(range(num_tpus))
    else:
        n_tpus, ids = autodetect_tpus()
    total["TPU"] = float(n_tpus)
    try:
        import psutil  # type: ignore

        total.setdefault("memory", float(psutil.virtual_memory().available))
    except Exception:
        total.setdefault("memory", 8.0 * 1024**3)
    return total, ids


def host_stats() -> Dict[str, float]:
    """Live host utilization for node heartbeats (the per-node metrics
    the reference's dashboard agent reports,
    ``dashboard/modules/reporter/reporter_agent.py:253``).  /proc reads
    only — no psutil dependency on the hot heartbeat path."""
    stats: Dict[str, float] = {"cpu_count": float(os.cpu_count() or 1)}
    try:
        with open("/proc/loadavg") as f:
            stats["load_1m"] = float(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        pass
    try:
        mem: Dict[str, int] = {}
        with open("/proc/meminfo") as f:
            for line in f:
                k, _, rest = line.partition(":")
                if k in ("MemTotal", "MemAvailable"):
                    mem[k] = int(rest.split()[0])  # kB
        if mem:
            stats["mem_total_mb"] = round(mem.get("MemTotal", 0) / 1024, 1)
            stats["mem_available_mb"] = round(
                mem.get("MemAvailable", 0) / 1024, 1)
    except (OSError, ValueError):
        pass
    return stats


# ---------------------------------------------------------------------------
# per-process resource sampling (reporter_agent's per-worker stats analog)
# ---------------------------------------------------------------------------

# gauge names the sampler emits; the head's top view, the TSDB trend rules
# (doctor RSS-growth), and the Grafana factory all key off these
PROC_RSS_MB = "ray_tpu_proc_rss_mb"
PROC_CPU_PCT = "ray_tpu_proc_cpu_percent"
PROC_OPEN_FDS = "ray_tpu_proc_open_fds"

_PROC_METRIC_HELP = {
    PROC_RSS_MB: "resident set size per tracked process (MB)",
    PROC_CPU_PCT: "CPU utilization per tracked process (%)",
    PROC_OPEN_FDS: "open file descriptors per tracked process",
}


class ProcSampler:
    """Reads RSS, CPU%, and open-fd counts for a set of pids from /proc.

    CPU% needs a delta between consecutive samples (utime+stime ticks over
    wall time), so one sampler instance persists across a sampling loop's
    lifetime; pids that vanish between samples simply drop out.  /proc
    only — no psutil on a 5 s always-on path."""

    def __init__(self):
        self._prev: Dict[int, Tuple[float, float]] = {}  # pid -> (ticks_s, t)
        try:
            self._hz = float(os.sysconf("SC_CLK_TCK")) or 100.0
        except (ValueError, OSError, AttributeError):
            self._hz = 100.0
        self._page_kb = (os.sysconf("SC_PAGE_SIZE") // 1024
                         if hasattr(os, "sysconf") else 4)

    def sample(self, pid: int) -> Optional[Dict[str, float]]:
        """One process's stats, or None when the pid is gone."""
        import time as _time

        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
        except OSError:
            self._prev.pop(pid, None)
            return None
        # comm may contain spaces/parens: fields start after the LAST ')'
        fields = stat[stat.rfind(")") + 2:].split()
        # fields[11]=utime, fields[12]=stime (0-based after comm/state),
        # fields[21]=rss pages
        try:
            cpu_s = (float(fields[11]) + float(fields[12])) / self._hz
            rss_mb = float(fields[21]) * self._page_kb / 1024.0
        except (IndexError, ValueError):
            return None
        now = _time.monotonic()
        cpu_pct = 0.0
        prev = self._prev.get(pid)
        if prev is not None and now > prev[1]:
            cpu_pct = max(0.0, (cpu_s - prev[0]) / (now - prev[1]) * 100.0)
        self._prev[pid] = (cpu_s, now)
        out = {"rss_mb": round(rss_mb, 2), "cpu_pct": round(cpu_pct, 2)}
        try:
            out["open_fds"] = float(len(os.listdir(f"/proc/{pid}/fd")))
        except OSError:
            pass
        return out

    def forget_missing(self, live_pids) -> None:
        """Drop CPU baselines for pids no longer tracked."""
        live = set(live_pids)
        for pid in [p for p in self._prev if p not in live]:
            del self._prev[p]


def resource_metrics_snapshot(sampler: ProcSampler,
                              entities: List[Tuple[Dict[str, str], int]],
                              ) -> Tuple[Dict[str, dict], List[tuple]]:
    """Sample ``entities`` ((tags, pid) pairs) into a registry-snapshot-
    shaped dict, so the result rides the existing ``metrics_report`` path
    and folds into the head's merged registry AND its TSDB unchanged.
    Also returns the per-entity raw stats as (tags, pid, stats) for
    callers that keep a live cache (the head's top view)."""
    values_by_metric: Dict[str, Dict[tuple, float]] = {
        PROC_RSS_MB: {}, PROC_CPU_PCT: {}, PROC_OPEN_FDS: {}}
    raw: List[Tuple[Dict[str, str], int, Dict[str, float]]] = []
    seen_pids = []
    for tags, pid in entities:
        stats = sampler.sample(pid)
        if stats is None:
            continue
        seen_pids.append(pid)
        key = tuple(sorted({**tags, "pid": str(pid)}.items()))
        values_by_metric[PROC_RSS_MB][key] = stats["rss_mb"]
        values_by_metric[PROC_CPU_PCT][key] = stats["cpu_pct"]
        if "open_fds" in stats:
            values_by_metric[PROC_OPEN_FDS][key] = stats["open_fds"]
        raw.append((tags, pid, stats))
    sampler.forget_missing(seen_pids)
    snap = {
        name: {"type": "gauge", "help": _PROC_METRIC_HELP[name],
               "values": values}
        for name, values in values_by_metric.items() if values
    }
    return snap, raw
