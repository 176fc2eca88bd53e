"""Run one cell of the benchmark once and print one JSON line last.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name and is data or a small
file of its own (see README.md): ``workloads/<cell>.json`` names a
configuration (``configs/<config>.json``), a traffic mix
(``traffic/<traffic>.json``) and a kind, whose driver is
``drivers/<kind>.py``; with ``--trace 1`` every reader under
``layer_metrics/`` is asked for its metric.  This process never starts a JAX
backend: the chip belongs to the Train worker or the Serve replica.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

T_PROCESS = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


class Context:
    """What a driver and a metric reader get to see of one run."""

    def __init__(self, args):
        self.name = args.workload
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.root, self.t_process = ROOT, T_PROCESS
        self.cell = load_json("workloads", self.name + ".json")
        self.config = load_json("configs", self.cell["config"] + ".json")
        self.traffic = load_json("traffic", self.cell["traffic"] + ".json")
        # a rehearsal cell walks the same code on the CPU with fake chips;
        # it is never listed in BENCHMARK.json and names the CPU as its device
        self.rehearsal = bool(self.cell.get("rehearsal"))
        self.platform = "cpu" if self.rehearsal else "tpu"
        self.out_dir = os.path.join(ROOT, ".bench_out")
        os.makedirs(self.out_dir, exist_ok=True)

    def init_cluster(self, ray_tpu) -> None:
        chips = self.cell["chips"]
        if self.rehearsal:
            ray_tpu.init(num_cpus=8, num_tpus=chips)  # fake chips, by name
        else:
            ray_tpu.init()  # the node must find its own chips
        found = int(ray_tpu.cluster_resources().get("TPU", 0))
        if found < chips:
            ray_tpu.shutdown()
            raise RuntimeError(
                f"ray_tpu.init() found {found} TPU chip(s), the cell needs {chips}")


def declared_metrics(name: str) -> tuple:
    """``(listed, end_to_end, per_layer)``: whether BENCHMARK.json lists
    this cell, and the units of the metrics it declares for it (for a cell
    it does not list, a rehearsal: of every metric)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = name in [w["name"] for w in bench["workloads"]]
    mine = lambda m: not listed or "workloads" not in m or name in m["workloads"]
    return (listed,
            {m["name"]: m["unit"] for m in bench["end_to_end"] if mine(m)},
            {m["name"]: m["unit"] for m in bench["per_layer"] if mine(m)})


def per_layer(ctx: Context, raw: dict) -> dict:
    """Ask every reader under ``layer_metrics/``; one that finds nothing to
    read returns None and its metric is left out of the line."""
    out = {}
    folder = os.path.join(HERE, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + fname[:-3].replace(".", "_").replace("-", "_"),
            os.path.join(folder, fname))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx, raw)
        if value is not None:
            out[fname[:-3]] = (float(value), mod.UNIT)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    # workers import ``benchmark.*`` and the program by name
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
    # one fixed cache inside the checkout, unless the machine names one
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    ctx = Context(args)
    if ctx.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    listed, e2e_units, layer_units = declared_metrics(ctx.name)
    if not listed and not ctx.rehearsal:
        raise SystemExit(f"{ctx.name} is not a cell of BENCHMARK.json")

    driver = importlib.import_module(f"benchmark.drivers.{ctx.cell['kind']}")
    raw = driver.run(ctx)

    from ray_tpu._private.resource_spec import jax_backend_initialized

    if jax_backend_initialized():
        raise RuntimeError("the benchmark's parent process started a JAX backend")
    if raw["device"]["platform"] != ctx.platform:
        raise RuntimeError(f"measured on {raw['device']}, not on a {ctx.platform}")

    if ctx.trace:
        found = per_layer(ctx, raw)
        metrics = {k: {"value": v, "unit": u} for k, (v, u) in found.items()
                   if k in layer_units}
    else:
        metrics = {k: {"value": v, "unit": e2e_units[k]}
                   for k, v in raw["end_to_end"].items() if k in e2e_units}
    device = dict(raw["device"])
    line = {"correct": raw["correct"], "attempted": raw["attempted"],
            "failed": raw["failed"], "metrics": metrics, "device": device}
    if ctx.trace and raw.get("trace"):
        device["busy_s"] = raw["trace"]["busy_s"]
        device["window_s"] = raw["trace"]["window_s"]
        line["breakdown"] = {"device_ops": raw["trace"]["device_ops"],
                             "idle_gaps": raw["trace"]["idle_gaps"]}
        line["modules"] = raw["trace"].get("modules")
    line.update({"workload": ctx.name, "seed": ctx.seed,
                 "seconds": ctx.seconds, "detail": raw.get("detail", {}),
                 "checks": raw.get("checks", {})})
    # the earlier line: cold or cached set-up, and what each first call cost
    print(json.dumps({"setup_detail": raw.get("warmup", {})}), flush=True)
    print(json.dumps(line), flush=True)
    return 0


def live_children() -> list:
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


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 — no result line, a non-zero exit
        import traceback

        traceback.print_exc()
        code = 1
    # every process this run started has ended before it returns (a killed
    # chip holder takes seconds to be gone); one left over is killed and
    # fails the run
    for _ in range(300):
        left = live_children()
        if not left:
            break
        time.sleep(0.1)
    for pid in left:
        import signal

        print(f"process {pid} was left running; killed", file=sys.stderr)
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
        code = code or 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # nothing may print after the result line
