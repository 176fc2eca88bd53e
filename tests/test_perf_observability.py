"""Performance observability: the shared FLOPs/roofline model
(``util/flops.py``), the step profiler's phase attribution + live MFU +
compile-cache accounting (``util/perf.py``), decode-loop attribution in
the serve engine (TTFT/ITL + prefill-interference meter), the four perf
doctor rules, the ``perf_summary`` surfaces (state API / CLI /
dashboard), and the ``profiling.py`` double-start guard.

NOTE on ordering: the cluster-backed healthy-run gate runs BEFORE the
induced-pathology tests in this module (tier-1 runs with
``-p no:randomly``) — the recompile-storm loop deliberately pollutes the
driver's local event ring, and the head folds that ring into
``list_events``.
"""

import io
import json
import os
import time
from contextlib import redirect_stdout

import pytest

import ray_tpu
from ray_tpu._private import events as events_mod
from ray_tpu.util import flops as flops_mod
from ray_tpu.util.perf import CompileTracker, StepProfiler, sample_device_memory


def _wait_for(pred, timeout=30.0, interval=0.1):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# flops model (pure)
# ---------------------------------------------------------------------------

def test_flops_model_shared_with_bench():
    """util/flops.py carries the exact bench formulas: 6N + 12·L·D·T and
    the per-generation peak table; an unknown kind is an error."""
    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny()
    n_params = 123_456
    assert flops_mod.transformer_flops_per_token(
        n_params, cfg.n_layers, cfg.d_model, cfg.max_seq_len) == \
        6 * n_params + 12 * cfg.n_layers * cfg.d_model * cfg.max_seq_len
    assert flops_mod.model_flops_per_token(cfg, n_params) == \
        flops_mod.transformer_flops_per_token(
            n_params, cfg.n_layers, cfg.d_model, cfg.max_seq_len)
    # bench.py re-exports: the two modules can never drift
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    import bench

    assert bench.peak_flops is flops_mod.peak_flops
    assert flops_mod.peak_flops("TPU v4") == 275e12
    assert flops_mod.peak_flops("TPU v5p") == 459e12
    with pytest.raises(KeyError):  # no made-up peak for an unknown kind
        flops_mod.peak_flops("weird accelerator")
    with pytest.raises(KeyError):
        flops_mod.mfu(1000.0, 1e9, "cpu")
    assert flops_mod.mfu(1000.0, 1e9, peak=4e12) == pytest.approx(0.25)
    assert flops_mod.mfu(1000.0, 1e9, "TPU v4") == \
        pytest.approx(1e12 / 275e12)
    assert flops_mod.decode_flops_per_token(n_params) == 2 * n_params


def test_xla_cost_analysis_crosscheck():
    """The analytical matmul count agrees with XLA's own cost analysis
    (the cross-check that keeps the 6N model honest)."""
    import jax
    import jax.numpy as jnp

    m, k, n = 32, 64, 16
    f = jax.jit(lambda a, b: a @ b)
    xla = flops_mod.xla_cost_analysis_flops(
        f, jnp.ones((m, k)), jnp.ones((k, n)))
    if xla is None:
        pytest.skip("backend exposes no cost_analysis")
    assert xla == pytest.approx(2 * m * k * n, rel=0.01)
    # diagnostic contract: bad input degrades to None, never raises
    assert flops_mod.xla_cost_analysis_flops(lambda x: x, 1) is None


# ---------------------------------------------------------------------------
# step profiler (pure-ish; local events + metrics only)
# ---------------------------------------------------------------------------

def test_step_profiler_phases_sum_exactly_to_wall():
    prof = StepProfiler(flops_per_token=1e6, tokens_per_step=100,
                        peak=1e9, hbm_every=1)
    for _ in range(3):
        with prof.step():
            with prof.phase("ingest"):
                time.sleep(0.001)
            with prof.phase("compute"):
                time.sleep(0.005)
    assert prof.summary()["steps"] == 3
    for rec in prof.steps:
        # the exact-sum invariant, per step: explicit phases + the
        # "other" residual == measured wall, to the float
        assert sum(rec["phases"].values()) == rec["wall_s"]
        assert rec["phases"]["ingest"] >= 0.001
        assert rec["phases"]["other"] >= 0.0
        assert rec["mfu"] is not None and rec["mfu"] > 0
    s = prof.summary()
    assert sum(p["s"] for p in s["phases"].values()) == \
        pytest.approx(s["wall_s"], abs=1e-7)
    assert s["mfu"]["mean"] > 0 and s["mfu"]["last"] > 0
    # CPU fallback HBM sample still lands (kind=host_rss, real bytes)
    assert s["hbm"] is not None and s["hbm"]["bytes_in_use"] > 0
    # a phase scope outside any step attributes nowhere (and must not
    # corrupt the next step)
    with prof.phase("ingest"):
        pass
    assert prof.summary()["steps"] == 3


def test_step_profiler_emits_perf_events_and_gauges():
    before = events_mod.buffer().last_seq()
    prof = StepProfiler(flops_per_token=1e6, tokens_per_step=10, peak=1e9)
    with prof.step():
        time.sleep(0.001)
    rows = [r for r in events_mod.local_events()
            if r["source"] == "perf" and r["seq"] > before]
    steps = [r for r in rows if r["message"] == "step phases"]
    assert len(steps) == 1
    d = steps[0]["data"]
    assert d["phases"]["other"] > 0 and d["mfu"] > 0
    assert steps[0]["span_dur"] == pytest.approx(d["wall_s"], abs=1e-6)
    # the MFU gauge is live in the registry (what the head TSDB ingests
    # and the mfu_regression trend rule reads)
    from ray_tpu.util.metrics import registry

    snap = registry().snapshot()
    assert any(v > 0 for v in
               snap["ray_tpu_train_step_mfu"]["values"].values())
    assert "ray_tpu_hbm_bytes_in_use" in snap


def test_wrap_jit_compile_cache_accounting():
    """Hit/miss counters across a forced reshape recompile: same shape =
    hit, new shape = miss with its own signature + compile wall."""
    import jax
    import jax.numpy as jnp

    prof = StepProfiler(hbm_every=0)
    f = prof.wrap_jit(jax.jit(lambda x: x * 2), name="reshape_probe")
    f(jnp.ones((4,)))      # miss (compile)
    f(jnp.ones((4,)))      # hit
    f(jnp.ones((4,)))      # hit
    f(jnp.ones((8,)))      # miss — the forced reshape recompile
    table = {e["fn"]: e for e in prof.summary()["compiles"]}
    e = table["reshape_probe"]
    assert e["misses"] == 2 and e["hits"] == 2
    assert e["n_sigs"] == 2 and len(set(e["signatures"])) == 2
    assert e["compile_s"] > 0
    # the compile events carry the cumulative signature count the
    # recompile-storm doctor rule thresholds on
    compiles = [r for r in events_mod.local_events()
                if r["source"] == "perf" and r["message"] == "jit compile"
                and (r.get("data") or {}).get("fn") == "reshape_probe"]
    assert [c["data"]["n_sigs"] for c in compiles] == [1, 2]
    # a plain callable (no _cache_size) degrades to all-compute
    g = prof.wrap_jit(lambda x: x, name="plain")
    g(1)
    assert {e["fn"]: e for e in prof.summary()["compiles"]}[
        "plain"]["misses"] == 0


def test_collective_phase_bills_into_open_step(monkeypatch):
    """jax_utils.allreduce_grads bills its wall to the active profiler's
    ``collective`` phase — gang sync shows up in the breakdown without
    the train fn instrumenting anything."""
    import numpy as np

    from ray_tpu.train import jax_utils
    from ray_tpu.util import collective

    def fake_allreduce(arr, group_name=None, op="mean"):
        time.sleep(0.003)
        return np.asarray(arr)

    monkeypatch.setattr(collective, "allreduce", fake_allreduce)
    prof = StepProfiler(hbm_every=0).install()
    try:
        with prof.step():
            out = jax_utils.allreduce_grads({"w": np.ones((4,))})
        assert list(out) == ["w"]
        rec = list(prof.steps)[-1]
        assert rec["phases"]["collective"] >= 0.003
        assert sum(rec["phases"].values()) == rec["wall_s"]
    finally:
        prof.uninstall()


def test_profiling_double_start_guard_and_profile_step(tmp_path):
    """profile_trace degrades to a no-op when a trace is already live
    (instead of raising out of XLA), and profile_step arms a one-step
    trace on the active profiler."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.util import profiling

    outer = tmp_path / "outer"
    with profiling.profile_trace(str(outer)):
        # nested start must not raise — the PR-11 guard
        with profiling.profile_trace(str(tmp_path / "inner")):
            jnp.ones(3).block_until_ready()
    assert outer.exists() and any(outer.rglob("*"))
    # no active profiler: arming reports False
    assert profiling.profile_step(str(tmp_path / "none")) is False
    prof = StepProfiler(hbm_every=0).install()
    try:
        stepdir = tmp_path / "one-step"
        assert profiling.profile_step(str(stepdir)) is True
        with prof.step():
            jax.jit(lambda x: x + 1)(jnp.ones(3)).block_until_ready()
        assert stepdir.exists() and any(stepdir.rglob("*"))
        # one-shot: the NEXT step runs untraced
        before = set(stepdir.rglob("*"))
        with prof.step():
            pass
        assert set(stepdir.rglob("*")) == before
    finally:
        prof.uninstall()


# ---------------------------------------------------------------------------
# decode attribution (engine, no cluster)
# ---------------------------------------------------------------------------

def _tiny_engine(**kw):
    from ray_tpu.serve.llm import GenerationEngine, make_config

    kw.setdefault("n_slots", 4)
    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("decode_chunk_steps", 2)
    kw.setdefault("max_new_tokens", 128)
    return GenerationEngine(make_config("gpt2", "tiny"), **kw).start()


def test_ttft_itl_histograms_populated_by_engine_loop():
    eng = _tiny_engine()
    try:
        futs = [eng.submit([1, 2, 3], 12) for _ in range(4)]
        for f in futs:
            assert len(f.result(timeout=120)) == 12
    finally:
        eng.stop()
    ps = eng.perf_stats()
    assert ps["ttft"]["count"] >= 4
    assert ps["ttft"]["p99_s"] >= ps["ttft"]["p50_s"] > 0
    assert ps["itl"]["count"] > 0 and ps["itl"]["p50_s"] > 0
    # the registry histograms feed the TSDB on the same numbers
    from ray_tpu.util.metrics import registry

    snap = registry().snapshot()
    ttft_hist = list(snap["ray_tpu_llm_ttft_s"]["values"].values())[0]
    assert ttft_hist["count"] >= 4


def test_prefill_interference_meter_fires_only_under_interleave():
    # sequential load: a lone request's admission never co-schedules
    # with another slot's decode — the meter must stay at zero
    eng = _tiny_engine()
    try:
        eng.generate([1, 2, 3], 8)
        eng.generate([4, 5], 8)
    finally:
        eng.stop()
    ps = eng.perf_stats()
    assert ps["ticks"]["interleaved"] == 0
    assert ps["interference_s"] == 0.0 and ps["interference_frac"] == 0.0

    # induced interleave: admissions landing while another request is
    # mid-decode bill admission dispatch (+ tick excess) to prefill
    eng = _tiny_engine()
    try:
        eng.generate([1, 2, 3], 4)  # compile outside the measurement
        f1 = eng.submit([1, 2, 3, 4], 128)
        time.sleep(0.03)
        f2 = eng.submit([5, 6, 7], 64)
        time.sleep(0.03)
        f3 = eng.submit([8, 9], 64)
        for f in (f1, f2, f3):
            f.result(timeout=300)
    finally:
        eng.stop()
    ps = eng.perf_stats()
    assert ps["ticks"]["interleaved"] >= 1
    assert ps["interference_s"] > 0
    assert 0 < ps["excess_billed_to_prefill"] <= 1.0
    # stop() flushed the meter as a perf event for the doctor/CLI
    rows = [r for r in events_mod.local_events()
            if r["source"] == "perf"
            and r["message"] == "prefill interference"]
    assert rows and rows[-1]["data"]["interleaved_ticks"] >= 1


# ---------------------------------------------------------------------------
# doctor rules (pure)
# ---------------------------------------------------------------------------

def _storm_events(n_sigs):
    return [{"source": "perf", "message": "jit compile",
             "severity": "DEBUG", "span_dur": 0.2,
             "data": {"fn": "train_step", "signature": f"s{i}",
                      "n_sigs": i + 1, "misses": i + 1, "hits": 3}}
            for i in range(n_sigs)]


def _step_events(n, ingest_frac):
    return [{"source": "perf", "message": "step phases",
             "severity": "DEBUG", "span_dur": 1.0, "entity_id": "rank0",
             "data": {"wall_s": 1.0, "mfu": 0.4,
                      "phases": {"ingest": ingest_frac,
                                 "compute": 1.0 - ingest_frac}}}
            ] * n


def _interference_event(frac, ticks):
    return {"source": "perf", "message": "prefill interference",
            "severity": "DEBUG", "entity_id": "engine-1", "ts": 10.0,
            "data": {"interference_s": frac * 100.0,
                     "interference_frac": frac,
                     "excess_billed_to_prefill": 0.9,
                     "interleaved_ticks": ticks,
                     "decode_only_ticks": 500}}


def test_perf_doctor_rules_fire_on_induced_pathologies():
    from ray_tpu.util import doctor

    # recompile storm: >= RECOMPILE_STORM_SIGS signatures for one fn
    f = doctor.diagnose(_storm_events(doctor.RECOMPILE_STORM_SIGS))
    assert [x["rule"] for x in f] == ["recompile_storm"]
    assert "train_step" in f[0]["summary"] and f[0]["remedy"]

    # ingest-bound: >= 30% of step wall waiting on data
    f = doctor.diagnose(_step_events(8, 0.5))
    assert [x["rule"] for x in f] == ["ingest_bound"]
    assert "50%" in f[0]["summary"]

    # prefill interference above threshold with enough interleaved ticks
    f = doctor.diagnose([_interference_event(0.45, 60)])
    assert [x["rule"] for x in f] == ["prefill_interference"]
    assert doctor.render(f)  # renders without KeyError

    # combined: all three at once, sorted by severity bucket
    f = doctor.diagnose(_storm_events(9) + _step_events(8, 0.6)
                        + [_interference_event(0.45, 60)])
    assert {x["rule"] for x in f} == {
        "recompile_storm", "ingest_bound", "prefill_interference"}


def test_perf_doctor_rules_stay_silent_on_healthy_runs():
    from ray_tpu.util import doctor

    healthy = (
        # multi-bucket prefill: 4 signatures is the DESIGN, not a storm
        _storm_events(doctor.RECOMPILE_STORM_SIGS - 1)
        # healthy step mix: 10% ingest wait
        + _step_events(20, 0.1)
        # mild interference, and high interference w/o enough ticks
        + [_interference_event(0.05, 500),
           _interference_event(0.9, doctor.PREFILL_MIN_TICKS - 1)])
    assert doctor.diagnose(healthy) == []
    # too few profiled steps: no verdict even at a high ingest share
    assert doctor.diagnose(
        _step_events(doctor.INGEST_MIN_STEPS - 1, 0.9)) == []


def test_mfu_regression_trend_rule():
    from ray_tpu.util import doctor

    def series(vals):
        return {"ray_tpu_train_step_mfu": [
            {"tags": {"rank": "0"}, "points": [[float(i), v]
                                               for i, v in enumerate(vals)]}]}

    # sustained 25% sag over the trailing quarter: fires
    sag = [0.40] * 12 + [0.30] * 4
    f = doctor.diagnose_trends(series(sag))
    assert [x["rule"] for x in f] == ["mfu_regression"]
    assert "regressed" in f[0]["summary"]
    # flat, noisy-flat, short, and CPU-noise-level series stay silent
    assert doctor.diagnose_trends(series([0.40] * 16)) == []
    assert doctor.diagnose_trends(
        series([0.40, 0.41, 0.39, 0.40] * 4)) == []
    assert doctor.diagnose_trends(series([0.4] * 6 + [0.2] * 2)) == []
    assert doctor.diagnose_trends(
        series([0.001] * 12 + [0.0001] * 4)) == []
    assert "ray_tpu_train_step_mfu" in doctor.TREND_METRICS


# ---------------------------------------------------------------------------
# cluster end-to-end.  Order matters (tier-1 runs -p no:randomly): the
# healthy-run doctor gate reads the head's whole perf event table, so it
# runs BEFORE the recompile-storm test pollutes the driver ring.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def perf_cluster():
    env = {"RAY_TPU_METRICS_PUSH_S": "0.25", "RAY_TPU_EVENTS_FLUSH_S": "0.3"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()
    for k, v in old.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _drive_profiler_steps(n=8):
    prof = StepProfiler(flops_per_token=1e6, tokens_per_step=1000,
                        peak=1e9, rank=0)
    for _ in range(n):
        with prof.step():
            with prof.phase("ingest"):
                time.sleep(0.0005)
            with prof.phase("compute"):
                time.sleep(0.005)
    return prof


def test_perf_summary_state_api_cli_and_dashboard(perf_cluster):
    import urllib.request

    from ray_tpu.experimental.state import api as state

    prof = _drive_profiler_steps()
    # the head samples its own registry into the TSDB on the push grid
    assert _wait_for(lambda: any(
        s.get("points")
        for s in state.query_metric("ray_tpu_train_step_mfu",
                                    window_s=600).get("series", [])))
    s = state.perf_summary(window_s=600.0)
    st = s["steps"]
    assert st["count"] >= 8
    assert st["phases"]["ingest"]["s"] > 0
    # the aggregate keeps the exact-sum property (head folds the same
    # per-step dicts the profiler emitted)
    assert sum(p["s"] for p in st["phases"].values()) == \
        pytest.approx(st["wall_s"], abs=1e-4)
    # origin-qualified keys: two gangs' rank0s must not collide
    assert any(k.endswith(":rank0") and v > 0
               for k, v in st["last_mfu"].items()), st["last_mfu"]
    assert s["mfu_trend"] and any(x.get("points") for x in s["mfu_trend"])
    assert any(row.get("bytes_in_use") for row in s["hbm"])

    # CLI renders the breakdown with the sum line
    from ray_tpu.scripts.cli import main as cli_main

    buf = io.StringIO()
    with redirect_stdout(buf):
        cli_main(["perf", "--window", "600"])
    text = buf.getvalue()
    assert "PHASE" in text and "ingest" in text
    assert "phases sum to measured step wall" in text
    assert "live MFU" in text

    # `ray_tpu top` shows the HBM watermark rows
    snap = state.top_snapshot()
    assert any(r.get("bytes_in_use") for r in snap.get("hbm", []))
    from ray_tpu.scripts.cli import _render_top

    assert "DEVICE MEMORY" in _render_top(snap, "cpu")

    # dashboard surface
    from ray_tpu._private.worker import global_worker

    dash = global_worker.node.dashboard
    if dash is None:
        pytest.skip("dashboard disabled in this environment")
    host, port = dash.address
    with urllib.request.urlopen(
            f"http://{host}:{port}/api/perf?window=600", timeout=30) as r:
        payload = json.loads(r.read().decode())
    assert payload["steps"]["count"] >= 8
    assert payload["steps"]["phases"]["ingest"]["s"] > 0
    del prof


def test_healthy_profiled_run_keeps_doctor_clean(perf_cluster):
    """The healthy-run-clean gate, extended to the perf rules: a normal
    profiled workload (one compile, low ingest share, no interference)
    produces ZERO findings from the four new rules."""
    import warnings

    import jax
    import jax.numpy as jnp

    from ray_tpu.experimental.state import api as state
    from ray_tpu.util import doctor

    prof = StepProfiler(flops_per_token=1e6, tokens_per_step=1000,
                        peak=1e9, rank=1)
    f = prof.wrap_jit(jax.jit(lambda x: x * 2), name="healthy_step")
    z = jnp.ones((8,))
    for _ in range(10):
        with prof.step():
            with prof.phase("ingest"):
                time.sleep(0.0002)
            f(z)
            with prof.phase("compute"):
                time.sleep(0.002)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        events = state.list_events(limit=100_000, source="perf")
    assert events, "profiled steps must reach the head's event table"
    findings = doctor.diagnose(events)
    perf_rules = {"recompile_storm", "ingest_bound", "prefill_interference"}
    assert not [x for x in findings if x["rule"] in perf_rules], findings


def test_recompile_storm_flags_through_real_event_pipeline(perf_cluster):
    """A forced-reshape loop drives the REAL compile-tracking pipeline
    past the storm threshold and doctor flags it off the head's event
    table.  Runs LAST in this module: the storm events stay in the
    driver ring afterwards (the healthy gate above already ran)."""
    import warnings

    import jax
    import jax.numpy as jnp

    from ray_tpu.experimental.state import api as state
    from ray_tpu.util import doctor

    prof = StepProfiler(hbm_every=0)
    f = prof.wrap_jit(jax.jit(lambda x: x + 1), name="storm_step")
    for i in range(doctor.RECOMPILE_STORM_SIGS + 1):
        with prof.step():
            f(jnp.ones((i + 1,)))  # every call a fresh shape signature
    table = {e["fn"]: e for e in prof.summary()["compiles"]}
    assert table["storm_step"]["n_sigs"] >= doctor.RECOMPILE_STORM_SIGS

    def storm_visible():
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            events = state.list_events(limit=100_000, source="perf")
        return any(x["rule"] == "recompile_storm"
                   for x in doctor.diagnose(events))

    assert _wait_for(storm_visible)


def test_backend_executor_collects_perf_summaries(perf_cluster):
    """A gang worker's installed profiler is harvestable through
    BackendExecutor.perf_summaries() after the run."""
    from ray_tpu.air import ScalingConfig
    from ray_tpu.train.backend import BackendConfig
    from ray_tpu.train.backend_executor import BackendExecutor

    def train_fn(config=None):
        import time as _t

        from ray_tpu.air import session
        from ray_tpu.train import jax_utils

        prof = jax_utils.step_profiler(
            flops_per_token=1e6, tokens_per_step=100, peak=1e9)
        for _ in range(4):
            with prof.step():
                _t.sleep(0.001)
        session.report({"done": True})

    be = BackendExecutor(
        BackendConfig(),
        ScalingConfig(num_workers=1, resources_per_worker={"CPU": 1}))
    be.start()
    try:
        be.start_training(train_fn)
        while be.get_next_results(timeout=60) is not None:
            pass
        summaries = be.perf_summaries()
        assert len(summaries) == 1 and summaries[0] is not None
        assert summaries[0]["steps"] == 4
        assert sum(p["s"] for p in summaries[0]["phases"].values()) == \
            pytest.approx(summaries[0]["wall_s"], abs=1e-6)
    finally:
        be.shutdown()
    # the gang aggregate landed as a perf event
    rows = [r for r in events_mod.local_events()
            if r["source"] == "perf"
            and r["message"] == "gang perf summary"]
    assert rows and rows[-1]["data"]["profiled_ranks"] == 1


def test_hbm_sample_shapes():
    """memory_stats-less devices fall back to host RSS; a fake device
    with stats reports HBM."""
    s = sample_device_memory()
    assert s is not None and s["bytes_in_use"] > 0
    assert s["kind"] in ("hbm", "host_rss")

    class FakeDev:
        id = 3

        @staticmethod
        def memory_stats():
            return {"bytes_in_use": 100, "bytes_limit": 1000,
                    "peak_bytes_in_use": 500}

    s = sample_device_memory(FakeDev())
    assert s == {"device": "3", "kind": "hbm", "bytes_in_use": 100,
                 "bytes_limit": 1000, "peak_bytes_in_use": 500}
