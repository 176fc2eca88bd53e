"""What the SmallThinker train cell adds to the benchmark: its configuration
file against the catalog, the program and the counts; its entries in
BENCHMARK.json; every reader on a hand-made ``raw`` of the new driver (a value
where the program counts, None where it does not, as the parent of the PR that
adds the family does not; this is where ``model.train_mfu_pct``'s
``gpt2_config`` KeyError would show); the rehearsal cell on the CPU."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import flops, flops_smallthinker as fk
from benchmark.drivers import train_family

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "train-smallthinker-21b-a3b-ep4-8k"
REHEARSAL = "rehearse-train-smallthinker-tiny"
NEW = ("model.moe_train_mfu_pct", "model.moe_train_expert_ffn_mfu_pct",
       "moe.train_exchange_share_pct", "moe.train_chip_load_max_over_mean")
SHARED = ("train_tokens_per_s_chip", "train.report_ms", "device.idle_pct.train",
          "collective.exposed_pct")


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(HERE, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CONFIG = load("configs", "smallthinker-21b-a3b-ep4.json")
KW = CONFIG["model_config"]
TINY = load("configs", "tiny-smallthinker.json")["model_config"]
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_files_are_found_by_name():
    cell = load("workloads", CELL + ".json")
    assert cell["config"] == CONFIG["name"] == "smallthinker-21b-a3b-ep4"
    assert cell["traffic"] == "host-batches-8k-b8" and cell["chips"] == 4
    traffic = load("traffic", cell["traffic"] + ".json")
    assert cell["kind"] == traffic["kind"] == "train_family"
    assert (traffic["seq_len"], traffic["batch_size"]) == (8192, 8)
    assert traffic["tokens"] == {"dist": "zipf", "zipf_exponent": 1.1}
    assert os.path.exists(os.path.join(HERE, "drivers", cell["kind"] + ".py"))
    assert cell["mesh"] == {"fsdp": 4} and set(cell["limits"]) == {
        "ce", "aux", "grad_norm_rel", "loss_drop"}
    # weights, ranks and batches are all the seed's, as the issue's cell has it
    assert "fixed_draw" not in cell
    assert cell["optimizer"] == load(
        "workloads", "train-gpt2-xl-fsdp4.json")["optimizer"]
    for key in ("reference_module", "counts_module"):
        assert importlib.util.find_spec(CONFIG[key]) is not None
    for name in NEW:
        assert os.path.exists(os.path.join(HERE, "layer_metrics", name + ".py"))
    tiny = load("workloads", REHEARSAL + ".json")
    assert tiny["kind"] == "train_family" and tiny["rehearsal"]
    assert load("configs", tiny["config"] + ".json")["family"] == "smallthinker"


def test_the_configuration_against_the_catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers", "vocab_size"}


def test_the_configuration_keeps_the_published_widths():
    assert (KW["d_model"], KW["n_heads"], KW["n_kv_heads"], KW["head_dim"],
            KW["n_experts"], KW["d_expert"], KW["experts_per_token"],
            KW["sliding_window"], KW["rope_base"], KW["rms_eps"]) == (
                2560, 28, 4, 128, 64, 768, 6, 4096, 1.5e6, 1e-6)
    assert (CONFIG["moe_num_primary_experts"], CONFIG["num_hidden_layers"],
            CONFIG["vocab_size"]) == (64, 4, 18992) == (
                KW["n_experts"], KW["n_layers"], KW["vocab_size"])
    assert CONFIG["published"]["num_hidden_layers"] == 52
    assert CONFIG["published"]["vocab_size"] == 151936 == 8 * KW["vocab_size"]
    for key in ("router_input", "aux_weight", "rotary", "window", "attention",
                "experts", "dtype", "weights"):
        assert key in CONFIG["assumed"]
    assert "4 chips share each layer" in CONFIG["deployment"]
    assert set(CONFIG["changed"]) == set(CONFIG["reduced"])
    assert set(CONFIG["trace_scopes"]) >= {
        "moe.router", "moe.exchange", "moe.expert_ffn", "attention.full",
        "attention.window", "head_loss"}
    sizes = CONFIG["reference_sizes"]
    assert sizes["rope_layout"] == sizes["sliding_window_layout"] == [0, 1, 1, 1]
    assert (sizes["top_k"], sizes["aux_weight"]) == (6, KW["aux_weight"])


def test_the_program_builds_the_configuration_and_the_counts_agree():
    import jax

    family = train_family.family_of(CONFIG)
    cfg = family.SIZES[CONFIG["size"]](**KW)
    shapes = jax.eval_shape(lambda: family.init(cfg, jax.random.PRNGKey(0)))
    held = sum(int(a.size) for a in jax.tree.leaves(shapes))
    # the issue's arithmetic: 4 x 398,627,840 + 2 x 18,992 x 2,560 + 2,560
    assert held == fk.param_count(KW) == 1_691_752_960
    assert fk.layer_params(KW) == {
        "attention": 20_971_520, "norms": 5_120, "router": 163_840,
        "expert": 5_898_240, "experts": 377_487_360}
    assert fk.active_params(KW) == 274_718_720
    assert fk.layouts(KW) == [(0, 0), (1, 4096), (1, 4096), (1, 4096)]
    assert fk.layouts(KW) == [
        (r, w) for r, w in zip(cfg.rope_layout, cfg.sliding_windows)]
    assert cfg.sliding_windows == (0, 4096, 4096, 4096)
    assert set(family.named_leaves(cfg)) >= {
        "layers.0.router", "layers.3.router", "layers.0.ew_gate_up.0",
        "layers.1.ew_down.17", "layers.2.ew_down.34", "layers.3.ew_down.51",
        "layers.0.wq", "layers.1.wk", "final_norm"}


def test_the_counts_by_hand():
    # the tiny size: attention 32 x 64 + 2 x 32 x 32 + 64 x 32, a router of
    # 32 x 8, experts of 3 x 32 x 24
    assert fk.layer_params(TINY) == {
        "attention": 6144, "norms": 64, "router": 256, "expert": 2304,
        "experts": 18432}
    assert fk.param_count(TINY) == 4 * (6144 + 64 + 256 + 18432) + 2 * 256 * 32 + 32
    assert fk.active_params(TINY) == 4 * (6144 + 256 + 2 * 2304) + 256 * 32
    # a window of 8 over 128 positions: 1..8 keys then 8
    assert fk.mean_attended_keys(128, 8) == (36 + 120 * 8) / 128
    assert fk.mean_attended_keys(128, 0) == 64.5
    assert fk.attention_flops_per_token(TINY, 128) == pytest.approx(
        12 * 64 * (64.5 + 3 * (36 + 960) / 128))
    # the cell's: 2.2 GFLOP a trained token, as the issue counts it
    assert fk.mean_attended_keys(8192, 4096) == 3072.25
    assert fk.train_flops_per_token(KW, 8192) == pytest.approx(
        6 * 274_718_720 + 12 * 3584 * (4096.5 + 3 * 3072.25))
    assert fk.expert_flops_per_pair(KW) == 6 * 5_898_240
    # four chips of two experts: the second holds 6 of 8 pairs in the worst layer
    assert fk.chip_load_max_over_mean(
        [[1, 1, 1, 1, 1, 1, 1, 1], [0, 1, 5, 1, 0, 0, 1, 0]], 4) == 3.0


def test_the_benchmark_names_the_cell_and_its_metrics():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL]["config"] == "smallthinker-21b-a3b-ep4"
    assert cells[CELL]["traffic"] == "host-batches-8k-b8"
    assert cells[CELL]["chips"] == 4
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "smallthinker-21b-a3b-ep4")
    assert config["reduced"] == CONFIG["reduced"]
    assert config["source"] == CONFIG["source"]
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    listed = {name for name, m in metrics.items() if CELL in m.get("workloads", ())}
    assert listed == set(SHARED) | set(NEW)
    assert metrics["model.train_mfu_pct"]["workloads"] == [
        "train-gpt2-medium-1k", "train-gpt2-xl-fsdp4"]
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "train_tokens_per_s_chip"
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) == 2 <= len(BENCH["workloads"]) // 4


def _raw(traced=True, step_module="jit_sparse_lm_step"):
    pairs = [[6144.0] * 64 for _ in range(4)]        # 65,536 x 6 a layer
    raw = {"kind": "train", "batch": 8, "seq": 8192, "steps": 90,
           "n_params": 1_691_752_960, "step_module": step_module,
           "routed_pairs_per_step": pairs, "chip_load_max_over_mean": 1.25,
           "report_s": 0.018, "n_reports": 90,
           "device": {"kind": "TPU v5 lite", "count": 4, "platform": "tpu"},
           "t_chip": 20.0, "t_init": 2.0, "t_window": 100.0,
           "warmup": {"init_state_s": 3.0, "first_step_s": 1.0,
                      "second_step_s": 0.5, "reference_s": 40.0,
                      "step_compile_s": 5.0}}
    if traced:
        raw["trace"] = {
            "devices": [{"plane": f"/device:TPU:{i}"} for i in range(4)],
            "busy_s": 1.47, "window_s": 1.5, "collective_exposed_s": 0.03,
            "traced_steps": 3, "step_runs": 12,
            "modules": {step_module + "(7)": {
                "count": 12, "total_s": 5.88, "median_s": 0.49}},
            "scopes": {"moe.expert_ffn": 2.4, "moe.exchange": 0.3,
                       "attention.window": 0.9}}
    return raw


def test_every_reader_answers_or_returns_none_for_the_new_raw():
    ctx = types.SimpleNamespace(
        config=CONFIG, cell=load("workloads", CELL + ".json"), t_process=0.0)
    raw = _raw()
    peak = flops.peaks("TPU v5 lite")["bf16_flops_per_s"]
    got = {}
    for fname in sorted(os.listdir(os.path.join(HERE, "layer_metrics"))):
        if fname.endswith(".py") and not fname.startswith("_"):
            got[fname[:-3]] = reader(fname[:-3]).read(ctx, raw)   # no raise
    answered = {k for k, v in got.items() if v is not None}
    assert answered == set(NEW) | {
        "train.report_ms", "device.idle_pct.train", "collective.exposed_pct",
        "ownership.chip_ready_s", "ownership.warmup_s"}
    assert got["model.moe_train_mfu_pct"] == pytest.approx(
        100 * fk.train_flops_per_token(KW, 8192) * 65536 / 0.49 / (4 * peak))
    assert 30 < got["model.moe_train_mfu_pct"] < 40
    assert got["model.moe_train_expert_ffn_mfu_pct"] == pytest.approx(
        100 * 6 * 5_898_240 * 4 * 393_216 * 3 / 2.4 / peak)
    assert 0 < got["model.moe_train_expert_ffn_mfu_pct"] < 100
    assert got["moe.train_exchange_share_pct"] == pytest.approx(
        100 * 0.3 / 4 / 1.47)
    assert got["moe.train_chip_load_max_over_mean"] == 1.25
    assert got["collective.exposed_pct"] == pytest.approx(100 * 0.03 / 1.47)
    # untraced: the counter alone
    bare = {n: reader(n).read(ctx, _raw(False)) for n in NEW}
    assert bare == {**dict.fromkeys(NEW), NEW[3]: 1.25}
    # a program without the scopes or the counter (the parent's): nothing
    old = _raw()
    old["trace"]["scopes"] = {}
    del old["routed_pairs_per_step"], old["chip_load_max_over_mean"]
    assert [reader(n).read(ctx, old) for n in NEW[1:]] == [None] * 3
    # another family's configuration and driver: nothing
    gpt2 = types.SimpleNamespace(
        config=load("configs", "gpt2-xl.json"),
        cell=load("workloads", "train-gpt2-xl-fsdp4.json"))
    plain = {k: v for k, v in _raw().items()
             if k not in ("step_module", "routed_pairs_per_step",
                          "chip_load_max_over_mean")}
    del plain["trace"]["scopes"]     # drivers/train.py reads no scope
    assert [reader(n).read(gpt2, plain) for n in NEW] == [None] * 4


def test_why_the_step_carries_a_name_of_its_own():
    """``run.py`` asks EVERY reader in a traced run, and the accepted
    ``model.train_mfu_pct`` (not this PR's to edit) reads
    ``config["gpt2_config"]`` as soon as the trace holds a module whose name
    contains ``train_step``: under that name this configuration's traced runs
    would die of a KeyError.  So the family names its jitted step otherwise."""
    from ray_tpu.models import smallthinker

    ctx = types.SimpleNamespace(
        config=CONFIG, cell=load("workloads", CELL + ".json"))
    with pytest.raises(KeyError, match="gpt2_config"):
        reader("model.train_mfu_pct").read(ctx, _raw(step_module="jit_train_step"))
    assert "train_step" not in smallthinker.STEP_NAME
    assert reader("model.train_mfu_pct").read(
        ctx, _raw(step_module="jit_" + smallthinker.STEP_NAME)) is None


def test_the_limits_are_broken_one_at_a_time():
    limits = {"ce": 0.01, "aux": 0.01, "grad_norm_rel": 0.05}
    ref = {"ce": 10.0, "aux": 4.0, "grad_norms": {"a": 1.0, "b": 0.1}}
    near = {"ce": 10.004, "aux": 4.002, "grad_norms": {"a": 1.01, "b": 0.102}}
    assert train_family.limits_broken(limits, near, ref) == []
    assert train_family.limits_broken(
        limits, {**near, "ce": 10.02}, ref) == ["ce"]
    assert train_family.limits_broken(
        limits, {**near, "aux": 3.98}, ref) == ["aux"]
    assert train_family.limits_broken(
        limits, {**near, "grad_norms": {"a": 1.0, "b": 0.11}}, ref) == [
            "grad_norm_rel"]
    # the control's reading: a forward pass, held by ce and aux alone
    assert train_family.limits_broken(
        limits, near, {"ce": 9.95, "aux": 4.05}) == ["ce", "aux"]


def _tiny_run(steps, lr, half_a_batch=False):
    """The driver's walk by hand on one CPU device at the rehearsal's size:
    ``(losses, first_step, reference)`` as :func:`train_family.verdict` takes
    them.  ``lr`` 0: an update that changes nothing; ``half_a_batch``: the
    program's first step sees the first half of the batch the reference sees."""
    import jax
    import numpy as np

    from benchmark import traffic_gen
    from benchmark.reference import smallthinker_ref as ref
    from ray_tpu.models import smallthinker as st

    tiny = load("configs", "tiny-smallthinker.json")
    cell = load("workloads", REHEARSAL + ".json")
    cfg = st.SIZES["tiny"](**tiny["model_config"])
    optimizer = st.make_optimizer(**{**cell["optimizer"], "lr": lr})
    state = st.init_state(cfg, jax.random.PRNGKey(7), optimizer)
    batches = traffic_gen.HostBatches(
        load("traffic", cell["traffic"] + ".json"), 7, cfg.vocab_size)
    first, kept = batches.next(), {}
    reference = {"grad_norms": ref.grad_norms(
        state["params"], first["inputs"], first["targets"],
        tiny["reference_sizes"], st.named_leaves(cfg), kept=kept),
        "ce": kept["ce"], "aux": kept["aux"]}
    step = jax.jit(st.make_train_step(cfg, optimizer))
    seen = {k: v[:len(v) // 2] for k, v in first.items()} if half_a_batch else first
    losses, first_step = [], None
    for i in range(steps):
        state, m = step(state, seen if i == 0 else batches.next())
        m = jax.device_get(m)
        losses.append(float(m["loss"]))
        first_step = first_step or {
            "ce": float(m["ce"]), "aux": float(m["aux"]),
            "grad_norms": {k: float(v) for k, v in m["grad_norms"].items()}}
    assert np.isfinite(losses).all()
    return losses, first_step, reference


def healthy_losses(checks, n=30):
    """Losses that fall by the healthy run's drop: the other checks' company."""
    return [10.0] * (n // 2) + [10.0 - checks["loss_drop"]] * (n // 2)


def test_a_planted_fault_is_not_correct():
    """What ``correct`` has to refuse, planted: an update that changes nothing
    (every first-step number is computed BEFORE the update, so only the
    losses can show it, and on fresh batches only with a margin) and a step
    that saw half the batch."""
    limits = load("workloads", REHEARSAL + ".json")["limits"]
    healthy = train_family.verdict(limits, *_tiny_run(30, 3e-3))
    assert healthy["correct"] and healthy["loss_drop"] > 4 * limits["loss_drop"]
    unchanged = train_family.verdict(limits, *_tiny_run(30, 0.0))
    assert not unchanged["correct"] and not unchanged["loss_falls"]
    assert unchanged["limits_broken"] == []        # the first step saw nothing
    assert abs(unchanged["loss_drop"]) < limits["loss_drop"] / 4
    losses, first_step, reference = _tiny_run(1, 3e-3, half_a_batch=True)
    halved = train_family.verdict(limits, healthy_losses(healthy), first_step, reference)
    assert not halved["correct"] and "ce" in halved["limits_broken"]


def test_a_program_without_the_family_fails_before_any_process():
    with pytest.raises(SystemExit, match="no model family"):
        train_family.family_of({"family": "no_such_family", "size": "x"})
    with pytest.raises(SystemExit, match="cannot be trained"):
        train_family.family_of({"family": "exaone_moe", "size": "tiny"})


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_cell_runs_on_the_cpu_and_is_correct(trace):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", REHEARSAL,
         "--seed", "3000000019", "--seconds", "4", "--trace", str(trace)],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 10
    checks = line["checks"]
    assert checks["limits_broken"] == [] and checks["loss_falls"]
    assert checks["loss_drop"] > checks["limits"]["loss_drop"]
    assert set(checks["first_step"]["grad_norms"]) == set(
        checks["reference"]["grad_norms"])
    assert line["detail"]["chip_load_max_over_mean"] >= 1.0
    if not trace:
        assert set(line["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
