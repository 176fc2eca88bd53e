"""The SmallThinker family as it is TRAINED (``ray_tpu/models/smallthinker.py``)
against the plain reference (``benchmark/reference/smallthinker_ref.py``), at a
tiny size on the CPU: seeded random float32 weights, two layers of each kind,
8 experts top-2, T <= 64; four virtual devices where a mesh is needed.  Also
what the family forced elsewhere: the dropless expert layer that a step
differentiates and spreads over chips (``ops/moe.py``), and the flash pair
under a band (``ops/attention.py``)."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import smallthinker_ref as ref  # noqa: E402
from ray_tpu.models import smallthinker as st  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.parallel.sharding import collective_profile  # noqa: E402

B, T = 2, 32


def sizes_of(cfg):
    return dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        top_k=cfg.experts_per_token, rope_layout=cfg.rope_layout,
        sliding_window_layout=cfg.sliding_window_layout,
        sliding_window=cfg.sliding_window, rope_theta=cfg.rope_base,
        rms_eps=cfg.rms_eps, aux_weight=cfg.aux_weight,
        activation=cfg.activation)


def batch_of(cfg, seed=0, b=B, t=T):
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (b, t + 1)).astype(np.int32)
    return {"inputs": toks[:, :-1], "targets": toks[:, 1:]}


def every_leaf(params):
    found = {}

    def walk(tree, path):
        if isinstance(tree, (dict, list)):
            for k, v in (tree.items() if isinstance(tree, dict) else enumerate(tree)):
                walk(v, path + [k])
        else:
            found[".".join(map(str, path))] = path

    walk(params, [])
    return found


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12))


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:4]), ("fsdp",))


def value_and_grads(cfg, params, batch, mesh=None):
    if mesh is not None:
        rules = st.sharding_rules(mesh)
        params = jax.device_put(params, st.param_shardings(mesh, rules, cfg))
        batch = jax.device_put(batch, NamedSharding(mesh, P("fsdp", None)))
    return jax.jit(jax.value_and_grad(
        lambda p, b: st.loss_fn(p, b, cfg, mesh), has_aux=True))(params, batch)


@pytest.mark.parametrize("activation,remat", [
    ("relu", False), ("silu", False), ("relu", True)],
    ids=["relu", "silu", "relu-layers-replayed"])
def test_loss_and_every_gradient_match_the_reference(activation, remat):
    """ReGLU and SwiGLU through the same grouped matmuls, the whole tree; and
    with every layer replayed in the backward pass under the model's policy
    (the expert block's own backward rule fed by a replay of its forward
    one)."""
    cfg = st.SmallThinkerConfig.tiny(activation=activation, remat=remat)
    params, batch = st.init(cfg, jax.random.PRNGKey(1)), batch_of(cfg)
    (loss, counted), grads = value_and_grads(cfg, params, batch)
    leaves, kept = every_leaf(params), {}
    wanted = ref.grads(params, batch["inputs"], batch["targets"], sizes_of(cfg),
                       leaves, kept=kept)
    ce, aux = kept["ce"], kept["aux"]
    assert float(counted["ce"]) == pytest.approx(ce, rel=2e-4)
    assert float(counted["aux"]) == pytest.approx(aux, rel=2e-4)
    assert float(loss) == pytest.approx(ce + cfg.aux_weight * aux, rel=2e-4)
    assert float(counted["routed_pairs"].sum()) == cfg.n_layers * B * T * 2
    assert np.array_equal(kept["pairs"], np.asarray(counted["routed_pairs"]))
    worst = {name: rel(st.pick(grads, path), wanted[name])
             for name, path in leaves.items()}
    assert max(worst.values()) < 2e-4, max(worst.items(), key=lambda kv: kv[1])


def test_the_four_way_step_is_the_one_device_step(mesh):
    """Loss, every gradient and the routed counts, experts two a device and
    their exchange in the step."""
    cfg = st.SmallThinkerConfig.tiny(n_layers=2)
    params, batch = st.init(cfg, jax.random.PRNGKey(2)), batch_of(cfg, 1, b=4)
    (l1, c1), g1 = value_and_grads(cfg, params, batch)
    (l4, c4), g4 = value_and_grads(cfg, params, batch, mesh)
    assert float(l4) == pytest.approx(float(l1), rel=1e-5)
    assert np.array_equal(np.asarray(c4["routed_pairs"]), np.asarray(c1["routed_pairs"]))
    assert max(rel(a, b) for a, b in zip(
        jax.tree.leaves(g4), jax.tree.leaves(g1))) < 2e-5
    held = g4["layers"][0]["ew_down"].sharding.shard_shape(
        g4["layers"][0]["ew_down"].shape)
    assert held[0] == cfg.n_experts // 4
    text = jax.jit(jax.grad(lambda p, b: st.loss_fn(p, b, cfg, mesh)[0])).lower(
        jax.device_put(params, st.param_shardings(mesh, None, cfg)),
        batch).compile().as_text()
    # the experts are brought whole to a chip's tokens (one gather a matrix);
    # their gradients go home a chip's block at a time, in float32, by three
    # shifts a matrix, and no sum across chips takes a layer's experts whole
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_expert
    profile = collective_profile(text)
    whole = {f"f32[{e},{f},{d}]", f"f32[{e},{d},{2 * f}]"}
    blocks = {f"f32[{e // 4},{f},{d}]", f"f32[{e // 4},{d},{2 * f}]"}
    assert whole <= set(profile["all-gather"]["outside"]["shapes"])
    shifted = profile["collective-permute"]["outside"]
    assert blocks <= set(shifted["shapes"]) and not whole & set(shifted["shapes"])
    assert shifted["count"] == cfg.n_layers * 2 * 3  # layers x matrices x shifts
    assert not any(whole & set(profile[kind]["outside"]["shapes"])
                   for kind in ("reduce-scatter", "all-reduce", "all-to-all"))


def test_the_four_shares_add_up_to_the_uncut_layer(mesh):
    """The guide's share test: what each of four chips computes of a layer
    (its own quarter of the tokens, against the experts of all four) laid end
    to end is the reference's whole layer, and nothing of it is zero."""
    cfg = st.SmallThinkerConfig.tiny()
    p = st.init(cfg, jax.random.PRNGKey(3))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(4), (48, cfg.d_model))
    z = h @ p["router"]
    experts, gates = moe.route_softmax_top_k(h, p["router"], 2)
    share = jax.jit(lambda c: moe.experts_ffn_train(
        *(jax.lax.dynamic_slice_in_dim(t, c * 12, 12) for t in (h, experts, gates)),
        p["ew_gate_up"], p["ew_down"], activation="relu"))
    shares = [share(c) for c in range(4)]
    whole = ref._experts(ref._leaf_of({"layers": [None, p]}), {}, 1, h, z,
                         np.asarray(experts), "relu", None)
    assert all(float(jnp.abs(s).max()) > 0 for s in shares)
    assert rel(jnp.concatenate(shares), whole) < 1e-5
    spread = jax.jit(lambda: moe.experts_ffn_train(
        h, experts, gates, p["ew_gate_up"], p["ew_down"], activation="relu",
        mesh=mesh, axis="fsdp"))()
    assert rel(spread, whole) < 1e-5


@pytest.mark.parametrize("chips,abroad", [(4, False), (2, False), (1, False), (4, True)],
                         ids=["four", "two", "one", "four-a-chip-routes-abroad"])
def test_the_spread_layer_differentiates_as_the_one_device_layer(chips, abroad):
    """The layer and its gradients in ``x``, the gates and both matrices, the
    experts over four chips (three shifts), two (one) and one (no region),
    against the layer on one device; ``abroad``: every token of chip 0
    chooses experts that other chips hold."""
    cfg = st.SmallThinkerConfig.tiny()
    p = st.init(cfg, jax.random.PRNGKey(9))["layers"][1]
    keys = jax.random.split(jax.random.PRNGKey(10), 2)
    h, weight = (jax.random.normal(k, (48, cfg.d_model)) for k in keys)
    experts, gates = moe.route_softmax_top_k(h, p["router"], 2)
    if abroad:  # chip 0 has tokens 0-11 and experts 0, 1
        far = 2 + 2 * (jnp.arange(12) % 3)
        experts = experts.at[:12].set(jnp.stack([far, far + 1], 1))
    mesh = Mesh(np.array(jax.devices()[:chips]), ("fsdp",))
    assert cfg.n_experts % chips == 0

    def layer(mesh):
        return jax.jit(jax.value_and_grad(lambda x, g, w_gu, w_d: (
            moe.experts_ffn_train(
                x, experts, g, w_gu, w_d, activation="relu", mesh=mesh,
                axis=mesh and "fsdp") * weight).sum(), (0, 1, 2, 3)))(
            h, gates, p["ew_gate_up"], p["ew_down"])

    (wanted, wanted_grads), (got, got_grads) = layer(None), layer(mesh)
    assert float(got) == pytest.approx(float(wanted), rel=1e-5)
    assert all(float(jnp.abs(g).max()) > 0 for g in wanted_grads)
    assert max(rel(a, b) for a, b in zip(got_grads, wanted_grads)) < 1e-5


def test_one_expert_takes_every_token_and_none_is_lost(mesh):
    """A router biased so that expert 3 (then 5) wins every token: no capacity,
    no drop, the reference's numbers, on one device and four."""
    cfg = st.SmallThinkerConfig.tiny(n_layers=1)
    params, batch = st.init(cfg, jax.random.PRNGKey(5)), batch_of(cfg, 2, b=4)
    params["tok_emb"] = params["tok_emb"].at[:, 0].set(1.0)
    router = params["layers"][0]["router"]
    params["layers"][0]["router"] = router.at[0, 3].set(100.0).at[0, 5].set(90.0)
    ce, aux = ref.loss(params, batch["inputs"], batch["targets"], sizes_of(cfg))
    for m in (None, mesh):
        (_, counted), grads = value_and_grads(cfg, params, batch, m)
        pairs = np.asarray(counted["routed_pairs"])[0]
        assert pairs[3] == pairs[5] == 4 * T and pairs.sum() == 2 * 4 * T
        assert float(counted["ce"]) == pytest.approx(ce, rel=2e-4)
        assert float(counted["aux"]) == pytest.approx(aux, rel=2e-4)
        assert float(jnp.abs(grads["layers"][0]["ew_down"][3]).max()) > 0
        assert float(jnp.abs(grads["layers"][0]["ew_down"][0]).max()) == 0


def test_a_layers_kind_follows_both_layouts():
    """A rotary GLOBAL layer and a NoPE WINDOW layer, which the published
    pattern never has, still compute what the reference says."""
    odd = st.SmallThinkerConfig.tiny(
        n_layers=2, rope_layout=(1, 0), sliding_window_layout=(0, 1))
    usual = st.SmallThinkerConfig.tiny(n_layers=2)
    assert odd.sliding_windows == (0, 8) and usual.sliding_windows == (0, 8)
    assert usual.rope_layout == (0, 1)
    params, batch = st.init(odd, jax.random.PRNGKey(6)), batch_of(odd, 3)
    ce = {}
    for name, cfg in (("odd", odd), ("usual", usual)):
        ce[name] = float(value_and_grads(cfg, params, batch)[0][1]["ce"])
        wanted, _ = ref.loss(params, batch["inputs"], batch["targets"], sizes_of(cfg))
        assert ce[name] == pytest.approx(wanted, rel=2e-4)
    assert abs(ce["odd"] - ce["usual"]) > 1e-4


def test_the_expert_layer_differentiates_past_one_block():
    """More pairs than ``_ONE_BLOCK_PAIRS`` (where the served form turns to a
    runtime loop that has no transpose), a number of pairs that is no whole
    tile of rows: gradients in x, gates and both weights against the dense
    sum."""
    n, d, f, e, k = 601, 16, 12, 8, 2
    assert n * k > moe._ONE_BLOCK_PAIRS
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    x, target = (jax.random.normal(kk, (n, d)) for kk in keys[:2])
    w_r = jax.random.normal(keys[2], (d, e))
    w_gu = jax.random.normal(keys[3], (e, d, 2 * f)) * 0.3
    w_d = jax.random.normal(keys[4], (e, f, d)) * 0.3

    def dense(x, w_r, w_gu, w_d):
        experts, gates = moe.route_softmax_top_k(x, w_r, k)
        y = 0.0
        for j in range(e):
            gu = x @ w_gu[j]
            weight = jnp.where(experts == j, gates, 0.0).sum(-1)
            y = y + (jax.nn.relu(gu[:, :f]) * gu[:, f:]) @ w_d[j] * weight[:, None]
        return jnp.square(y - target).sum()

    def program(x, w_r, w_gu, w_d):
        experts, gates = moe.route_softmax_top_k(x, w_r, k)
        y = moe.experts_ffn_train(x, experts, gates, w_gu, w_d, activation="relu")
        return jnp.square(y - target).sum()

    wanted = jax.value_and_grad(dense, (0, 1, 2, 3))(x, w_r, w_gu, w_d)
    got = jax.jit(jax.value_and_grad(program, (0, 1, 2, 3)))(x, w_r, w_gu, w_d)
    assert float(got[0]) == pytest.approx(float(wanted[0]), rel=1e-5)
    assert max(rel(a, b) for a, b in zip(got[1], wanted[1])) < 2e-5


def operations_of(jaxpr, inside=()):
    """``[(primitive, the enclosing equations' primitives, result's aval)]`` of
    every equation of a jaxpr and of the jaxprs its equations hold."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        found.append((name, inside, eqn.outvars[0].aval if eqn.outvars else None))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += operations_of(sub, inside + (name,))
    return found


@pytest.mark.parametrize("replayed", [False, True], ids=["kept", "replayed"])
@pytest.mark.parametrize("starved", [False, True], ids=["routed", "an-expert-without-rows"])
@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_the_trained_block_against_plain_reverse_mode(activation, starved, replayed):
    """The block's own backward pass (the gate before the down matmul, the
    gates' gradient from ``dh``) against plain reverse mode of the layer as
    the reference writes it (every expert dense over every token, the gate
    AFTER the down matmul): the loss and the gradient in x, in the GATES and in
    both weights, past one block of pairs, a number of pairs that is no whole
    tile of rows; with an expert no token chose (its gradients exactly 0); and
    under the model's remat policy, the backward rule fed by a replay."""
    n, d, f, e, k = 601, 16, 12, 8, 2
    assert n * k > moe._ONE_BLOCK_PAIRS and n * k % 8
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    x, target = (jax.random.normal(kk, (n, d)) for kk in keys[:2])
    w_gu = jax.random.normal(keys[3], (e, d, 2 * f)) * 0.3
    w_d = jax.random.normal(keys[4], (e, f, d)) * 0.3
    experts, gates = moe.route_softmax_top_k(
        x, jax.random.normal(keys[2], (d, e)), k)
    if starved:  # expert 5's pairs go to expert 6 (or 7, where 6 is the other)
        other = experts[:, ::-1]
        experts = jnp.where(experts == 5, jnp.where(other == 6, 7, 6), experts)
        assert not bool((experts == 5).any())
    gates = gates * jax.random.uniform(keys[5], gates.shape, minval=0.5, maxval=1.5)
    act = moe.ACTIVATIONS[activation]

    def dense(x, gates, w_gu, w_d):
        y = 0.0
        for j in range(e):
            gu = x @ w_gu[j]
            weight = jnp.where(experts == j, gates, 0.0).sum(-1)
            y = y + (act(gu[:, :f]) * gu[:, f:]) @ w_d[j] * weight[:, None]
        return jnp.square(y - target).sum()

    def program(x, gates, w_gu, w_d):
        y = moe.experts_ffn_train(x, experts, gates, w_gu, w_d, activation=activation)
        return jnp.square(y - target).sum()

    if replayed:
        program = jax.checkpoint(program, policy=st.remat_policy())
    wanted = jax.value_and_grad(dense, (0, 1, 2, 3))(x, gates, w_gu, w_d)
    got = jax.jit(jax.value_and_grad(program, (0, 1, 2, 3)))(x, gates, w_gu, w_d)
    assert float(got[0]) == pytest.approx(float(wanted[0]), rel=1e-5)
    assert all(float(jnp.abs(g).max()) > 0 for g in wanted[1])
    assert max(rel(a, b) for a, b in zip(got[1], wanted[1])) < 2e-5
    if starved:
        assert all(float(jnp.abs(g[5]).max()) == 0 for g in got[1][2:])
        assert all(float(jnp.abs(g[6]).max()) > 0 for g in got[1][2:])


def test_a_replayed_layer_runs_seven_grouped_matmuls():
    """One layer of the model (bf16, as the cell trains it) under the model's
    remat policy, its ``jax.grad`` read as a jaxpr: SEVEN grouped matmuls (two
    forward, the gate-and-up one again in the replay, four backward: the down
    matmul is not run a second time), ONE gather whose result is a float32
    block of ``pairs x D`` (the forward's way back to the tokens, ``k`` blocks
    of ``[N, D]``; the rows, the replay's rows, the cotangent's rows and the
    rows' gradient are bf16 gathers), and no sort in the backward pass (the
    forward's two are kept by name)."""
    cfg = st.SmallThinkerConfig.tiny(n_layers=1, remat=True, dtype=jnp.bfloat16)
    params, batch = st.init(cfg, jax.random.PRNGKey(12)), batch_of(cfg)
    k, d = cfg.experts_per_token, cfg.d_model
    operations = operations_of(jax.make_jaxpr(jax.grad(
        lambda p: st.loss_fn(p, batch, cfg)[0]))(params).jaxpr)
    matmuls = [inside for name, inside, _ in operations if name == "ragged_dot_general"]
    assert len(matmuls) == 7
    # (the backward pass is the ``remat2`` equation: the replay and the
    # transposes; what stands outside it is the forward pass)
    assert sum("remat2" in inside for inside in matmuls) == 5
    blocks = sorted(
        (str(aval.dtype), aval.shape, "remat2" in inside)
        for name, inside, aval in operations
        if name == "gather" and aval.shape in ((B * T * k, d), (k, B * T, d)))
    assert blocks == [
        ("bfloat16", (k, B * T, d), True),    # the rows' gradient, back to the tokens
        ("bfloat16", (B * T * k, d), False),  # the rows
        ("bfloat16", (B * T * k, d), True),   # the rows again, the cotangent's rows
        ("bfloat16", (B * T * k, d), True),
        ("float32", (k, B * T, d), False)]    # the result, back to the tokens
    sorts = ["remat2" in inside for name, inside, _ in operations if name == "sort"]
    assert sorts == [False, False]


def test_ten_steps_lower_the_loss(mesh):
    cfg = st.SmallThinkerConfig.tiny(n_layers=2, remat=True)
    optimizer = st.make_optimizer(lr=3e-3, warmup=2, total_steps=100)
    rules = st.sharding_rules(mesh)
    shard = st.param_shardings(mesh, rules, cfg)
    state = jax.jit(lambda k: st.init_state(cfg, k, optimizer))(jax.random.PRNGKey(8))
    state["params"] = jax.device_put(state["params"], shard)
    step = jax.jit(st.make_train_step(cfg, optimizer, mesh), donate_argnums=(0,))
    assert step.__name__ == st.STEP_NAME and "train_step" not in st.STEP_NAME
    batch = jax.device_put(batch_of(cfg, 4, b=4), NamedSharding(mesh, P("fsdp", None)))
    losses = []
    for _ in range(10):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    assert set(m) == {"loss", "step", "ce", "aux", "routed_pairs", "grad_norms"}
    assert m["routed_pairs"].shape == (cfg.n_layers, cfg.n_experts)
    assert set(m["grad_norms"]) == set(st.named_leaves(cfg))
    assert all(float(v) > 0 for v in m["grad_norms"].values())


@pytest.mark.parametrize("t,window,bq,bk", [
    (256, 128, 128, 128), (256, 130, 128, 128), (256, 64, 128, 128)])
def test_the_flash_pair_under_a_band(t, window, bq, bk):
    """Forward and backward kernels (TPU interpreter) under ``i - window < j
    <= i`` against masked scores written out: a band of whole blocks, one that
    ends inside a block, one narrower than a block."""
    attn = importlib.import_module("ray_tpu.ops.attention")
    q, k, v, g = (jax.random.normal(kk, (1, 2, t, 128))
                  for kk in jax.random.split(jax.random.PRNGKey(t + window), 4))

    def plain(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 128 ** -0.5
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where((j <= i) & (j > i - window), s, -1e30), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    kernel = lambda q, k, v: attn.flash_attention_tpu(  # noqa: E731
        q, k, v, True, None, bq, bk, True, window)
    assert rel(kernel(q, k, v), plain(q, k, v)) < 1e-5
    got = jax.grad(lambda *a: (kernel(*a) * g).sum(), (0, 1, 2))(q, k, v)
    wanted = jax.grad(lambda *a: (plain(*a) * g).sum(), (0, 1, 2))(q, k, v)
    assert max(rel(a, b) for a, b in zip(got, wanted)) < 1e-5
    # which bands go to the pair is a rule by shape
    shape = (2, 28, 8192, 128)
    assert attn.flash_plan(shape, shape, causal=True, window=4096) == (1024, 1024)
    assert attn.flash_plan(shape, shape, causal=True, window=513) is None
