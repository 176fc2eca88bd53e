"""Model zoo tests: shapes, training progress, sharded end-to-end step."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import bert, gpt2, mlp


def test_gpt2_tiny_forward_shapes():
    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = gpt2.apply(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_gpt2_tiny_loss_decreases():
    cfg = gpt2.GPT2Config.tiny()
    optimizer = gpt2.make_optimizer(lr=1e-3, warmup=1, total_steps=50)
    state = gpt2.init_state(cfg, jax.random.PRNGKey(0), optimizer)
    step = jax.jit(gpt2.make_train_step(cfg, optimizer))
    rng = np.random.default_rng(0)
    # one repeated batch: loss must fall when memorizing it
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 33), np.int32))}
    first = None
    for _ in range(10):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first - 0.5


def test_gpt2_causality():
    """Changing a future token must not change past logits."""
    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init(cfg, jax.random.PRNGKey(1))
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, 10].set(5)
    l1 = gpt2.apply(params, t1, cfg)
    l2 = gpt2.apply(params, t2, cfg)
    np.testing.assert_allclose(l1[0, :10], l2[0, :10], atol=1e-5)
    assert not np.allclose(l1[0, 10:], l2[0, 10:], atol=1e-5)


def test_bert_forward_and_bidirectional():
    cfg = bert.BertConfig.tiny()
    params = bert.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = bert.apply(params, tokens, cfg)
    assert logits.shape == (2, cfg.num_classes)
    # bidirectional: changing a late token changes the [CLS] features
    t2 = tokens.at[0, 12].set(7)
    l2 = bert.apply(params, t2, cfg)
    assert not np.allclose(logits[0], l2[0], atol=1e-6)


def test_mlp_trains():
    cfg = mlp.MLPConfig(in_dim=16, hidden=(32,), num_classes=4)
    params = mlp.init(cfg, jax.random.PRNGKey(0))
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, 64))

    @jax.jit
    def step(params, opt_state):
        loss, g = jax.value_and_grad(mlp.loss_fn)(params, {"x": x, "y": y}, cfg)
        updates, opt_state = opt.update(g, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    first = None
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state)
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.5
    assert float(mlp.accuracy(params, {"x": x, "y": y}, cfg)) > 0.7


@pytest.mark.parametrize("label", [
    "gpt2 fsdp*sp*tp", "gpt2-moe pp*ep*dp", "llama tp*sp*fsdp",
    "gpt2-125m-shape fsdp*sp*tp"])
def test_dryrun_multichip_8(label):
    """The driver's multi-chip validation path (``dryrun_multichip(8)``, a
    case of it a case here): full sharded train step (fsdp/sp/tp axes + ring
    attention; pp/ep/dp) on the 8-device CPU mesh."""
    import __graft_entry__ as g

    cases = g.dryrun_cases(8)
    assert label in cases and len(cases) == 4  # every case of it runs here
    model, cfg, sizes, parity_atol = cases[label]
    g._dryrun_one(model, cfg, sizes, 8, parity_atol=parity_atol, label=label)


def test_llama_tiny_forward_and_gqa():
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    assert cfg.q_per_kv == 2  # grouped-query: 4 q heads over 2 kv heads
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.apply(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    # KV projections are q_per_kv x smaller than Q (the GQA saving)
    assert params["blocks"]["wk"].shape[-1] * cfg.q_per_kv == \
        params["blocks"]["wq"].shape[-1]


def test_llama_tiny_loss_decreases():
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    optimizer = llama.make_optimizer(lr=1e-3, warmup=1, total_steps=50)
    state = llama.init_state(cfg, jax.random.PRNGKey(0), optimizer)
    step = jax.jit(llama.make_train_step(cfg, optimizer))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 33), np.int32))}
    first = last = None
    for _ in range(10):
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])
        first = first if first is not None else loss
        last = loss
    assert last < first * 0.9, (first, last)


def test_llama_causality():
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (1, 32), np.int32)
    base = np.asarray(llama.apply(params, jnp.asarray(toks), cfg))
    toks2 = toks.copy()
    toks2[0, -1] = (toks2[0, -1] + 1) % cfg.vocab_size  # change the LAST token
    out2 = np.asarray(llama.apply(params, jnp.asarray(toks2), cfg))
    # earlier positions must be unaffected (causal), last position changes
    np.testing.assert_allclose(base[0, :-1], out2[0, :-1], atol=1e-4)
    assert not np.allclose(base[0, -1], out2[0, -1])


def test_llama_sharded_train_step():
    """FSDP+TP sharded llama step on the 8-device CPU mesh matches the
    single-device loss."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshSpec, create_mesh
    from ray_tpu.parallel.sharding import FSDP_TP_RULES

    cfg = llama.LlamaConfig.tiny()
    optimizer = llama.make_optimizer(lr=1e-3, warmup=1, total_steps=50)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 33), np.int32))}

    state0 = llama.init_state(cfg, jax.random.PRNGKey(0), optimizer)
    _, m_single = jax.jit(llama.make_train_step(cfg, optimizer))(state0, batch)

    mesh = create_mesh(MeshSpec(fsdp=2, tp=2, dp=2))
    shardings = llama.param_shardings(mesh, FSDP_TP_RULES, cfg)
    state = llama.init_state(cfg, jax.random.PRNGKey(0), optimizer)
    params = jax.device_put(state["params"], shardings)
    state = {**state, "params": params}
    step = jax.jit(llama.make_train_step(cfg, optimizer, mesh))
    batch_sharded = jax.device_put(
        batch, NamedSharding(mesh, P(("dp", "fsdp"), None))
    )
    state, m_sharded = step(state, batch_sharded)
    np.testing.assert_allclose(
        float(m_single["loss"]), float(m_sharded["loss"]), rtol=1e-3
    )


def test_llama_sequence_parallel_matches_single():
    """sp>1 mesh routes through the shard_map ring-attention seam."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshSpec, create_mesh

    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64), np.int32))
    single = np.asarray(llama.apply(params, toks, cfg))

    mesh = create_mesh(MeshSpec(sp=4, dp=2))
    toks_sp = jax.device_put(toks, NamedSharding(mesh, P("dp", None)))
    out = np.asarray(jax.jit(
        lambda p, t: llama.apply(p, t, cfg, mesh)
    )(params, toks_sp))
    np.testing.assert_allclose(single, out, atol=3e-2, rtol=3e-2)


def _fsdp_parity_case(family):
    """(model module, config, loss(params, batch, cfg, mesh), batch) for the
    fsdp parity test: float32 tiny configs, as dryrun_multichip uses."""
    from ray_tpu.models import bert, gpt2, llama
    from ray_tpu.ops.layers import cross_entropy_loss

    rng = np.random.default_rng(0)
    f32 = jnp.float32
    if family == "bert":
        cfg = dataclasses.replace(
            bert.BertConfig.tiny(dtype=f32), remat=True, remat_policy="full")
        batch = {"tokens": rng.integers(0, cfg.vocab_size, (8, 32), np.int32),
                 "labels": rng.integers(0, cfg.num_classes, (8,), np.int32)}

        def loss(params, batch, cfg, mesh=None):
            logits = bert.apply(params, batch["tokens"], cfg, mesh=mesh)
            return cross_entropy_loss(logits, batch["labels"])

        return bert, cfg, loss, batch
    model, cfg = {
        "gpt2": (gpt2, gpt2.GPT2Config.tiny(
            dtype=f32, remat=True, remat_policy="full")),
        "llama": (llama, llama.LlamaConfig.tiny(
            dtype=f32, remat=True, remat_policy="full")),
    }[family]
    batch = {"tokens": rng.integers(0, cfg.vocab_size, (8, 33), np.int32)}
    return model, cfg, model.loss_fn, batch


@pytest.mark.parametrize("family", ["gpt2", "bert", "llama"])
def test_fsdp4_matches_unsharded(family):
    """Three optimizer steps on mesh fsdp=4 (weights gathered per layer,
    gradients landing in the at-rest sharding) against the same steps with
    ``mesh=None``: losses, first gradients and final parameters agree within
    the tolerance ``__graft_entry__._dryrun_one`` uses for its parity."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import gpt2
    from ray_tpu.models.transformer import make_train_step_from_loss
    from ray_tpu.parallel import create_mesh
    from ray_tpu.parallel.sharding import logical_to_sharding, rules_for_mesh

    atol = 2e-3
    model, cfg, loss, batch = _fsdp_parity_case(family)
    optimizer = gpt2.make_optimizer(lr=1e-3, warmup=1, total_steps=50)
    mesh = create_mesh({"fsdp": 4}, devices=jax.devices()[:4])
    p_shard = logical_to_sharding(
        model.logical_axes(cfg), mesh, rules_for_mesh(mesh))
    on_batch = NamedSharding(mesh, P("fsdp"))

    def run(mesh):
        params = model.init(cfg, jax.random.PRNGKey(0))
        data = batch
        if mesh is not None:
            params = jax.device_put(params, p_shard)
            data = jax.device_put(batch, on_batch)
        grads = jax.jit(jax.grad(loss), static_argnums=(2, 3))(
            params, data, cfg, mesh)
        state = {"params": params, "opt_state": optimizer.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        step = jax.jit(make_train_step_from_loss(loss, cfg, optimizer, mesh))
        losses = []
        for _ in range(3):
            state, metrics = step(state, data)
            losses.append(float(metrics["loss"]))
        return losses, grads, state["params"]

    losses, grads, params = run(mesh)
    ref_losses, ref_grads, ref_params = run(None)
    np.testing.assert_allclose(losses, ref_losses, atol=atol)
    assert ref_losses[-1] < ref_losses[0]
    # in float32 the two programs differ by the order of their sums alone: a
    # gradient off by a factor, or one bias's sum dropped, cannot hide here
    # (Adam's update is scale-free, so the parameters alone would hide it)
    for got, want, tol in ((grads, ref_grads, dict(rtol=1e-4, atol=1e-6)),
                           (params, ref_params, dict(atol=atol))):
        for path, g in jax.tree_util.tree_leaves_with_path(got):
            w = want
            for k in path:
                w = w[k.key]
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), **tol,
                err_msg=jax.tree_util.keystr(path))
    # the layers' gradients landed where their parameters rest
    for g, s in zip(jax.tree.leaves(grads["blocks"]),
                    jax.tree.leaves(p_shard["blocks"])):
        assert g.sharding.is_equivalent_to(s, g.ndim), (g.sharding, s)


def test_fsdp4_bf16_gradients_no_worse_than_unsharded():
    """bf16 compute, float32 masters: every parameter's gradient of the
    fsdp4 step is as near the float32 program's as the one-chip bf16
    program's is (the sums over the batch that fsdp spreads over chips are
    float32; summing bf16 per-chip partials would put the matrices' further
    off)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import gpt2
    from ray_tpu.parallel import create_mesh
    from ray_tpu.parallel.sharding import logical_to_sharding, rules_for_mesh

    def config(dtype):
        return gpt2.GPT2Config.tiny(dtype=dtype, remat=True, remat_policy="full")

    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(
        0, config(jnp.float32).vocab_size, (32, 33), np.int32)}
    mesh = create_mesh({"fsdp": 4}, devices=jax.devices()[:4])

    def grads(dtype, mesh):
        cfg = config(dtype)
        params, data = gpt2.init(cfg, jax.random.PRNGKey(0)), batch
        if mesh is not None:
            params = jax.device_put(params, logical_to_sharding(
                gpt2.logical_axes(cfg), mesh, rules_for_mesh(mesh)))
            data = jax.device_put(batch, NamedSharding(mesh, P("fsdp")))
        got = jax.jit(jax.grad(gpt2.loss_fn), static_argnums=(2, 3))(
            params, data, cfg, mesh)
        return {jax.tree_util.keystr(k): np.asarray(g, np.float64)
                for k, g in jax.tree_util.tree_leaves_with_path(got)}

    want = grads(jnp.float32, None)
    one_chip, fsdp4 = grads(jnp.bfloat16, None), grads(jnp.bfloat16, mesh)
    for name, w in want.items():
        off = lambda g: np.linalg.norm(g[name] - w) / np.linalg.norm(w)  # noqa: E731
        assert off(fsdp4) <= 1.02 * off(one_chip), (name, off(fsdp4), off(one_chip))


def test_unsharded_step_has_no_fsdp_machinery():
    """Without a mesh the step is the program it was: no sharding constraint
    and no custom_vjp (the weight gather's) in its jaxpr."""
    from ray_tpu.models import gpt2, llama

    tokens = jnp.zeros((2, 17), jnp.int32)
    for model, cfg in ((gpt2, gpt2.GPT2Config.tiny(remat=True)),
                       (llama, llama.LlamaConfig.tiny(remat=True))):
        optimizer = model.make_optimizer(lr=1e-3)
        state = jax.eval_shape(
            lambda k: model.init_state(cfg, k, optimizer), jax.random.PRNGKey(0))
        jaxpr = str(jax.make_jaxpr(model.make_train_step(cfg, optimizer))(
            state, {"tokens": tokens}))
        assert "sharding_constraint" not in jaxpr and "custom_vjp" not in jaxpr
