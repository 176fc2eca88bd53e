"""Attention / layer op correctness on the virtual CPU mesh."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import (
    attention,
    blockwise_attention,
    cross_entropy_loss,
    flash_attention_tpu,
    layernorm,
    mha_reference,
    ring_attention,
    rmsnorm,
    rope,
)
from ray_tpu.ops.ring_attention import ulysses_attention
from ray_tpu.parallel import MeshSpec, create_mesh

# the package's ``attention`` attribute is the function
attention_module = importlib.import_module("ray_tpu.ops.attention")


def _qkv(b=2, h=2, t=256, d=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, h, t, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_reference(causal):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_k=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_blockwise_grads_match_reference():
    q, k, v = _qkv(t=128)

    def loss_ref(q, k, v):
        return mha_reference(q, k, v, causal=True).sum()

    def loss_blk(q, k, v):
        return blockwise_attention(q, k, v, causal=True, block_k=32).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_interpret_matches_reference(causal):
    q, k, v = _qkv(t=256, d=32)
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention_tpu(q, k, v, causal, None, 128, 128, True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    mesh = create_mesh(MeshSpec(sp=8))
    b, h, t, d = 1, 2, 256, 16
    q, k, v = _qkv(b, h, t, d)
    ref = mha_reference(q, k, v, causal=causal)

    f = jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=causal),
            mesh=mesh,
            in_specs=P(None, None, "sp", None),
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )
    )
    out = f(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_ring_attention_grads():
    mesh = create_mesh(MeshSpec(sp=4), devices=jax.devices()[:4])
    q, k, v = _qkv(1, 2, 64, 16)

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=True),
        mesh=mesh,
        in_specs=P(None, None, "sp", None),
        out_specs=P(None, None, "sp", None),
        check_vma=False,
    )
    g_ring = jax.grad(lambda q, k, v: ring(q, k, v).sum(), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: mha_reference(q, k, v, causal=True).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_ulysses_matches_full():
    mesh = create_mesh(MeshSpec(sp=2), devices=jax.devices()[:2])
    q, k, v = _qkv(1, 4, 128, 16)
    ref = mha_reference(q, k, v, causal=True)
    f = jax.jit(
        jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp", causal=True),
            mesh=mesh,
            in_specs=P(None, None, "sp", None),
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )
    )
    np.testing.assert_allclose(f(q, k, v), ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_full_attention_matches_reference(causal):
    from ray_tpu.ops import full_attention

    q, k, v = _qkv(t=256, d=32)
    ref = mha_reference(q, k, v, causal=causal)
    out = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda q: full_attention(q, k, v, causal=causal).sum())(q)
    g_ref = jax.grad(lambda q: mha_reference(q, k, v, causal=causal).sum())(q)
    np.testing.assert_allclose(g, g_ref, atol=2e-4, rtol=2e-4)


def test_causal_skip_matches_reference():
    from ray_tpu.ops import causal_skip_attention

    q, k, v = _qkv(t=512, d=32)
    ref = mha_reference(q, k, v, causal=True)
    out = causal_skip_attention(q, k, v, block=128)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda q: causal_skip_attention(q, k, v, block=128).sum())(q)
    g_ref = jax.grad(lambda q: mha_reference(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(g, g_ref, atol=2e-4, rtol=2e-4)


def test_attention_dispatch_long_seq_uses_blockwise():
    """Past the materialization cap the O(block) path must kick in and
    still be exact."""
    q, k, v = _qkv(b=1, h=1, t=256, d=16)
    ref = mha_reference(q, k, v, causal=True)
    import importlib
    import sys

    importlib.import_module("ray_tpu.ops.attention")
    am = sys.modules["ray_tpu.ops.attention"]  # pkg attr is shadowed by the fn

    old = am._MAX_MATERIALIZED_T
    am._MAX_MATERIALIZED_T = 128  # force the long-T path at test size
    try:
        out = attention(q, k, v, causal=True)
    finally:
        am._MAX_MATERIALIZED_T = old
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("t", [192, 320, 96, 127])  # incl. prime length
@pytest.mark.parametrize("causal", [False, True])
def test_attention_dispatch_odd_seq_lens(t, causal):
    """Lengths not divisible by 128 are padded+masked, not crashed on."""
    q, k, v = _qkv(t=t, d=32)
    ref = mha_reference(q, k, v, causal=causal)
    out = attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # grads flow through the padded path
    g = jax.grad(lambda q: attention(q, k, v, causal=causal).sum())(q)
    g_ref = jax.grad(lambda q: mha_reference(q, k, v, causal=causal).sum())(q)
    np.testing.assert_allclose(g, g_ref, atol=2e-4, rtol=2e-4)


def test_rmsnorm_layernorm():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    w = jnp.ones(64)
    out = rmsnorm(x, w)
    np.testing.assert_allclose(
        np.mean(np.asarray(out) ** 2, -1), np.ones(4), rtol=1e-4
    )
    out = layernorm(x, w, jnp.zeros(64))
    np.testing.assert_allclose(np.mean(np.asarray(out), -1), np.zeros(4), atol=1e-5)


@pytest.mark.parametrize("op", ["dense", "dense_batched", "layernorm", "rmsnorm"])
def test_f32_param_grads(op):
    """``f32_param_grads`` changes no value forward and, in float32, no
    gradient; with bf16 activations and float32 parameters the parameters'
    gradients come back float32 (summed over the batch in float32, never
    rounded to bf16) and agree with the float32 computation as closely as
    bf16 activations allow."""
    from ray_tpu.ops.layers import dense, layernorm, rmsnorm

    k = jax.random.split(jax.random.PRNGKey(0), 3)
    if op == "dense":
        x, params = jax.random.normal(k[0], (64, 33, 16)), (
            jax.random.normal(k[1], (16, 24)), jax.random.normal(k[2], (24,)))
        fn = dense
    elif op == "dense_batched":  # the MoE experts' [E, C, D] @ [E, D, F]
        x, params = jax.random.normal(k[0], (4, 257, 16)), (
            jax.random.normal(k[1], (4, 16, 24)), jax.random.normal(k[2], (4, 1, 24)))
        fn = dense
    elif op == "layernorm":
        x, params = jax.random.normal(k[0], (64, 33, 16)), (
            jax.random.normal(k[1], (16,)), jax.random.normal(k[2], (16,)))
        fn = layernorm
    else:
        x, params = jax.random.normal(k[0], (64, 33, 16)), (
            jax.random.normal(k[1], (16,)),)
        fn = rmsnorm

    def grads(x, flag):
        loss = lambda x, *p: jnp.sum(  # noqa: E731
            jnp.sin(fn(x, *p, f32_param_grads=flag).astype(jnp.float32)))
        return jax.grad(loss, argnums=tuple(range(1 + len(params))))(x, *params)

    np.testing.assert_array_equal(
        fn(x, *params, f32_param_grads=True), fn(x, *params))
    want = grads(x, False)
    for g, w in zip(grads(x, True), want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    # bf16 activations, float32 masters
    xb = x.astype(jnp.bfloat16)
    wide = grads(xb, True)
    want = grads(xb.astype(jnp.float32), False)
    assert wide[0].dtype == jnp.bfloat16
    for g, w in zip(wide[1:], want[1:]):
        assert g.dtype == jnp.float32
        assert np.abs(g - w).max() <= 3e-2 * np.abs(w).max()


def test_rope_preserves_norm_and_relative_phase():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
    pos = jnp.arange(8)
    out = rope(x, pos)
    np.testing.assert_allclose(
        jnp.linalg.norm(out, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5
    )
    # position 0 is identity
    np.testing.assert_allclose(out[:, 0], x[:, 0], atol=1e-6)


def test_cross_entropy():
    logits = jnp.array([[[2.0, 0.0, 0.0], [0.0, 2.0, 0.0]]])
    labels = jnp.array([[0, -100]])  # second token ignored
    loss = cross_entropy_loss(logits, labels)
    expected = -np.log(np.exp(2) / (np.exp(2) + 2))
    np.testing.assert_allclose(loss, expected, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_backward_matches_reference(causal):
    """The pallas dq/dk/dv kernels (recompute-free, logsumexp residual)
    against autodiff through the naive reference."""
    q, k, v = _qkv(t=256, d=32)

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v, causal=causal) * 0.01).sum()

    def loss_flash(q, k, v):
        return (flash_attention_tpu(q, k, v, causal, None, 128, 128, True) * 0.01).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_fl):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_backward_rectangular(causal):
    """t_k != t_q (decode-with-cache shape): the causal diagonal must be
    bottom-right aligned, matching mha_reference/blockwise semantics."""
    q, _, _ = _qkv(t=128, d=32)
    _, k, v = _qkv(t=256, d=32)

    def loss_flash(q, k, v):
        return flash_attention_tpu(q, k, v, causal, None, 128, 128, True).sum()

    def loss_ref(q, k, v):
        return mha_reference(q, k, v, causal=causal).sum()

    out_fl = flash_attention_tpu(q, k, v, causal, None, 128, 128, True)
    np.testing.assert_allclose(
        out_fl, mha_reference(q, k, v, causal=causal), atol=2e-5, rtol=2e-5
    )
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


# (heads, dk, dv, the forward's block_q and block_k): what ``flash_plan`` gives
# the kernels at the cells' shapes (one forward cell of 1,024, backward cells of
# 512) in each layout: an even number of heads of 64, two side by side on the
# lanes; heads of 128, one a lane block; XL's odd number of heads of 64 and
# latent attention's 192 | 128, a (batch x head) row each; and blocks the
# diagonal crosses off-centre
FLASH_PLANS = [(4, 64, 64, 1024, 1024), (2, 128, 128, 1024, 1024),
               (3, 64, 64, 1024, 1024), (2, 192, 128, 1024, 1024),
               (2, 64, 64, 256, 512)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("h,dk,dv,block_q,block_k", FLASH_PLANS)
def test_flash_pair_at_the_dispatch_blocks(h, dk, dv, block_q, block_k, dtype):
    """The Pallas pair as training at T=1,024 runs it (interpret mode): the
    values and all three gradients against the naive float32 reference."""
    ks = jax.random.split(jax.random.PRNGKey(dk + block_q), 4)
    q, k = (jax.random.normal(key, (1, h, 1024, dk), jnp.float32).astype(dtype)
            for key in ks[:2])
    v, g = (jax.random.normal(key, (1, h, 1024, dv), jnp.float32).astype(dtype)
            for key in ks[2:])
    packed = attention_module._flash_pack(q, k, v)[2]
    assert packed == {(4, 64): (2, 128, 128), (2, 128): (1, 128, 128),
                      (3, 64): (1, 64, 64), (2, 192): (1, 192, 128),
                      (2, 64): (2, 128, 128)}[h, dk]
    assert attention_module.flash_plan(
        q.shape, k.shape, v.shape, causal=True) == (1024, 1024)

    def flash(q, k, v):
        return flash_attention_tpu(q, k, v, True, None, block_q, block_k, True)

    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    want, pull = jax.vjp(
        lambda q, k, v: mha_reference(q, k, v, causal=True), f32(q), f32(k), f32(v))
    got, pull_flash = jax.vjp(flash, q, k, v)
    assert got.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(f32(got), want, atol=tol, rtol=tol)
    for name, a, b in zip("qkv", pull_flash(g), pull(f32(g))):
        assert a.dtype == dtype
        np.testing.assert_allclose(
            f32(a), b, atol=tol * np.abs(b).max(), rtol=tol,
            err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("t,kernels", [(512, 0), (1024, 2), (2048, 2)])
def test_attention_takes_the_kernel_by_shape_and_platform(t, kernels):
    """Causal self-attention, forward and backward: lowered for a TPU the
    Pallas pair (by its two names) from 1,024 positions up and the XLA path
    below; lowered for the CPU never the kernel."""
    x = jax.ShapeDtypeStruct((1, 2, t, 64), jnp.bfloat16)
    grads = jax.jit(jax.grad(
        lambda q, k, v: attention(q, k, v, causal=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).trace(x, x, x)
    tpu = grads.lower(lowering_platforms=("tpu",)).as_text()
    assert tpu.count("tpu_custom_call") == kernels
    for name in ("flash_attention_fwd", "flash_attention_bwd"):
        assert (name in tpu) == bool(kernels), name
    assert "tpu_custom_call" not in grads.lower(
        lowering_platforms=("cpu",)).as_text()
    plan = functools.partial(attention_module.flash_plan, causal=True)
    assert (plan(x.shape, x.shape) is not None) == bool(kernels)
    # not causal, a window layer, queries against a longer cache, a row that
    # the backward kernel could not hold: the XLA paths on every platform
    assert attention_module.flash_plan(x.shape, x.shape, causal=False) is None
    assert plan(x.shape, x.shape, window=128) is None
    assert plan((1, 2, 128, 64), x.shape) is None
    assert plan((1, 2, 32768, 64), (1, 2, 32768, 64)) is None
    assert plan((1, 2, 16384, 64), (1, 2, 16384, 64)) == (1024, 1024)
    assert plan((1, 64, 8192, 192), (1, 64, 8192, 192), (1, 64, 8192, 128)) == (
        1024, 1024)
    assert plan((1, 2, 1536, 64), (1, 2, 1536, 64)) == (512, 512)


@pytest.mark.parametrize("axes", [{"fsdp": 4}, {"dp": 2, "tp": 2}],
                         ids=["fsdp4", "dp2_tp2"])
def test_attend_under_a_mesh_equals_unsharded(axes):
    """``_attend`` at a shape the kernel takes runs under ``shard_map`` over
    the batch axes and ``tp``: same values and gradients as without a mesh
    (on the CPU both run the XLA path inside), and nothing of q, k or v is
    gathered: the compiled program holds no collective."""
    from ray_tpu.models.transformer import _attend

    n = int(np.prod(list(axes.values())))
    mesh = create_mesh(MeshSpec(**axes), devices=jax.devices()[:n])
    q, k, v = _qkv(b=4, h=2, t=1024, d=16)

    def loss(mesh):
        return lambda q, k, v: (
            _attend(q, k, v, causal=True, mesh=mesh)[0] ** 2).sum()

    want, g_want = jax.value_and_grad(loss(None), argnums=(0, 1, 2))(q, k, v)
    sharded = jax.jit(jax.value_and_grad(loss(mesh), argnums=(0, 1, 2)))
    got, g_got = sharded(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)
    spec = jax.sharding.NamedSharding(mesh, P(
        tuple(a for a in ("dp", "fsdp") if a in axes), "tp" if "tp" in axes else None))
    placed = [jax.device_put(t, spec) for t in (q, k, v)]
    text = sharded.lower(*placed).compile().as_text()
    assert "all-gather" not in text and "all-to-all" not in text
