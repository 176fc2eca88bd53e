"""Attention / layer op correctness on the virtual CPU mesh."""

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
