"""Attention implementations with one contract — ``[B, H, T, D]`` q/k/v.

:func:`attention` dispatches by shape.  Its thresholds predate the chip
and no benchmark cell sits on either side of any of them (ROADMAP D7): the
cells run :func:`causal_skip_attention` (training at T=1,024, the 512
prefill bucket) and :func:`full_attention` (the 64/128/256 buckets); the
other paths have no cell.

- :func:`causal_skip_attention` — the causal path at moderate T: unrolled
  q-blocks contracting only visible keys (~40% of the FLOPs of masked full
  attention skipped at T=1024), bf16 matmuls with f32 accumulation.
- :func:`full_attention` — masked materialized-scores path (non-causal,
  or shapes causal-skip can't take).
- :func:`blockwise_attention` — online-softmax ``lax.scan`` over k/v
  blocks; O(block) memory, any length (pads+masks), differentiable; also
  the inner block the ring-attention layer reuses.

- :func:`flash_attention_tpu` — pallas MXU-tiled kernels for BOTH forward
  and backward (dq/dk/dv rebuilt from the saved logsumexp, recompute-free).
  The dispatch selects it from 8k tokens on TPU.

Not in the dispatch:

- :func:`mha_reference` — naive O(T²) f32 attention; numerical ground
  truth for tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def mha_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Naive O(T²) attention, the numerical ground truth."""
    *_, t_q, d = q.shape
    t_k = k.shape[-2]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((t_q, t_k), dtype=bool), t_k - t_q)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v)


def _block_update(carry, s, v_blk):
    """One online-softmax step: fold scores ``s`` (f32, [..., q, kb]) and
    values ``v_blk`` into the running (out, max, denom)."""
    o, m, l = carry
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p, v_blk.astype(jnp.float32)
    )
    return o_new, m_new, l_new


def blockwise_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False,
    scale: Optional[float] = None, block_k: int = 512,
) -> jax.Array:
    """Flash-style attention as a ``lax.scan`` over k/v blocks.

    O(T_k / block_k) sequential steps, O(block) memory per step; jax AD
    differentiates through the scan, and ``jax.checkpoint`` around the
    caller gives full rematerialization.  Also correct when ``t_k != t_q``
    (used by ring attention, where k/v rotate around the ``sp`` ring).
    """
    *_, t_q, d = q.shape
    t_k = k.shape[-2]
    scale = scale if scale is not None else d ** -0.5
    block_k = min(block_k, t_k)
    # Lengths that don't divide block_k are padded (padded keys masked out
    # below) rather than shrinking the block — a prime t_k with block_k=1
    # would mean t_k sequential 1-wide matmul steps.
    pad = (-t_k) % block_k
    if pad:
        widths = [(0, 0)] * (k.ndim - 2) + [(0, pad), (0, 0)]
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    n_blocks = (t_k + pad) // block_k

    qf = q.astype(jnp.float32) * scale
    k_blocks = k.reshape(*k.shape[:-2], n_blocks, block_k, d)
    v_blocks = v.reshape(*v.shape[:-2], n_blocks, block_k, d)
    # scan over the block axis: move it to front
    k_blocks = jnp.moveaxis(k_blocks, -3, 0)
    v_blocks = jnp.moveaxis(v_blocks, -3, 0)

    q_pos = jnp.arange(t_q) + (t_k - t_q)  # align causal diagonal

    def step(carry, blk):
        idx, k_blk, v_blk = blk
        s = jnp.einsum("...qd,...kd->...qk", qf, k_blk.astype(jnp.float32))
        k_pos = idx * block_k + jnp.arange(block_k)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            if pad:
                mask &= (k_pos < t_k)[None, :]
            s = jnp.where(mask, s, NEG_INF)
        elif pad:
            s = jnp.where((k_pos < t_k)[None, :], s, NEG_INF)
        return _block_update(carry, s, v_blk), None

    o0 = jnp.zeros((*q.shape[:-1], d), jnp.float32)
    m0 = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    (o, m, l), _ = lax.scan(
        step, (o0, m0, l0), (jnp.arange(n_blocks), k_blocks, v_blocks)
    )
    return (o / l[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------

from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402


def _masked_scores(q_ref, k_ref, qi, ki, *, scale, causal, block_q, block_k,
                   q_offset):
    """scale·QKᵀ for one (q block, k block) cell, causal-masked with the
    bottom-right-aligned diagonal.  Shared by the forward and both backward
    kernels so masking semantics can never desynchronize."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    if causal:
        q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return s


def _block_visible(qi, ki, *, block_q, block_k, q_offset):
    """True iff the (qi, ki) cell has any unmasked element — cells fully
    above the causal diagonal are skipped (≈2x MXU work saved at long T)."""
    return ki * block_k <= q_offset + (qi + 1) * block_q - 1


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, block_q: int, block_k: int,
                  q_offset: int):
    """Grid = (batch*heads, n_q_blocks, n_k_blocks); the k axis is the
    innermost (sequential) dimension, so the f32 scratch (acc, m, l)
    carries the online softmax across k steps of one q block."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    visible = (
        _block_visible(qi, ki, block_q=block_q, block_k=block_k, q_offset=q_offset)
        if causal else ki >= 0
    )

    @pl.when(visible)
    def _():
        s = _masked_scores(q_ref, k_ref, qi, ki, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k, q_offset=q_offset)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=-1)
        m_ref[:, 0] = m_new
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _():
        o_ref[0] = (acc_ref[:] / l_ref[:, 0][:, None]).astype(o_ref.dtype)
        # logsumexp residual: the backward kernels rebuild P = exp(S - LSE)
        # from it without re-running the online softmax.  Kept as a
        # [bq, 1] column (TPU blocks want the sublane dim divisible by 8).
        lse_ref[0, :, 0] = m_ref[:, 0] + jnp.log(l_ref[:, 0])


def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool, scale: float,
    block_q: int, block_k: int, interpret: bool,
):
    """Returns (out [B,H,Tq,D], lse [B,H,Tq] f32)."""
    b, h, t_q, d = q.shape
    t_k = k.shape[-2]
    bq, bk = min(block_q, t_q), min(block_k, t_k)
    if t_q % bq or t_k % bk:
        raise ValueError(f"seq lens ({t_q},{t_k}) not divisible by blocks ({bq},{bk})")
    qr = q.reshape(b * h, t_q, d)
    kr = k.reshape(b * h, t_k, d)
    vr = v.reshape(b * h, t_k, d)
    grid = (b * h, t_q // bq, t_k // bk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        q_offset=t_k - t_q,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, t_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, t_q, d), lse.reshape(b, h, t_q)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                     dq_acc, *, scale: float, causal: bool,
                     block_q: int, block_k: int, q_offset: int):
    """dQ: grid (bh, n_q, n_k), k innermost; one q block accumulates
    dQ = sum_k dS @ K with dS = P * (dO Vᵀ - Δ) * scale, P = exp(S - LSE)
    rebuilt from the forward's logsumexp (recompute-free backward,
    FlashAttention-2 eq. 13-16)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    visible = (
        _block_visible(qi, ki, block_q=block_q, block_k=block_k, q_offset=q_offset)
        if causal else ki >= 0
    )

    @pl.when(visible)
    def _():
        s = _masked_scores(q_ref, k_ref, qi, ki, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k, q_offset=q_offset)
        p = jnp.exp(s - lse_ref[0])               # [bq,1] bcast -> [bq, bk]
        do = do_ref[0]
        dp = jax.lax.dot_general(                 # dO @ Vᵀ  [bq, bk]
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0]) * scale
        k = k_ref[0]
        dq_acc[:] += jax.lax.dot_general(         # dS @ K  [bq, d]
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                      causal: bool, block_q: int, block_k: int,
                      q_offset: int):
    """dK/dV: grid (bh, n_k, n_q), q innermost; one k block accumulates
    dV = sum_q Pᵀ @ dO and dK = sum_q dSᵀ @ Q."""
    kbi = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    visible = (
        _block_visible(qi, kbi, block_q=block_q, block_k=block_k, q_offset=q_offset)
        if causal else qi >= 0
    )

    @pl.when(visible)
    def _():
        s = _masked_scores(q_ref, k_ref, qi, kbi, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k, q_offset=q_offset)
        p = jnp.exp(s - lse_ref[0])               # [bq,1] bcast -> [bq, bk]
        do = do_ref[0]
        dv_acc[:] += jax.lax.dot_general(         # Pᵀ @ dO  [bk, d]
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0]) * scale
        q = q_ref[0]
        dk_acc[:] += jax.lax.dot_general(         # dSᵀ @ Q  [bk, d]
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, *, causal, scale,
                    block_q, block_k, interpret):
    b, h, t_q, d = q.shape
    t_k = k.shape[-2]
    bq, bk = min(block_q, t_q), min(block_k, t_k)
    qr = q.reshape(b * h, t_q, d)
    kr = k.reshape(b * h, t_k, d)
    vr = v.reshape(b * h, t_k, d)
    dor = g.reshape(b * h, t_q, d)
    lser = lse.reshape(b * h, t_q, 1)
    # Δ = rowsum(dO ⊙ O): one fused elementwise reduce, cheap in XLA
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(b * h, t_q, 1)

    q_spec = pl.BlockSpec((1, bq, d), lambda bh, a, b2: (bh, a, 0))
    row_spec = pl.BlockSpec((1, bq, 1), lambda bh, a, b2: (bh, a, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, q_offset=t_k - t_q),
        grid=(b * h, t_q // bq, t_k // bk),
        in_specs=[
            q_spec,                                                # q by qi
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            q_spec,                                                # dO by qi
            row_spec,                                              # lse
            row_spec,                                              # delta
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, t_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta)

    k_spec = pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, q_offset=t_k - t_q),
        grid=(b * h, t_k // bk, t_q // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),  # q
            k_spec,                                                    # k
            k_spec,                                                    # v
            pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),  # dO
            pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh, qi, 0)),  # lse
            pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh, qi, 0)),  # delta
        ],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, t_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta)
    return (
        dq.reshape(b, h, t_q, d),
        dk.reshape(b, h, t_k, d),
        dv.reshape(b, h, t_k, d),
    )


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def flash_attention_tpu(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = False, scale: Optional[float] = None,
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
) -> jax.Array:
    """Pallas flash attention: MXU-tiled forward AND backward.  The
    backward is recompute-free — P is rebuilt from the forward's saved
    logsumexp, never materializing the full score matrix (the standard
    dq/dk/dv flash backward)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    out, _ = _flash_forward(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    out, lse = _flash_forward(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _flash_backward(
        q, k, v, out, lse, g, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


flash_attention_tpu.defvjp(_flash_fwd, _flash_bwd)


def attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False,
    scale: Optional[float] = None, block_q: int = 128, block_k: int = 128,
) -> jax.Array:
    """Dispatch to an implementation by shape (module docstring: none of
    the thresholds has a cell on either side).  Single entry point used by
    the model zoo.

    - causal, square, block-divisible, moderate T → :func:`causal_skip_attention`
    - moderate T → :func:`full_attention` (masked, MXU dtypes)
    - T ≥ 8k on TPU, block-divisible → :func:`flash_attention_tpu`
      (pallas fwd + recompute-free bwd kernels)
    - other long T → :func:`blockwise_attention` (O(block) memory,
      pads+masks any length; ring attention covers sharded-T)
    """
    t_q, t_k = q.shape[-2], k.shape[-2]
    if t_q <= _MAX_MATERIALIZED_T and t_k <= _MAX_MATERIALIZED_T:
        if causal and t_q == t_k and t_q % 256 == 0 and t_q >= 512:
            return causal_skip_attention(q, k, v, scale=scale, block=256)
        return full_attention(q, k, v, causal=causal, scale=scale)
    if (
        q.ndim == 4
        and t_k >= 8192  # predates the chip; no cell on either side
        and t_q % block_q == 0
        and t_k % block_k == 0
        and jax.default_backend() == "tpu"
    ):
        # long context: the pallas kernel pair (fwd + recompute-free bwd)
        return flash_attention_tpu(
            q, k, v, causal, scale, block_q, block_k, False
        )
    return blockwise_attention(
        q, k, v, causal=causal, scale=scale, block_k=block_k
    )


def _scores(q, k, scale: float) -> jax.Array:
    """Q·Kᵀ in the input dtype with f32 accumulation (MXU-friendly)."""
    bdims = tuple(range(q.ndim - 2))
    return lax.dot_general(
        q, k, (((q.ndim - 1,), (k.ndim - 1,)), (bdims, bdims)),
        preferred_element_type=jnp.float32,
    ) * scale


def _weighted_values(p: jax.Array, v: jax.Array) -> jax.Array:
    """softmax(P)·V with P cast back to V's dtype for the MXU."""
    bdims = tuple(range(p.ndim - 2))
    return lax.dot_general(
        p.astype(v.dtype), v,
        (((p.ndim - 1,), (v.ndim - 2,)), (bdims, bdims)),
    )


def full_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Materialized-scores attention with MXU-friendly dtypes: inputs stay
    in their dtype (bf16 in the models), scores accumulate in f32
    (``preferred_element_type``), softmax in f32, P@V back in input dtype.

    XLA fuses the masked softmax.
    """
    *_, t_q, d = q.shape
    t_k = k.shape[-2]
    scale = scale if scale is not None else d ** -0.5
    s = _scores(q, k, scale)
    if causal:
        mask = jnp.tril(jnp.ones((t_q, t_k), dtype=bool), t_k - t_q)
        s = jnp.where(mask, s, NEG_INF)
    return _weighted_values(jax.nn.softmax(s, axis=-1), v)


def causal_skip_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    scale: Optional[float] = None, block: int = 256,
) -> jax.Array:
    """Causal attention that skips fully-masked key blocks: an unrolled
    loop over q blocks where block i only contracts keys ``[0:(i+1)*block]``
    — ~40% fewer FLOPs than masked full attention at T=1024, every matmul
    shape static so XLA tiles each branch onto the MXU.  Requires
    ``t_q == t_k`` divisible by ``block``.

    One dot + one full-width masked select per q block (XLA fuses the
    select into the softmax; separate unmasked-prefix and masked-diagonal
    dots would need a concat).  The dispatcher's causal default below 4k
    tokens: the train cells and the 512 prefill bucket run it; no cell
    runs the pallas pair against it.
    """
    *_, t, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    n = t // block
    outs = []
    for i in range(n):
        qi = lax.slice_in_dim(q, i * block, (i + 1) * block, axis=-2)
        kv_len = (i + 1) * block
        ki = lax.slice_in_dim(k, 0, kv_len, axis=-2)
        vi = lax.slice_in_dim(v, 0, kv_len, axis=-2)
        q_pos = i * block + jnp.arange(block)
        mask = q_pos[:, None] >= jnp.arange(kv_len)[None, :]
        s = jnp.where(mask, _scores(qi, ki, scale), NEG_INF)
        outs.append(_weighted_values(jax.nn.softmax(s, axis=-1), vi))
    return jnp.concatenate(outs, axis=-2)


# Above this, materialized scores risk HBM pressure; the O(block) blockwise
# path takes over.
_MAX_MATERIALIZED_T = 4096
