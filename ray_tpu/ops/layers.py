"""Fused layer ops: norms, rotary embeddings, losses.

Plain jnp compositions written so XLA fuses them into neighbouring matmuls
(f32 accumulation, bf16 storage) — per the guide, hand-scheduling what the
compiler already fuses is an anti-pattern, so pallas is reserved for the
attention inner loop.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _sum_to(g: jax.Array, shape) -> jax.Array:
    """Sum ``g`` over the dimensions that broadcasting ``shape`` to it added."""
    lead = g.ndim - len(shape)
    g = g.sum(tuple(range(lead)))
    kept = tuple(i for i, n in enumerate(shape) if n == 1 and g.shape[i] != 1)
    return g.sum(kept, keepdims=True) if kept else g


# The three ways a block uses a parameter: x @ w, y + b, y * w, the parameter
# cast to the activation's dtype at use (float32 masters, bf16 compute).
# Each is the plain operation forward.  Backward, the PARAMETER's gradient
# (a sum over every sequence and token) is accumulated in float32 and comes
# back in the parameter's own dtype, so a float32 master's gradient is never
# rounded to bf16 on the way.  Where the batch is spread over chips that
# sum is the cross-chip one: the partitioner then reduces float32 partials
# (under plain bf16 autodiff it reduces the bf16 outputs of per-chip sums).
# Asked for with ``f32_param_grads`` (the models do under an ``fsdp`` mesh
# axis); without it every op below is the plain expression it always was.

@jax.custom_vjp
def _matmul_f32_grad(x, w):
    return x @ w.astype(x.dtype)


def _matmul_f32_grad_bwd(res, g):
    x, w = res
    sum_over = "...k,...n->kn" if w.ndim == 2 else "...ck,...cn->...kn"
    dw = jnp.einsum(sum_over, x, g, preferred_element_type=jnp.float32)
    return g @ jnp.swapaxes(w.astype(g.dtype), -1, -2), dw.astype(w.dtype)


_matmul_f32_grad.defvjp(lambda x, w: (x @ w.astype(x.dtype), (x, w)),
                        _matmul_f32_grad_bwd)


@jax.custom_vjp
def _add_f32_grad(y, b):
    return y + b.astype(y.dtype)


_add_f32_grad.defvjp(
    lambda y, b: (y + b.astype(y.dtype), b),
    lambda b, g: (g, _sum_to(g.astype(jnp.float32), b.shape).astype(b.dtype)))


@jax.custom_vjp
def _mul_f32_grad(y, w):
    return y * w.astype(y.dtype)


def _mul_f32_grad_bwd(res, g):
    y, w = res
    dw = _sum_to(g.astype(jnp.float32) * y.astype(jnp.float32), w.shape)
    return g * w.astype(g.dtype), dw.astype(w.dtype)


_mul_f32_grad.defvjp(lambda y, w: (y * w.astype(y.dtype), (y, w)),
                     _mul_f32_grad_bwd)


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
          *, f32_param_grads: bool = False) -> jax.Array:
    """``x @ w (+ b)``, ``w`` and ``b`` cast to ``x``'s dtype at use.  With
    ``f32_param_grads`` their gradients are accumulated in float32 (see
    above); ``w`` may carry leading batch dimensions (``[E, K, N]`` against
    ``x [E, C, K]``)."""
    if f32_param_grads:
        y = _matmul_f32_grad(x, w)
        return y if b is None else _add_f32_grad(y, b)
    y = x @ w.astype(x.dtype)
    return y if b is None else y + b.astype(y.dtype)


def _affine(out, weight, bias, f32_param_grads):
    """``out * weight (+ bias)``, both cast to ``out``'s dtype."""
    if f32_param_grads:
        out = _mul_f32_grad(out, weight)
        return out if bias is None else _add_f32_grad(out, bias)
    out = out * weight.astype(out.dtype)
    return out if bias is None else out + bias.astype(out.dtype)


def rmsnorm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6,
            f32_param_grads: bool = False) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return _affine(out, weight, None, f32_param_grads)


def layernorm(
    x: jax.Array, weight: jax.Array, bias: Optional[jax.Array] = None,
    *, eps: float = 1e-5, f32_param_grads: bool = False,
) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return _affine(out.astype(x.dtype), weight, bias, f32_param_grads)


def rope(
    x: jax.Array, positions: jax.Array, *, base: float = 10000.0,
) -> jax.Array:
    """Rotary position embedding. x: [..., T, D] with D even.

    positions: [T] (shared across batch — training) or [B, T] (per-sequence
    absolute positions — KV-cache decode, where each slot sits at its own
    offset).  x is [B, H, T, D] in the batched case."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if positions.ndim == 2:  # [B, T] -> angles [B, 1, T, D/2]
        angles = positions.astype(jnp.float32)[:, None, :, None] * inv_freq
    else:
        angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    out = jnp.stack([out1, out2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def mrope(x: jax.Array, positions: jax.Array, sections, *,
          base: float = 10000.0) -> jax.Array:
    """Rotary embedding on THREE position axes (M-RoPE: Qwen2-VL's, what a
    config's ``rope_scaling.mrope_section`` declares).  ``x [B, heads, T, d]``;
    ``sections``: how many of the ``d / 2`` frequencies (``base ** (-2i / d)``)
    turn by the temporal, the row and the column component of a position, in
    that order; ``positions [B, 3, T]`` one ``(t, h, w)`` a token, or ``[T]`` /
    ``[B, T]``: every axis the same, which is plain rotary at that position (a
    text token's).  The rotate-half pairing (value ``i`` with ``i + d / 2``)."""
    d = x.shape[-1]
    assert sum(sections) * 2 == d, (sections, d)
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    at = positions.astype(jnp.float32)
    if positions.ndim == 3:  # each frequency's own axis: [B, T, d / 2]
        axis = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                          total_repeat_length=d // 2)
        at = jnp.take_along_axis(
            jnp.swapaxes(at, 1, 2), jnp.broadcast_to(
                axis, (at.shape[0], at.shape[2], d // 2)), axis=2)
        angles = (at * inv_freq)[:, None]
    else:
        angles = at[..., None] * inv_freq
        if positions.ndim == 2:
            angles = angles[:, None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def cross_entropy_loss(
    logits: jax.Array, labels: jax.Array, *, ignore_index: int = -100,
    z_loss: float = 0.0,
) -> jax.Array:
    """Token-level cross entropy with optional z-loss (logit drift control).

    logits: [..., V] (any dtype; reduced in f32), labels: [...] int.
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    nll = lse - label_logit
    if z_loss:
        nll = nll + z_loss * lse**2
    valid = labels != ignore_index
    nll = jnp.where(valid, nll, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)
