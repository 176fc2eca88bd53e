"""EVA attention as EvaByte serves it (``attention_class: eva``): a query
attends the EXACT keys of its own window (``window`` positions, block-aligned:
window ``w`` is positions ``[w W, (w + 1) W)``, and a query sees the positions
of its window at or below its own) beside ONE pooled key and value a
``chunk`` of positions of every EARLIER window, all under one softmax.

Three pieces, each the plain ``jax.numpy`` form with the kernels the repo has
where they fit:

- :func:`pool_chunks`: a chunk's summary.  A head's learned vector ``phi``
  scores the chunk's (rotated) keys, ``p = softmax_j(scale k_j . phi)``; the
  summary key is ``sum_j p_j k_j + mu`` (``mu``: a learned offset a head), the
  summary value ``sum_j p_j v_j``.  Float32 sums, positions last as the cache
  stores them.
- :func:`windowed_attention`: a prefill call's attention, a window at a time:
  a window's queries attend its own keys causally and every summary of the
  windows before it (what the cache holds of the slot, for a prompt's PART,
  then what this call's earlier windows pooled).  The summaries are laid
  AHEAD of the window's keys as if they were earlier positions, so the causal
  program the repo has for a prompt's part
  (:func:`ray_tpu.ops.attention.continued_attention`: lowered for a TPU the
  flash kernel, which folds and fetches no key block beyond the last real one)
  computes it; a window with nothing before it is plain causal attention.
- :func:`merge`: two un-normalised softmaxes ``(acc, max, denominator)`` over
  disjoint key sets into one, the flash-decoding merge: how a decode step
  joins what it read of the window with what it read of the summaries
  (:func:`ray_tpu.models.generate.decode_chunk`).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import attention, continued_attention


def pool_chunks(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array, *,
                chunk: int, scale: float) -> Tuple[jax.Array, jax.Array]:
    """``k, v [..., KV, dh, T]`` (positions LAST, ``T`` whole chunks) -> the
    chunks' summaries ``[..., KV, dh, T // chunk]``, a key and a value each.
    ``phi, mu [..., KV, dh]``: the layer's pooling vector and key offset a
    head (leading dimensions broadcast against ``k``'s)."""
    *lead, dh, t = k.shape
    assert t % chunk == 0, (t, chunk)
    f32 = jnp.float32
    kc, vc = (a.astype(f32).reshape(*lead, dh, t // chunk, chunk) for a in (k, v))
    s = (kc * phi.astype(f32)[..., None, None]).sum(-3) * scale  # [.., n, chunk]
    p = jax.nn.softmax(s, axis=-1)[..., None, :, :]
    ks = (kc * p).sum(-1) + mu.astype(f32)[..., None]
    return ks.astype(k.dtype), (vc * p).sum(-1).astype(v.dtype)


def merge(a, b):
    """Two un-normalised softmaxes over disjoint keys, ``(acc [..., d], m
    [...], d [...])`` each (an empty one: ``acc = 0, d = 0, m = -1e30``), as
    one."""
    (acc_a, m_a, d_a), (acc_b, m_b, d_b) = a, b
    m = jnp.maximum(m_a, m_b)
    w_a, w_b = jnp.exp(m_a - m), jnp.exp(m_b - m)
    return (acc_a * w_a[..., None] + acc_b * w_b[..., None], m,
            d_a * w_a + d_b * w_b)


def _at_rows(buf, new, rows):
    """``new [B, KV, n, d]`` into ``buf [B, KV, R, d]`` at row ``rows[b]``."""
    return jax.vmap(lambda h, t, at: lax.dynamic_update_slice(
        h, t.astype(h.dtype), (0, at, 0)))(buf, new, rows)


def windowed_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       phi: jax.Array, mu: jax.Array, *, window: int,
                       chunk: int, scale: Optional[float] = None,
                       held=None, rows0: Optional[jax.Array] = None):
    """A prefill call's EVA attention.  ``q [B, H, T, dh]``, ``k, v [B, KV, T,
    dh]`` (rotated), the call's tokens at positions ``first + 0..T-1`` with
    ``first`` a multiple of ``window``; ``T <= window`` (one window, maybe
    partial) or whole windows.  ``held``: ``(ks, vs) [B, KV, dh, R]``, the
    summaries the cache holds of the rows' slots, of which row ``b``'s first
    ``rows0[b]`` are real (a prompt's PART; None: the call starts its prompts).

    Returns ``(out [B, H, T, dh], pooled)``; ``pooled``: ``(ks, vs) [B, KV,
    dh, windows x window // chunk]``, every window of the call pooled
    (:func:`pool_chunks`; whether a row's tokens FILL a window is the
    caller's to know), or None where ``T < window``."""
    B, H, T, dh = q.shape
    KV = k.shape[1]
    scale = dh ** -0.5 if scale is None else scale
    tw = min(T, window)
    assert T % tw == 0 and (held is None or tw == window), (T, window)
    n_w, cpw = T // tw, window // chunk
    heads = lambda t: jnp.repeat(t, H // KV, axis=1) if H != KV else t  # noqa: E731
    # the summaries a window's queries attend, by row: the cache's, then the
    # call's own earlier windows'
    rows0 = jnp.zeros((B,), jnp.int32) if rows0 is None else rows0.astype(jnp.int32)
    if held is None:
        held = tuple(jnp.zeros((B, KV, dh, 0), t.dtype) for t in (k, v))
    room = (n_w - 1) * cpw
    # whole blocks of the flash kernel where a window is whole blocks
    keys = held[0].shape[-1] + room + tw
    keys = -(-keys // 512) * 512 if tw % 512 == 0 else keys
    sums = tuple(jnp.pad(jnp.swapaxes(h, 2, 3).astype(t.dtype),
                         ((0, 0), (0, 0), (0, keys - h.shape[-1]), (0, 0)))
                 for h, t in zip(held, (k, v)))
    outs, pooled = [], []
    for j in range(n_w):
        at = slice(j * tw, (j + 1) * tw)
        qj, kj, vj = q[:, :, at], k[:, :, at], v[:, :, at]
        first = rows0 + j * cpw
        if j == 0 and held[0].shape[-1] == 0:
            with jax.named_scope("attention.eva_window"):
                outs.append(attention(qj, heads(kj), heads(vj), causal=True,
                                      scale=scale))
        else:
            # summaries at "positions" below ``first``, the window's own keys
            # from there: causal by position is the layer's mask
            with jax.named_scope("attention.eva_summary"):
                kk, vv = (heads(_at_rows(s, t, first))
                          for s, t in zip(sums, (kj, vj)))
                outs.append(continued_attention(qj, kk, vv, first, scale=scale))
        if tw < window:  # a lone partial window: nothing to pool
            return outs[0], None
        with jax.named_scope("attention.eva_pool"):
            pooled.append(pool_chunks(
                jnp.swapaxes(kj, 2, 3), jnp.swapaxes(vj, 2, 3), phi, mu,
                chunk=chunk, scale=scale))
        if j + 1 < n_w:
            sums = tuple(_at_rows(s, jnp.swapaxes(p, 2, 3), first)
                         for s, p in zip(sums, pooled[-1]))
    return (jnp.concatenate(outs, axis=2),
            tuple(jnp.concatenate(t, axis=-1) for t in zip(*pooled)))
