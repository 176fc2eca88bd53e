"""A learned index over cached positions (the DeepSeek-V3.2 indexer): which
positions a query's attention reads.

A full layer of such a model scores every cached position for every query
with a small side network (``index_n_heads`` heads of ``index_head_dim``
against ONE index key a position, which is cached beside the attention's own
row), keeps the ``index_topk`` highest and runs its softmax over those alone.
Three pieces, each a function of arrays (no model, no cache):

- :func:`index_scores`: ``I[t, s] = sum_h w[t, h] * relu(q[t, h] . k[s]) *
  d ** -0.5 * H ** -0.5`` in float32.
- :func:`top_k_mask`: the EXACT ``k`` largest of a row, as a mask.  ``lax.top_k``
  over 17,000 scores for 2,048 places is a sort a row; ``approx_max_k`` is not
  the model.  Here the ``k``-th largest value is found by bisection over the
  float's bits (32 counting passes over the row, each a compare and a sum),
  and the mask is ``score >= threshold``; where several positions tie AT the
  threshold, the lower positions are kept (what ``lax.top_k`` does, so a
  reference that calls it selects the same set).
- :func:`causal_top_k_mask`: a whole prompt's masks, ``[B, T, T]`` int8, a block
  of queries at a time (the ``[heads, T, T]`` float32 products of 16,384
  positions never exist together), one mask a query ROW: the attention's heads
  share it (:func:`ray_tpu.ops.attention.masked_attention`).

The decode step scores the slab's index keys below a slot's live length and the
chunk-local ones together and hands the mask to the latent decode kernel
(:mod:`ray_tpu.models.generate`); that first form reads every live tile and
masks.  A read that gathers only the selected rows is a later change: the
engine's ``perf_stats()["dsa"]`` counts rows scored, selected and read so that
it can be measured.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import attention, masked_attention

__all__ = ["index_scores", "top_k_mask", "causal_top_k_mask",
           "selected_attention"]

# queries a block of :func:`causal_top_k_mask`: [heads, block, T] float32
# products are 1 GB at 64 heads and 16,384 positions
QUERY_BLOCK = 256


def index_scores(q: jax.Array, w: jax.Array, k: jax.Array,
                 layout: str = "bhqd,bkd->bhqk") -> jax.Array:
    """Index scores, float32, in the layout the einsum ``layout`` names (``h``
    the index heads, ``d`` their width; the result keeps ``h`` second and
    loses it here).  A prompt: ``q [B, H, Tq, d]`` (a query's index heads),
    ``w [B, Tq, H]`` (its head weights), ``k [B, Tk, d]`` (ONE index key a
    position) -> ``I [B, Tq, Tk]``.  A decode step (one query a slot): ``q [B,
    H, d]``, ``w [B, H]`` against a cache's ``[B, d, S]`` (``"bhd,bds->bhs"``)
    or a chunk's ``[steps, B, d]`` (``"bhd,tbd->bht"``) -> ``[B, positions]``.
    Operands stay in their dtype for the MXU, products accumulate in float32,
    and everything after the product is float32."""
    H, d = q.shape[1], q.shape[-1]
    s = jnp.einsum(layout, q, k.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    weights = jnp.moveaxis(w.astype(jnp.float32), -1, 1)[..., None]
    return (jax.nn.relu(s) * weights).sum(1) * (d ** -0.5 * H ** -0.5)


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 with the same order (negatives flipped whole,
    non-negatives in the upper half)."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    negative = bits >> 31 == 1
    return jnp.where(negative, ~bits, bits | jnp.uint32(1 << 31))


def top_k_mask(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """``scores [..., N]`` float32, ``valid [..., N]`` bool -> bool mask of
    the ``min(k, valid.sum())`` largest valid scores of each row, exactly;
    among equal scores at the last place the lower positions.  Nothing of a
    row without a valid entry."""
    keys = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
    # every valid key is >= 1 (0.0 maps to 2**31, -inf to 0x007fffff), so key
    # 0 is "not valid" and a threshold of 0 would keep everything
    want = jnp.minimum(k, valid.sum(-1, dtype=jnp.int32))[..., None]

    def bit(j, threshold):
        # the largest threshold that still leaves ``want`` keys at or above
        # it, built from the top bit down: the want-th largest key itself
        candidate = threshold | (jnp.uint32(1) << (jnp.uint32(31) - j.astype(jnp.uint32)))
        enough = (keys >= candidate).sum(-1, keepdims=True, dtype=jnp.int32) >= want
        return jnp.where(enough, candidate, threshold)

    threshold = lax.fori_loop(0, 32, bit, jnp.zeros_like(want, dtype=jnp.uint32))
    above, at = keys > threshold, keys == threshold
    room = want - above.sum(-1, keepdims=True, dtype=jnp.int32)
    # ties at the threshold: the first ``room`` of them by position
    first = jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= room
    return valid & (above | (at & first)) & (want > 0)


def causal_top_k_mask(q: jax.Array, w: jax.Array, k: jax.Array, top_k: int,
                      block: int = QUERY_BLOCK, first=None) -> jax.Array:
    """A prompt's selection: ``q [B, H, T, d]``, ``w [B, T, H]``, ``k [B, Tk,
    d]`` -> ``keep [B, T, Tk]`` int8, row ``t`` holding the ``top_k`` positions
    ``s <= t`` of largest index score (all of them while ``t < top_k``).  A
    block of queries at a time (``lax.map``), so that the float32 products a
    block are what is held.  ``first [B]`` int32 (None: 0, and ``Tk == T``): a
    prompt's PART, whose row ``t`` sits at position ``first[b] + t`` and whose
    keys are a slot's index keys by position, the cached ones below ``first``
    and the part's own from there, up to a static bound ``Tk``: ONE threshold
    over both, the whole prompt's selection for the same scores.  A part
    scores the blocks of ``T`` keys below the longest row's end alone (a
    RUNTIME trip count; the scores beyond it stay 0 and are never valid): no
    query may select a position there."""
    B, H, T, d = q.shape
    Tk = k.shape[1]
    block = min(block, T)
    assert T % block == 0, (T, block)
    n = T // block
    positions = jnp.arange(Tk)

    def live_scores(qb, wb):
        def trip(i, scores):  # (a last block the bound cuts short starts earlier)
            at = jnp.minimum(i * T, Tk - T)
            return lax.dynamic_update_slice_in_dim(scores, index_scores(
                qb, wb, lax.dynamic_slice_in_dim(k, at, T, 1)), at, 2)

        live = first.max().astype(jnp.int32) + T
        return lax.fori_loop(0, (live + T - 1) // T, trip,
                             jnp.zeros((B, block, Tk), jnp.float32))

    def rows(args):
        qb, wb, at = args                          # [B, H, block, d], [B, block, H]
        with jax.named_scope("attention.index_score"):                # [B, block, Tk]
            scores = (index_scores(qb, wb, k) if first is None
                      else live_scores(qb, wb))
        at = at + jnp.arange(block)
        if first is not None:
            at = first.astype(jnp.int32)[:, None] + at
        causal = positions <= at[..., None]
        with jax.named_scope("attention.index_select"):
            return top_k_mask(scores, jnp.broadcast_to(causal, scores.shape),
                              top_k).astype(jnp.int8)

    qb = jnp.moveaxis(q.reshape(B, H, n, block, d), 2, 0)
    wb = jnp.moveaxis(w.reshape(B, n, block, H), 1, 0)
    keep = lax.map(rows, (qb, wb, jnp.arange(n) * block))   # [n, B, block, Tk]
    return jnp.moveaxis(keep, 0, 1).reshape(B, T, k.shape[1])


def selected_attention(q: jax.Array, k: jax.Array, v: jax.Array, index,
                       top_k: int, *, scale: float) -> jax.Array:
    """A whole prompt's attention on a layer that selects: ``q [B, H, T, dk]``,
    ``k [B, H or KV or 1, T, dk]``, ``v [B, H or KV or 1, T, dv]``, ``index = (index
    queries [B, Hi, T, d], head weights [B, T, Hi], index keys [B, 1, T,
    d])``.  Query ``t`` attends the ``top_k`` positions ``s <= t`` its index
    scores put first; a prompt of at most ``top_k`` positions selects all of
    them, and is plain causal attention (decided by the shape)."""
    if k.shape[1] == 1 != q.shape[1]:  # one key head for every query head
        k, v = (jnp.broadcast_to(t, (*q.shape[:3], t.shape[-1])) for t in (k, v))
    elif k.shape[1] != q.shape[1]:  # grouped-query heads: a key head a group
        k, v = (jnp.repeat(t, q.shape[1] // t.shape[1], axis=1) for t in (k, v))
    if q.shape[2] <= top_k:
        return attention(q, k, v, causal=True, scale=scale)
    qi, w, ki = index
    keep = causal_top_k_mask(qi, w, ki[:, 0], top_k)
    return masked_attention(q, k, v, keep, scale=scale)
