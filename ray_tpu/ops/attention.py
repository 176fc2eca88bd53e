"""Attention implementations with one contract — ``[B, H, T, D]`` q/k/v.

:func:`attention` dispatches by shape and, for the Pallas pair, by the
platform the program is lowered for (``lax.platform_dependent``; no flag).
Which benchmark cell runs which path: training at T=1,024 (both train cells)
and the prefill buckets of 1,024 and 2,048 positions (K-EXAONE's full layer,
Kimi-K2's and dots3-note's un-absorbed form, Granite's two attention layers) run
:func:`flash_attention_tpu` on the chip; the 512 bucket (GPT-2 XL's widest)
runs :func:`causal_skip_attention`, the 64/128/256 buckets
:func:`full_attention`, window layers :func:`band_attention`.  A prompt above
2,048 tokens is prefilled in PARTS (``serve/llm.py``), outside the dispatch:
:func:`continued_attention` (the forward kernel under a runtime key length,
with dots3-note's selection as its mask) and :func:`band_attention_after`.  The crossover
(``FLASH_MIN_T``) is the chip's: the op-level table of both paths at the
cells' shapes and the train steps with either path are in PERF.md, section 6,
PR 43.  Off the TPU every one of these calls takes the XLA paths, which are
also what the tests hold the kernels to.

- :func:`flash_attention_tpu` — the Pallas pair: an MXU-tiled forward kernel
  with online softmax (``flash_attention_fwd``) and ONE backward kernel
  (``flash_attention_bwd``: dq, dk and dv from the saved logsumexp,
  recompute-free); no score leaves VMEM.  Heads of 64 or 128 are read and
  written in the projections' own layout ``[B, T, H x d]``, two heads of 64
  side by side on the 128 lanes (:func:`_flash_pack`), so XLA re-lays nothing
  out around the kernels.  Its result and logsumexp carry names
  (``FLASH_RESIDUALS``) so that a remat policy can keep them.
- :func:`causal_skip_attention` — the causal XLA path at moderate T: unrolled
  q-blocks contracting only visible keys, scores materialized in float32,
  bf16 matmuls with f32 accumulation.
- :func:`full_attention` — masked materialized-scores path (non-causal,
  or shapes causal-skip can't take).
- :func:`blockwise_attention` — online-softmax ``lax.scan`` over k/v
  blocks; O(block) memory, any length (pads+masks), differentiable; also
  the inner block the ring-attention layer reuses.

Not in the dispatch:

- :func:`continued_attention` — a prompt's PART against a slot's keys by
  position, the cached prefix with the part's own among them, up to a static
  bound: the Pallas forward kernel with the first query's position and the key
  length as prefetched scalars (one program whatever the offset; no key block
  at or beyond the length is folded or fetched), optionally under a selection
  mask; off the TPU the masked scores a block of queries at a time, which the
  tests hold the kernel to.  :func:`band_attention_after`: the same for a
  window layer, the ring's positions ahead of the part then its own.
  :func:`live_blocks`: what is prepared FOR that kernel of the cached
  positions (an up-projection, a repeat to the query heads), the blocks below
  the live length alone, a runtime trip count.
- :func:`mha_reference` — naive O(T²) f32 attention; numerical ground
  truth for tests.
- :func:`ragged_decode_attention` — the decode step's contract, not
  ``[B, H, T, D]``: one query a slot against the S-minor KV cache ``[L, B,
  KV, dh, S]`` left whole in HBM, a Pallas kernel that copies in only each
  slot's 128-position tiles below its own live length and returns the
  softmax un-normalised (acc, max, denominator) for the caller to merge.
  Arithmetic on the VPU (one query a head is a matrix-VECTOR product).
  ``models/generate.py`` calls it where a decode program is lowered for a
  TPU with a cache of whole tiles: the ``serve-gpt2-xl-chat`` cell, whose
  masked einsums over the padded slab it replaces (the cell's
  ``model.decode_step_ms``: PERF.md section 6, PR 30).
- :func:`ragged_latent_decode_attention` — the decode step of multi-head
  latent attention: 64 absorbed queries a slot against a cache of ONE latent
  row a position, ``[L, B, 1, 576, S]``.  The tile walk is
  :func:`ragged_decode_attention`'s (the same plan of (slot, tile) items, the
  same double buffer across slots, the same un-normalised result), but a
  tile ``[576, 128]`` is copied in ONCE and serves as the keys of all heads
  and, its first 512 rows, as their values.  Why it is not that kernel: there
  a head's query meets its own K and V tile, a matrix-vector product on the
  VPU; here every head meets the same tile, 139,264 FLOPs a position against
  1,152 bytes (121 FLOP a byte, half way to the v5e's ridge of 240), so
  scores and values are two MXU matmuls with float32 accumulation and an
  online, row-wise softmax.  Off the TPU and for a cache that is not whole
  tiles: :func:`latent_slab_attention`, the masked einsums over the slab,
  which the tests hold the kernel to.
- :func:`cache_flush` — not attention, but the other kernel over the same
  cache: a decode chunk's new columns merged into the one or two
  128-position tiles they fall in, of the slots that decoded only, the slab
  left in HBM and updated in place.  ``models/generate.py`` ends a chunk
  with it where the decode program is lowered for a TPU with a cache of
  whole tiles; elsewhere a ``dynamic_update_slice`` a slot
  (``generate._flush_slices``), which the tests hold the kernel to.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.layout import Layout, with_layout_constraint

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
    t_k, dv = k.shape[-2], v.shape[-1]
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
    v_blocks = v.reshape(*v.shape[:-2], n_blocks, block_k, dv)
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

    o0 = jnp.zeros((*q.shape[:-1], dv), jnp.float32)
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


def _nt(a, b):
    """``a [m, d] x b [n, d]^T -> [m, n]``, float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _nn(a, b):
    """``a [m, n] x b [n, d] -> [m, d]``, float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _causal_mask(first_q, first_k, shape, *, keys_first: bool = False):
    """Query position >= key position over a ``[queries, keys]`` cell (``[keys,
    queries]`` if ``keys_first``) whose first query and key sit at those
    positions.  One place for both kernels, so their masks can never differ."""
    q_pos = first_q + lax.broadcasted_iota(jnp.int32, shape, int(keys_first))
    k_pos = first_k + lax.broadcasted_iota(jnp.int32, shape, int(not keys_first))
    return q_pos >= k_pos


def _own_lanes(x, i: int, heads: int):
    """``x [rows, heads x d]`` with every lane but head ``i``'s zeroed: a
    contraction over all the lanes against it is that head's alone.  (With
    narrow heads side by side on the 128 lanes the MXU pass is as deep as it
    would be for one of them.)"""
    if heads == 1:
        return x
    d = x.shape[-1] // heads
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= i * d) & (lane < (i + 1) * d), x, jnp.zeros_like(x))


def _flash_kernel(*refs, scale: float, causal: bool, block_q: int,
                  block_k: int, q_offset: int, with_keep: bool = False,
                  rows_per_bound: int = 0, window: int = 0):
    """Grid = (batch, lane blocks of heads, n_q_blocks, n_k_blocks); the k
    axis is the innermost (sequential) dimension, so the f32 scratch (acc, m,
    l: one of each a head of the lane block) carries the online softmax
    across k steps of one q block.  A lane block is ``heads`` heads side by
    side (two of 64 on the 128 lanes, as the projections leave them; one
    where a head fills them): a head's scores are its queries, the others'
    lanes zeroed once a q block (``q_own``), against the block's keys, and its
    ``P V`` keeps its own lanes at the end.  Row statistics stay ``[bq, 1]``
    columns (a lane broadcast against the scores).

    The causal diagonal (bottom-right aligned: query ``i`` is position
    ``q_offset + i``) leaves a cell wholly above it out (half the work at long
    T), and only a cell it crosses pays the iota, compare and select.

    ``with_keep``: a fourth operand ``keep [bq, bk]`` int8, one mask a query ROW
    that every head shares (a learned selection of positions:
    :func:`masked_attention`): a score outside it counts for nothing, in every
    cell, and a cell may hold none of a row's positions, so the
    exponentials are masked too (a row's running max is then still
    ``NEG_INF``, and ``exp(s - m)`` of a masked score would be 1).

    ``rows_per_bound`` (0: none): the first query's position, the key length
    and the first key that counts are RUNTIME values, three prefetched scalars
    for every ``rows_per_bound`` packed rows (``bounds_ref [3 x batch rows]``:
    a prompt continued at whatever position is one program,
    :func:`continued_attention`), instead of the static ``q_offset``, the
    keys' whole length and 0.  A key block at or beyond the key length, or
    below the first key, is folded no more than one above the diagonal (and the
    index maps fetch neither); one that either bound crosses is masked as one
    the diagonal crosses is.  ``window`` (with runtime bounds; 0: none): a
    query attends the ``window`` keys up to its own position only, and a block
    wholly below that band is left out like one above the diagonal
    (:func:`band_attention_after`)."""
    bounds_ref, refs = (refs[0], refs[1:]) if rows_per_bound else (None, refs)
    q_ref, k_ref, v_ref, *rest = refs
    keep_ref, rest = (rest[0], rest[1:]) if with_keep else (None, rest)
    o_ref, lse_ref, q_own, m_ref, l_ref, acc_ref = rest
    qi, ki, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    heads = q_own.shape[0]
    k_len = k_min = None
    if bounds_ref is not None:
        row = pl.program_id(0) // rows_per_bound
        q_offset, k_len, k_min = (bounds_ref[3 * row + j] for j in range(3))
    first_q, first_k = q_offset + qi * block_q, ki * block_k

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for i in range(heads):
            q_own[i] = _own_lanes(q_ref[0], i, heads)

    def fold(masked: bool):
        k, v = k_ref[0], v_ref[0]
        mask = _causal_mask(first_q, first_k, (block_q, block_k)) if masked else None
        if masked and k_len is not None:
            k_pos = first_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = mask & (k_pos < k_len) & (k_pos >= k_min)
            if window:
                mask = mask & (k_pos > first_q - window + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0))
        if keep_ref is not None:
            chosen = keep_ref[0].astype(jnp.int32) != 0
            mask = chosen if mask is None else mask & chosen
        for i in range(heads):
            s = _nt(q_own[i], k) * scale
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[i]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if keep_ref is not None or (window and masked):
                # (a band's first cell of a row may hold none of its keys too)
                p = jnp.where(mask, p, 0.0)
            l_ref[i] = l_ref[i] * alpha + p.sum(axis=-1, keepdims=True)
            m_ref[i] = m_new
            acc_ref[i] = acc_ref[i] * alpha + _nn(p.astype(v.dtype), v)

    if causal:
        # (each condition where it is used: under static bounds the kernel
        # is the training forward's, operation for operation)
        whole = first_k + block_k - 1 <= first_q
        if k_len is not None:
            whole = whole & (first_k + block_k <= k_len) & (first_k >= k_min)
        if window:  # every key in the LAST query's band ...
            whole = whole & (first_k > first_q + block_q - 1 - window)
        pl.when(whole)(lambda: fold(False))
        seen = first_k <= first_q + block_q - 1
        if k_len is not None:
            seen = seen & (first_k < k_len) & (first_k + block_k > k_min)
        if window:  # ... some key in the first's
            seen = seen & (first_k + block_k - 1 > first_q - window)
        pl.when(seen & jnp.logical_not(whole))(lambda: fold(True))
    else:
        fold(False)

    @pl.when(ki == nk - 1)
    def _():
        out = jnp.zeros(acc_ref.shape[1:], jnp.float32)
        for i in range(heads):
            out = out + _own_lanes(acc_ref[i] / l_ref[i], i, heads)
            # logsumexp residual: the backward kernel rebuilds P = exp(S -
            # LSE) from it without re-running the online softmax.  It leaves
            # with positions on the LANES (a whole-tile transpose): a ``[..,
            # T, 1]`` result is padded to 128 lanes in HBM, 64 MB for 0.5
            lse = m_ref[i] + jnp.log(l_ref[i])
            lse_ref[0, 0, i:i + 1, :] = jnp.broadcast_to(lse, (block_q, 128)).T[:1]
        o_ref[0] = out.astype(o_ref.dtype)


# one v5e core has 128 MiB of VMEM; the default scoped limit (16 MiB) is under
# what 1,024 x 1,024 float32 score tiles and a resident sequence take
_FLASH_VMEM = 64 * 1024 * 1024


def _flash_blocks(t_q: int, t_k: int, block_q: int, block_k: int):
    bq, bk = min(block_q, t_q), min(block_k, t_k)
    if t_q % bq or t_k % bk:
        raise ValueError(f"seq lens ({t_q},{t_k}) not divisible by blocks ({bq},{bk})")
    return bq, bk


def _flash_pack(q, k, v):
    """The kernels' operands: ``[N, T, lanes]``, walked a lane block of
    ``heads`` heads at a time.  Heads that tile the 128 lanes (64 | 128 wide,
    keys and values alike) stay as the projections leave them, ``[B, T, H x
    d]``: the caller's ``[B, H, T, d]`` is a transpose of that, which XLA
    cancels against this one, so nothing is re-laid out around the kernels and
    no lane is padding.  Other widths (latent attention's 192 | 128) go a
    (batch x head) row each, one lane block.  Returns ``(pack, unpack, (heads,
    q's and v's lane-block width))``; ``unpack`` gives ``[B, H, T, d]`` back."""
    b, h, _, d = q.shape
    dv = v.shape[-1]
    heads = 128 // d if d == dv and 128 % d == 0 else 0
    if heads and h % heads == 0:
        pack = lambda x: x.transpose(0, 2, 1, 3).reshape(  # noqa: E731
            b, x.shape[2], h * x.shape[3])
        unpack = lambda x: x.reshape(b, x.shape[1], h, -1).transpose(0, 2, 1, 3)  # noqa: E731
        return pack, unpack, (heads, 128, 128)
    pack = lambda x: x.reshape(b * h, *x.shape[2:])  # noqa: E731
    unpack = lambda x: x.reshape(b, h, *x.shape[1:])  # noqa: E731
    return pack, unpack, (1, d, dv)


def _flash_forward(qp, kp, vp, *, layout, causal: bool, scale: float,
                   block_q: int, block_k: int, interpret: bool, keep=None,
                   bounds=None, window: int = 0):
    """Packed operands (:func:`_flash_pack`) in; returns the packed result
    ``[N, Tq, lanes]`` and the logsumexp ``[N, lane blocks, heads, Tq]`` f32.
    ``v`` may be another width than ``q`` and ``k`` (latent attention's 128
    against 192).  ``keep [B, Tq, Tk]`` int8 (None: no such mask): the
    positions a query row attends, shared by the ``N / B`` packed rows of its
    batch row (:func:`masked_attention`).  ``bounds [3 x B]`` int32 (None: the
    static shapes say all three): batch row ``b``'s first query sits at
    position ``bounds[3 b]`` and attends the keys from ``bounds[3 b + 2]`` to
    below ``bounds[3 b + 1]``, runtime values that ride into scalar memory
    ahead of the grid; causal only.  ``window``: the kernel's (with bounds).
    ``kp``, ``vp`` may come IN BLOCKS of positions, ``[blocks, N, positions a
    block, lanes]`` (:func:`live_blocks`: position ``j`` is row ``j % a`` of
    block ``j // a``, whole key blocks of the kernel a block): only the index
    maps differ, the kernel sees the same ``[block_k, lanes]`` tiles."""
    heads, lw, lwv = layout
    n, t_q, w = qp.shape
    in_blocks = kp.shape[2] if kp.ndim == 4 else 0  # positions a block
    t_k, nb = (kp.shape[0] * in_blocks or kp.shape[1]), w // lw
    bq, bk = _flash_blocks(t_q, t_k, block_q, block_k)
    assert in_blocks % bk == 0, (in_blocks, bk)
    nk, q_offset = t_k // bk, t_k - t_q
    per_bound = 0 if bounds is None else n // (bounds.shape[0] // 3)
    assert bounds is None or causal, "runtime bounds: causal attention only"
    assert bounds is not None or not window, "a band: with runtime bounds only"

    # a cell above the causal diagonal (or outside the runtime bounds, or
    # below a band) asks for the key block its neighbour already holds, and
    # the pipeline fetches nothing for it.  An index map's arguments: the
    # grid's, then the prefetched bounds where there are any
    def held(ki, i, qi, prefetched):
        """The key block step ``ki`` of query block ``qi`` asks for: ``ki``
        held to the blocks the kernel folds."""
        if not causal:
            return ki
        if not prefetched:
            return jnp.minimum(ki, jnp.clip(
                (q_offset + (qi + 1) * bq - 1) // bk, 0, nk - 1))
        first, k_len, k_min = (
            prefetched[0][3 * (i // per_bound) + j] for j in range(3))
        last = jnp.minimum((first + (qi + 1) * bq - 1) // bk, (k_len - 1) // bk)
        lowest = k_min // bk
        if window:
            lowest = jnp.maximum(lowest, (first + qi * bq - window + 1) // bk)
        return jnp.clip(ki, jnp.clip(lowest, 0, nk - 1), jnp.clip(last, 0, nk - 1))

    by_q = lambda i, hb, qi, ki, *b: (i, qi, hb)  # noqa: E731
    by_k = lambda i, hb, qi, ki, *b: (i, held(ki, i, qi, b), hb)  # noqa: E731

    def key_tile(lanes):
        if not in_blocks:
            return pl.BlockSpec((1, bk, lanes), by_k)

        def in_block(*grid):  # (the leading axis squeezed: the same tile)
            i, at, hb = by_k(*grid)
            return at // tiles, i, at % tiles, hb

        tiles = in_blocks // bk  # of the kernel, a block of positions

        return pl.BlockSpec((None, 1, bk, lanes), in_block)

    masks, mask_specs = (), []
    if keep is not None:
        per_row = n // keep.shape[0]  # packed rows (heads) of one batch row
        masks = (keep,)
        mask_specs = [pl.BlockSpec(
            (1, bq, bk), lambda i, hb, qi, ki, *b: (
                i // per_row, qi, held(ki, i, qi, b)))]
    spec = dict(
        grid=(n, nb, t_q // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, lw), by_q),
            key_tile(lw),
            key_tile(lwv),
            *mask_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, lwv), by_q),
            pl.BlockSpec((1, 1, heads, bq), lambda i, hb, qi, ki, *b: (i, hb, 0, qi)),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, bq, lw), qp.dtype),      # a head's own lanes of q
            pltpu.VMEM((heads, bq, 1), jnp.float32),    # running max
            pltpu.VMEM((heads, bq, 1), jnp.float32),    # running denom
            pltpu.VMEM((heads, bq, lwv), jnp.float32),  # output accumulator
        ])
    if bounds is not None:
        spec = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **spec))
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, q_offset=q_offset,
                          with_keep=keep is not None, rows_per_bound=per_bound,
                          window=window),
        **spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, t_q, vp.shape[-1]), qp.dtype),
            jax.ShapeDtypeStruct((n, nb, heads, t_q), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FLASH_VMEM),
        name="flash_attention_fwd",
        interpret=interpret,
    )(*(() if bounds is None else (bounds.astype(jnp.int32),)), qp, kp, vp, *masks)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, *, heads: int,
                      scale: float, causal: bool, block_q: int, block_k: int,
                      q_offset: int, window: int = 0):
    """dQ, dK and dV in ONE kernel (five matmuls and one exp a cell, where a
    ``dq`` and a ``dk, dv`` kernel took seven and two): grid (batch, lane
    blocks of heads, n_k_blocks); a row's queries, dO, logsumexp and Δ stay in
    VMEM while its key blocks go by, and a rolled loop walks the query chunks
    a key block sees, from the causal diagonal on, masking only the chunks the
    diagonal crosses.  P = exp(S - LSE) is rebuilt from the forward's
    logsumexp (recompute-free backward, FlashAttention-2 eq. 13-16): dV = sum
    Pᵀ dO, dS = P (dO Vᵀ - Δ), dK = scale sum dSᵀ Q, and dQ = scale sum dS K
    into a float32 scratch of the whole row, written when the row's last key
    block is done.  A cell is computed KEYS FIRST (Sᵀ = K Qᵀ, ``[bk, bq]``):
    Pᵀ and dSᵀ are then the left operands of plain matmuls, and LSE and Δ are
    used as the rows (positions on the lanes) they are stored as.  Of a lane
    block of several heads, a head's cell contracts ITS lanes of K and V (the
    others zeroed once a key block), so dS K lands on its own lanes of dQ, and
    dK and dV keep their own lanes at the end.  ``window`` (0: none; causal):
    a query attends the ``window`` keys up to its own position only, so the
    walk ends at the last chunk whose band still reaches this key block, and a
    chunk the band's lower edge crosses is masked like one the diagonal
    crosses."""
    ki, nk = pl.program_id(2), pl.num_programs(2)
    nq = q_ref.shape[1] // block_q
    first_k = ki * block_k

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    whole, inside, end = 0, nq, nq
    if causal:
        # the first chunk of queries whose last row sees this key block, and
        # the first whose FIRST row sees its last key
        seen = jnp.clip((first_k - q_offset) // block_q, 0, nq)
        whole = jnp.clip(-((q_offset - first_k - block_k + 1) // block_q), 0, nq)
    if window:
        # the chunks whose LAST row's band still holds this block's first key,
        # and those whose first row's band holds its last
        inside = jnp.clip((first_k + window - q_offset) // block_q, whole, nq)
        end = jnp.clip(
            (first_k + block_k + window - 2 - q_offset) // block_q + 1, inside, nq)
    dk_all = jnp.zeros(k_ref.shape[1:], jnp.float32)
    dv_all = jnp.zeros(v_ref.shape[1:], jnp.float32)
    for i in range(heads):
        k, v = (_own_lanes(ref[0], i, heads) for ref in (k_ref, v_ref))

        def chunk(j, carry, masked: bool, i=i, k=k, v=v):
            dk, dv = carry
            rows = pl.ds(pl.multiple_of(j * block_q, block_q), block_q)
            stat = pl.ds(i * nq + j, 1)
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]
            s = _nt(k, q) * scale                               # [bk, bq]
            if masked:
                mask = _causal_mask(q_offset + j * block_q, first_k,
                                    s.shape, keys_first=True)
                if window:
                    mask = mask & (
                        first_k + lax.broadcasted_iota(jnp.int32, s.shape, 0)
                        > q_offset + j * block_q - window
                        + lax.broadcasted_iota(jnp.int32, s.shape, 1))
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse_ref[0, 0, stat, :])
            dv = dv + _nn(p.astype(do.dtype), do)
            ds = (p * (_nt(v, do) - delta_ref[0, 0, stat, :])).astype(q.dtype)
            dk = dk + _nn(ds, q)
            dq_acc[rows, :] += lax.dot_general(                 # dS K
                ds, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return dk, dv

        carry = (jnp.zeros_like(dk_all), jnp.zeros_like(dv_all))
        if causal:
            carry = lax.fori_loop(
                seen, whole, functools.partial(chunk, masked=True), carry)
        dk, dv = lax.fori_loop(
            whole, inside, functools.partial(chunk, masked=False), carry)
        if window:
            dk, dv = lax.fori_loop(
                inside, end, functools.partial(chunk, masked=True), (dk, dv))
        dk_all = dk_all + _own_lanes(dk, i, heads)
        dv_all = dv_all + _own_lanes(dv, i, heads)
    dk_ref[0] = (dk_all * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_all.astype(dv_ref.dtype)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_backward(qp, kp, vp, outp, lse, gp, *, layout, causal, scale,
                    block_q, block_k, interpret, window: int = 0):
    """Packed operands, result and cotangent in; packed ``(dq, dk, dv)``."""
    heads, lw, lwv = layout
    n, t_q, w = qp.shape
    t_k, nb = kp.shape[1], w // lw
    bq, bk = _flash_blocks(t_q, t_k, block_q, block_k)
    # Δ = rowsum(dO ⊙ O) a head: one fused elementwise reduce, cheap in XLA;
    # like LSE ``[N, lane blocks, heads x chunks of queries, bq]``: a chunk of
    # a head's queries a ROW, positions on the lanes
    d_head = lwv // heads
    delta = jnp.sum(
        (gp.astype(jnp.float32) * outp.astype(jnp.float32)).reshape(
            n, t_q, nb * heads, d_head), axis=-1)                # [N, T, H]
    stats = lambda x: x.reshape(n, nb, heads * (t_q // bq), bq)  # noqa: E731
    row_of_q = lambda width: pl.BlockSpec(  # noqa: E731
        (1, t_q, width), lambda i, hb, ki: (i, 0, hb))
    by_k = lambda width: pl.BlockSpec(  # noqa: E731
        (1, bk, width), lambda i, hb, ki: (i, ki, hb))
    stat = pl.BlockSpec((1, 1, heads * (t_q // bq), bq),
                        lambda i, hb, ki: (i, hb, 0, 0))
    return pl.pallas_call(
        functools.partial(_flash_bwd_kernel, heads=heads, scale=scale,
                          causal=causal, block_q=bq, block_k=bk,
                          q_offset=t_k - t_q, window=window),
        grid=(n, nb, t_k // bk),
        in_specs=[row_of_q(lw), by_k(lw), by_k(lwv), row_of_q(lwv), stat, stat],
        out_specs=[row_of_q(lw), by_k(lw), by_k(lwv)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (qp, kp, vp)],
        scratch_shapes=[pltpu.VMEM((t_q, lw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FLASH_VMEM),
        name="flash_attention_bwd",
        interpret=interpret,
    )(qp, kp, vp, gp, stats(lse),
      stats(delta.transpose(0, 2, 1).reshape(n, nb, heads, t_q)))


# what a remat policy may keep of a layer's attention so that the backward
# pass does not run the forward kernel again (``save_only_these_names``)
FLASH_RESIDUALS = ("flash_attention_out", "flash_attention_lse")
# the backward kernel's blocks: at most this (the chip's sweep, PERF.md
# section 6, PR 43: 512 x 512 cells beat 256 and 1,024 on the v5e)
_FLASH_BWD_BLOCK = 512


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def flash_attention_tpu(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = False, scale: Optional[float] = None,
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
    window: int = 0,
) -> jax.Array:
    """Pallas flash attention: an MXU-tiled forward kernel and ONE backward
    kernel.  The backward is recompute-free: P is rebuilt from the forward's
    saved logsumexp, never materializing the full score matrix (the standard
    flash backward, dq and dk/dv fused); it keeps a row's queries and dO in
    VMEM (``flash_plan`` bounds the length).  ``block_q``, ``block_k``: the
    forward's; the backward's are these up to 512.  Heads of 64 or 128 are
    read and written where the projections leave them (:func:`_flash_pack`).
    ``window`` (0: none; causal self-attention): position ``i`` attends ``i -
    window < j <= i``; both kernels leave out the cells wholly below the band
    as they do those above the diagonal."""
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                      window)[0]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret, window=0):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    pack, unpack, layout = _flash_pack(q, k, v)
    qp, kp, vp = pack(q), pack(k), pack(v)
    band = {}
    if window:
        # the forward kernel takes a band beside runtime bounds: every row's
        # first query at position 0, all of the keys, none before the first
        assert causal and q.shape[-2] == k.shape[-2], (causal, q.shape, k.shape)
        band = dict(window=window, bounds=jnp.tile(
            jnp.array([0, k.shape[-2], 0], jnp.int32), q.shape[0]))
    outp, lse = _flash_forward(
        qp, kp, vp, layout=layout, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret, **band,
    )
    outp, lse = map(checkpoint_name, (outp, lse), FLASH_RESIDUALS)
    return unpack(outp), (q, k, v, outp, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res, g):
    q, k, v, outp, lse = res
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    pack, unpack, layout = _flash_pack(q, k, v)
    grads = _flash_backward(
        pack(q), pack(k), pack(v), outp, lse, pack(g), layout=layout,
        causal=causal, scale=scale, block_q=min(block_q, _FLASH_BWD_BLOCK),
        block_k=min(block_k, _FLASH_BWD_BLOCK), interpret=interpret,
        window=window,
    )
    return tuple(map(unpack, grads))


flash_attention_tpu.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Ragged decode attention: one query a slot against the LIVE tiles of the
# slot's cache
# ---------------------------------------------------------------------------

DECODE_TILE = 128  # cache positions a tile; one lane width
# KV heads a trip of the kernel's rolled head loop, at most: on the v5e one
# head a trip left the kernel 12 % slower, all 25 of GPT-2 XL unrolled cost
# a replica 2 s of Python lowering at start-up (PERF.md, PR 30)
HEAD_UNROLL = 5


def ragged_decode_plan(n: jax.Array, n_tiles: int) -> jax.Array:
    """The kernel's work list for live lengths ``n [B]`` over a cache of
    ``n_tiles`` tiles a slot, as ONE int32 vector (one copy into scalar
    memory a call): ``[count, n[0..B), slot[0..W), tile[0..W)]`` with ``W =
    B * n_tiles``; items ``w < count`` are the ``(slot, tile)`` pairs with
    ``tile * 128 < n[slot]``, in slot order, and the rest are never read.
    A function of ``n`` alone: compute it once where ``n`` is fixed (a
    decode chunk), not once a layer."""
    n = n.astype(jnp.int32)
    tiles = (n + DECODE_TILE - 1) // DECODE_TILE
    ends = jnp.cumsum(tiles)
    w = jnp.arange(n.shape[0] * n_tiles, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, w, side="right"), n.shape[0] - 1)
    tile = w - (ends - tiles)[slot]
    return jnp.concatenate([ends[-1:], n, slot, tile]).astype(jnp.int32)


def _ragged_decode_kernel(layer_ref, plan_ref, q_ref, k_hbm, v_hbm, *rest,
                          scale: float, groups: int, with_keep: bool = False):
    """One invocation walks the work list: item ``w`` is one 128-position
    tile of one slot, copied from the caches in HBM (double-buffered: item
    ``w + 1`` is in flight while ``w`` is computed, across slots too) and
    folded into that slot's running softmax.

    All arithmetic is on the VPU, a head at a time, on ``[dh, 128]`` blocks
    with positions on the lanes, as the cache stores them.  The softmax
    runs LANE-WISE: every lane keeps its own running max, denominator and
    accumulator column over the slot's tiles (no cross-lane operation per
    tile), and the 128 partial softmaxes are merged when the slot's last
    tile is done.  The two layout changes a slot needs — q's values from
    lanes to sublanes, the accumulator's from sublanes to lanes — are
    128 x 128 transposes at the slot's first and last tile.  The loops over
    heads are rolled (``HEAD_UNROLL``), so a head's running max and
    denominator sit on 8 equal rows of ``m_run`` / ``l_run``: a whole
    sublane tile, which a dynamic index can address.

    ``out_ref [B, 2 * heads_pad + n_blocks, 128]`` (one copy out a call):
    rows ``[0, heads_pad)`` the max of head ``r`` on every lane, the next
    ``heads_pad`` its denominator still spread over the lanes, then the
    accumulator as ``n_blocks`` rows of 128 consecutive ``(head, d)``.

    ``with_keep``: a further operand ``keep [B, tiles, 128]`` float32, whole
    in VMEM, as :func:`_ragged_latent_kernel` takes it: of the live positions
    a slot attends only those where it is not 0 (a window layer's ring: the
    entries inside the step's window).  A slot's first tile may then hold
    nothing it attends: its lanes keep the empty softmax, which the merge at
    the last tile weighs 0."""
    keep_ref, rest = (rest[0], rest[1:]) if with_keep else (None, rest)
    out_ref, k_buf, v_buf, sem, q_wide, acc, m_run, l_run = rest
    T = DECODE_TILE
    n_slots = q_ref.shape[0]
    kv_heads, dh = k_buf.shape[1], k_buf.shape[2]
    heads, heads_pad = kv_heads * groups, m_run.shape[0] // 8
    unroll = max(u for u in range(1, HEAD_UNROLL + 1) if kv_heads % u == 0)
    n_blocks = q_wide.shape[0] // T
    n_items = (plan_ref.shape[0] - 1 - n_slots) // 2
    layer, count = layer_ref[0], plan_ref[0]
    slot_of = lambda w: plan_ref[1 + n_slots + w]  # noqa: E731
    tile_of = lambda w: plan_ref[1 + n_slots + n_items + w]  # noqa: E731

    # slots the list never visits (nothing live) return the empty softmax
    out_ref[:, :heads_pad, :] = jnp.full(
        (n_slots, heads_pad, T), NEG_INF, jnp.float32)
    out_ref[:, heads_pad:, :] = jnp.zeros(
        (n_slots, heads_pad + n_blocks, T), jnp.float32)

    def copies(w, buf):
        start = pl.multiple_of(tile_of(w) * T, T)
        return [
            pltpu.make_async_copy(
                src.at[layer, slot_of(w), :, :, pl.ds(start, T)],
                dst.at[buf], sem.at[j, buf])
            for j, (src, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf)))]

    @pl.when(count > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def item(w, _):
        buf = w % 2
        b, t = slot_of(w), tile_of(w)
        n = plan_ref[1 + b]

        @pl.when(w + 1 < count)
        def _():
            for c in copies(w + 1, 1 - buf):
                c.start()

        @pl.when(t == 0)
        def _():  # the slot's first tile: q[r] on every lane of row r
            for i in range(n_blocks):
                rows = slice(i * T, (i + 1) * T)
                q_wide[rows, :] = jnp.broadcast_to(
                    q_ref[b, :, rows], (T, T)).T
            acc[...] = jnp.zeros_like(acc)
            l_run[...] = jnp.zeros_like(l_run)
            m_run[...] = jnp.full_like(m_run, NEG_INF)

        for c in copies(w, buf):
            c.wait()
        live = t * T + lax.broadcasted_iota(jnp.int32, (1, T), 1) < n
        if keep_ref is not None:
            # the slot's mask of tile t: row t of [tiles, T], read as the
            # aligned group of 8 rows that holds it
            t8 = pl.multiple_of((t // 8) * 8, 8)
            rows8 = keep_ref[b, pl.ds(t8, 8), :]
            mine = lax.broadcasted_iota(jnp.int32, rows8.shape, 0) == t - t8
            chosen = jnp.sum(jnp.where(mine, rows8, 0.0), axis=0, keepdims=True)
            live = live & (chosen != 0.0)

        def kv_head(kv):
            k = k_buf[buf, kv].astype(jnp.float32)  # [dh, T]
            v = v_buf[buf, kv].astype(jnp.float32)
            for g in range(groups):
                r = kv * groups + g
                rows = pl.ds(pl.multiple_of(r * dh, 8), dh)
                stat = pl.ds(pl.multiple_of(r * 8, 8), 8)
                s = jnp.sum(q_wide[rows, :] * k, axis=0, keepdims=True) * scale
                s = jnp.where(live, s, NEG_INF)
                m_prev = m_run[stat, :]  # [8, T], the head's 8 equal rows
                m_new = jnp.maximum(m_prev, s)
                alpha = jnp.exp(m_prev - m_new)
                # a lane that has only seen masked positions has s == m_new
                e = jnp.where(live, jnp.exp(s - m_new), 0.0)
                m_run[stat, :] = m_new
                l_run[stat, :] = l_run[stat, :] * alpha + e
                acc[rows, :] = acc[rows, :] * alpha[:1] + e[:1] * v

        def kv_heads_at(i, _):
            for u in range(unroll):
                kv_head(i * unroll + u)

        lax.fori_loop(0, kv_heads // unroll, kv_heads_at, None)

        @pl.when((t + 1) * T >= n)
        def _():  # the slot's last tile: merge the 128 lane-wise softmaxes
            every8 = pl.ds(0, heads_pad, stride=8)  # a head's first row
            m_lane = m_run[every8, :]
            m_all = jnp.max(m_lane, axis=1, keepdims=True)
            weight = jnp.exp(m_lane - m_all)  # 0 on a lane that saw nothing
            out_ref[b, :heads_pad, :] = jnp.broadcast_to(m_all, m_lane.shape)
            out_ref[b, heads_pad:2 * heads_pad, :] = l_run[every8, :] * weight
            m_run[every8, :] = weight  # the max is spent: row 0 carries on

            def weigh(r, _):
                rows = pl.ds(pl.multiple_of(r * dh, 8), dh)
                w8 = m_run[pl.ds(pl.multiple_of(r * 8, 8), 8), :]
                acc[rows, :] = acc[rows, :] * w8[:1]

            lax.fori_loop(0, heads, weigh, None)
            for i in range(n_blocks):
                out_ref[b, 2 * heads_pad + i:2 * heads_pad + i + 1, :] = (
                    jnp.sum(acc[i * T:(i + 1) * T, :].T, axis=0, keepdims=True))

    lax.fori_loop(0, count, item, None)


def ragged_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            layer: jax.Array, plan: jax.Array, *,
                            scale: Optional[float] = None, interpret=False,
                            keep=None, name="ragged_decode_attention"):
    """The cache half of a decode step's attention, reading only what is
    live: ``q [B, KV, G, dh]`` (one query a head a slot) against layer
    ``layer`` of the WHOLE caches ``k, v [L, B, KV, dh, S]``, which stay in
    HBM; slot ``b`` attends positions ``j < n[b]``, and the kernel copies
    in only its tiles ``t < ceil(n[b] / 128)`` (``plan``:
    :func:`ragged_decode_plan` of ``n``).  Returns the softmax
    un-normalised, flash-decoding style, for the caller to merge with its
    other keys: ``acc [B, KV, G, dh]``, running max ``m`` and denominator
    ``d [B, KV, G]``, all f32.  A slot with ``n[b] == 0`` gives ``acc = 0,
    d = 0, m = -1e30`` and moves no byte of cache.  ``scale``: the scores'
    (None: ``dh ** -0.5``).  Needs ``S % 128 == 0`` and ``dh % 8 == 0``.
    ``keep [B, S]`` (None: every live position): the positions a slot's
    queries attend (one mask a slot, shared by its heads: a window layer's
    ring read, the step's window over the entries the ring holds); the kernel
    still reads every live tile and masks; a slot that attends nothing gives
    ``d = 0``.  ``name``: the call's own in the compiled program and in a
    device trace, for a caller whose reads are to be told from another's."""
    B, KV, G, dh = q.shape
    S, T = k.shape[-1], DECODE_TILE
    assert S % T == 0 and dh % 8 == 0, (S, dh)
    heads = KV * G
    n_blocks = -(-heads * dh // T)  # whole 128-row blocks of (head, d)
    heads_pad = -(-heads // 8) * 8
    q_rows = jnp.pad(q.reshape(B, 1, heads * dh).astype(jnp.float32),
                     ((0, 0), (0, 0), (0, n_blocks * T - heads * dh)))
    whole = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    masks, more = (), {}
    if keep is not None:  # [B, tiles (whole groups of 8), T]
        tiles = -(-(S // T) // 8) * 8
        masks = (jnp.pad(keep.astype(jnp.float32).reshape(B, S // T, T),
                         ((0, 0), (0, tiles - S // T), (0, 0))),)
        more = {"with_keep": True}
    out = pl.pallas_call(
        functools.partial(_ragged_decode_kernel, groups=G,
                          scale=dh ** -0.5 if scale is None else scale, **more),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                whole(B, 1, n_blocks * T),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                *(whole(*m.shape) for m in masks),
            ],
            out_specs=whole(B, 2 * heads_pad + n_blocks, T),
            scratch_shapes=[
                pltpu.VMEM((2, KV, dh, T), k.dtype),
                pltpu.VMEM((2, KV, dh, T), v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((n_blocks * T, T), jnp.float32),  # q, lane-wide
                pltpu.VMEM((n_blocks * T, T), jnp.float32),  # accumulator
                # lane-wise max and denominator, a head's on 8 equal rows
                # (whole sublane tiles, so a rolled head loop can index them)
                pltpu.VMEM((heads_pad * 8, T), jnp.float32),
                pltpu.VMEM((heads_pad * 8, T), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (B, 2 * heads_pad + n_blocks, T), jnp.float32),
        name=name,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), plan, q_rows, k, v, *masks)
    acc = out[:, 2 * heads_pad:, :].reshape(B, n_blocks * T)[:, :heads * dh]
    return (acc.reshape(B, KV, G, dh),
            out[:, :heads, 0].reshape(B, KV, G),
            out[:, heads_pad:heads_pad + heads, :].sum(-1).reshape(B, KV, G))


# ---------------------------------------------------------------------------
# Ragged LATENT decode attention: 64 heads against ONE latent row a position
# ---------------------------------------------------------------------------


def latent_slab_attention(q: jax.Array, c: jax.Array, layer: jax.Array,
                          mask: jax.Array, *, scale: float, dv: int):
    """:func:`ragged_latent_decode_attention`'s sums as masked einsums over
    layer ``layer``'s whole padded slab ``[B, 1, dk, S]``, slot ``b`` attending
    the positions where ``mask [B, S]`` holds: what every platform can run
    (the CPU, a cache that is not whole tiles), and the plain reference the
    kernel is tested against."""
    c = lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)[:, 0]  # [B, dk, S]
    mask = mask[:, None, :]
    s = jnp.einsum("bhd,bds->bhs", q.astype(c.dtype), c,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m = s.max(-1)
    e = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)  # n == 0: nothing
    acc = jnp.einsum("bhs,bds->bhd", e.astype(c.dtype), c[:, :dv],
                     preferred_element_type=jnp.float32)
    return acc, m, e.sum(-1)


def _ragged_latent_kernel(layer_ref, plan_ref, q_ref, c_hbm, *rest,
                          scale: float, dv: int, with_keep: bool = False):
    """One invocation walks :func:`ragged_decode_plan`'s work list, as
    :func:`_ragged_decode_kernel` does: item ``w`` is one 128-position tile of
    one slot, ``[dk, 128]`` with positions on the lanes as the cache stores
    them, copied in ONCE (double-buffered: item ``w + 1`` is in flight while
    ``w`` is computed, across slots too) and used twice, as the keys of the
    slot's ``heads`` queries and, its first ``dv`` rows, as their values.

    Unlike that kernel the arithmetic is two MXU matmuls a tile in the
    cache's dtype with float32 accumulation, scores ``[heads, dk] x [dk,
    128]`` and values ``[heads, 128] x [dv, 128]^T``: every head attends the
    SAME rows, so a tile is a matrix-matrix product (139,264 FLOPs a cached
    position against 1,152 bytes), where K and V per head make a head's
    query a matrix-VECTOR product.  The softmax runs row-wise, online: a
    head's running max and denominator sit on all 128 lanes of its row of
    ``m_run`` / ``l_run``, so that every update is elementwise.  A slot's
    accumulator, max and denominator are written when its last tile is done.

    ``with_keep``: a further operand ``keep [B, tiles, 128]`` float32, whole in VMEM:
    of the live positions a slot attends only those where it is not 0 (a
    learned selection: :mod:`ray_tpu.ops.dsa`).  Every live tile is still
    copied in; a tile may hold none of the chosen positions, which the masked
    exponentials already allow for."""
    keep_ref, rest = (rest[0], rest[1:]) if with_keep else (None, rest)
    acc_ref, m_ref, d_ref, c_buf, sem, acc, m_run, l_run = rest
    T = DECODE_TILE
    n_slots = q_ref.shape[0]
    n_items = (plan_ref.shape[0] - 1 - n_slots) // 2
    layer, count = layer_ref[0], plan_ref[0]
    slot_of = lambda w: plan_ref[1 + n_slots + w]  # noqa: E731
    tile_of = lambda w: plan_ref[1 + n_slots + n_items + w]  # noqa: E731

    # slots the list never visits (nothing live) return the empty softmax
    acc_ref[...] = jnp.zeros_like(acc_ref)
    d_ref[...] = jnp.zeros_like(d_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    def copy(w, buf):
        start = pl.multiple_of(tile_of(w) * T, T)
        return pltpu.make_async_copy(
            c_hbm.at[layer, slot_of(w), 0, :, pl.ds(start, T)],
            c_buf.at[buf], sem.at[buf])

    @pl.when(count > 0)
    def _():
        copy(0, 0).start()

    def item(w, _):
        buf = w % 2
        b, t = slot_of(w), tile_of(w)
        n = plan_ref[1 + b]

        @pl.when(w + 1 < count)
        def _():
            copy(w + 1, 1 - buf).start()

        @pl.when(t == 0)
        def _():  # the slot's first tile
            acc[...] = jnp.zeros_like(acc)
            l_run[...] = jnp.zeros_like(l_run)
            m_run[...] = jnp.full_like(m_run, NEG_INF)

        copy(w, buf).wait()
        tile = c_buf[buf]                                       # [dk, T]
        s = lax.dot_general(
            q_ref[b], tile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale         # [heads, T]
        live = t * T + lax.broadcasted_iota(jnp.int32, s.shape, 1) < n
        if keep_ref is not None:
            # the slot's mask of tile t: row t of [tiles, T], read as the
            # aligned group of 8 rows that holds it (a load at an arbitrary
            # sublane is not one the chip has)
            t8 = pl.multiple_of((t // 8) * 8, 8)
            rows = keep_ref[b, pl.ds(t8, 8), :]
            mine = lax.broadcasted_iota(jnp.int32, rows.shape, 0) == t - t8
            chosen = jnp.sum(jnp.where(mine, rows, 0.0), axis=0, keepdims=True)
            live = live & (chosen != 0.0)                       # [1, T] a row
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_run[...]                       # a head's max on every lane
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # the slot's first tile holds position 0, so m_new is a real score
        # (under a selection it may hold none: e is masked either way)
        e = jnp.where(live, jnp.exp(s - m_new), 0.0)
        m_run[...] = m_new
        l_run[...] = l_run[...] * alpha + jnp.sum(e, axis=1, keepdims=True)
        acc[...] = acc[...] * alpha[:, :1] + lax.dot_general(
            e.astype(tile.dtype), tile[:dv], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [heads, dv]

        @pl.when((t + 1) * T >= n)
        def _():  # the slot's last tile
            acc_ref[b] = acc[...]
            m_ref[b] = m_run[...]
            d_ref[b] = l_run[...]

    lax.fori_loop(0, count, item, None)


def ragged_latent_decode_attention(q: jax.Array, c: jax.Array,
                                   layer: jax.Array, plan: jax.Array, *,
                                   scale: float, dv: int, keep=None,
                                   interpret=False,
                                   name="ragged_latent_decode_attention"):
    """The cache half of a decode step's LATENT attention, reading only what
    is live: ``q [B, H, dk]`` (one absorbed query a head a slot) against layer
    ``layer`` of the WHOLE latent cache ``c [L, B, 1, dk, S]`` (one row a
    position: its ``dk`` values are the position's key for every head, the
    first ``dv`` of them its value), which stays in HBM; slot ``b`` attends
    positions ``j < n[b]``, and the kernel copies in only its tiles ``t <
    ceil(n[b] / 128)``, each ONCE for scores and values (``plan``:
    :func:`ragged_decode_plan` of ``n``, the list
    :func:`ragged_decode_attention` walks).  Returns the softmax
    un-normalised for the caller to merge with its other keys: ``acc [B, H,
    dv]``, running max ``m`` and denominator ``d [B, H]``, all f32; a slot
    with ``n[b] == 0`` gives ``acc = 0, d = 0, m = -1e30`` and moves no byte
    of cache.  Needs ``S % 128 == 0`` and ``dk, dv % 8 == 0``.  ``keep [B, S]``
    (None: every live position): the positions a slot's queries attend, where
    a layer selects them (one mask a slot, shared by its heads); the kernel
    still reads every live tile and masks.  ``name``: the call's own in the
    compiled program and in a device trace, for a caller whose reads are to be
    told from another's.

    What it shares with :func:`ragged_decode_attention`: the plan, the walk,
    the double buffer, the un-normalised result.  Why it is not that kernel:
    that one multiplies on the VPU a head at a time over separate ``k`` and
    ``v`` tiles of one width; here every head reads the same tile, so the
    work is two MXU matmuls over a tile that is copied in once
    (:func:`_ragged_latent_kernel`)."""
    B, H, dk = q.shape
    S, T = c.shape[-1], DECODE_TILE
    assert S % T == 0 and dk % 8 == 0 and dv % 8 == 0, (S, dk, dv)
    assert c.shape[2:4] == (1, dk) and dv <= dk, (c.shape, dk, dv)
    whole = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    masks = ()
    if keep is not None:  # [B, tiles (whole groups of 8), T]
        tiles = -(-(S // T) // 8) * 8
        masks = (jnp.pad(keep.astype(jnp.float32).reshape(B, S // T, T),
                         ((0, 0), (0, tiles - S // T), (0, 0))),)
    acc, m, d = pl.pallas_call(
        functools.partial(_ragged_latent_kernel, scale=scale, dv=dv,
                          with_keep=keep is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole(B, H, dk), pl.BlockSpec(memory_space=pl.ANY),
                      *(whole(*m.shape) for m in masks)],
            out_specs=[whole(B, H, dv), whole(B, H, T), whole(B, H, T)],
            scratch_shapes=[
                pltpu.VMEM((2, dk, T), c.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, dv), jnp.float32),  # accumulator
                # max and denominator, a head's on every lane of its row
                pltpu.VMEM((H, T), jnp.float32),
                pltpu.VMEM((H, T), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, T), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, T), jnp.float32)],
        # q and the three results live in VMEM whole, double-buffered by the
        # pipeline: 33 slots x 64 heads x (576 bf16 + 768 f32) is 18 MB
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        name=name,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), plan, q.astype(c.dtype), c,
      *masks)
    return acc, m[..., 0], d[..., 0]


# ---------------------------------------------------------------------------
# The decode chunk's flush: a chunk's new columns merged into the tiles of the
# slots that decoded
# ---------------------------------------------------------------------------

# tiles in flight: while one is merged the next is on its way in and the one
# before on its way back
FLUSH_BUFFERS = 3


def cache_flush_plan(active: jax.Array, pos0: jax.Array, steps: int,
                     n_positions: int, written=None) -> jax.Array:
    """:func:`cache_flush`'s work list as ONE int32 vector: ``[count,
    start[0..B), slot[0..W), tile[0..W)]`` with ``W = 2 * B``.  ``start[b]``
    is where slot ``b``'s ``steps`` columns go, ``pos0[b]`` (held to
    ``n_positions - steps``, as a ``dynamic_update_slice`` holds it); items
    ``w < count`` are the ``(slot, tile)`` pairs that the columns of an
    ``active`` slot fall in, one tile or, where they cross a 128-position
    boundary, two, in slot order; the rest are never read.  A function of
    ``active`` and ``pos0`` alone: built once a chunk for every tensor.
    ``written`` (a cut chunk's ``n <= steps``, which may be traced; None:
    ``steps``): only the first ``written`` columns hold anything a later
    step attends, so the second tile is listed where THEY cross; the columns
    after them that fall in the first tile are written with it."""
    B, T = pos0.shape[0], DECODE_TILE
    assert steps <= T, steps
    start = jnp.clip(pos0.astype(jnp.int32), 0, n_positions - steps)
    tiles = active.astype(jnp.int32) * (
        1 + (start % T > T - (steps if written is None else written)))
    ends = jnp.cumsum(tiles)
    w = jnp.arange(2 * B, dtype=jnp.int32)
    # item w is of the first slot whose items end beyond it
    slot = jnp.minimum((w[:, None] >= ends[None, :]).sum(1), B - 1)
    tile = start[slot] // T + w - (ends - tiles)[slot]
    return jnp.concatenate([ends[-1:], start, slot, tile]).astype(jnp.int32)


def _cache_flush_kernel(plan_ref, cols_hbm, slab_hbm, out_hbm, tile_buf,
                        cols_buf, sem, *, steps: int):
    """One invocation walks :func:`cache_flush_plan`'s list, and under every
    item the layers: a unit of work is one 128-position tile ``[KV, dh, 128]``
    of one slot of one layer.  It is copied in with the slot's ``steps`` new
    columns of that layer ``[steps, KV * dh]``, the columns are placed at
    their lanes by a 0/1 matrix ``[steps, 128]`` on the MXU (exact: one term a
    sum, and the contraction over the columns' leading axis is the transpose
    the cache's layout asks for), every other lane keeps what it held, and the
    tile is copied back to where it came from: ``slab_hbm`` and ``out_hbm``
    are one buffer.  ``FLUSH_BUFFERS`` units are in flight: the next one's
    copies in and the last one's copy back run under this one's merge.  Both
    loops are rolled.  No unit is visited twice, so no copy waits for another
    unit's."""
    T, N = DECODE_TILE, FLUSH_BUFFERS
    n_layers, n_slots = slab_hbm.shape[:2]
    kv_heads, dh = tile_buf.shape[1:3]
    count = plan_ref[0]
    total = count * n_layers

    def unit_of(j):
        """Unit ``j``: ``(layer, slot, tile)``, the layers under an item."""
        w = j // n_layers
        return (j % n_layers, plan_ref[1 + n_slots + w],
                plan_ref[1 + 3 * n_slots + w])

    def copies(j, back: bool):
        """Unit ``j``'s copies: in (its tile, its columns), or back."""
        (layer, b, t), buf = unit_of(j), j % N
        at = (layer, b, slice(None), slice(None),
              pl.ds(pl.multiple_of(t * T, T), T))
        if back:
            return [pltpu.make_async_copy(
                tile_buf.at[buf], out_hbm.at[at], sem.at[2, buf])]
        return [
            pltpu.make_async_copy(slab_hbm.at[at], tile_buf.at[buf],
                                  sem.at[0, buf]),
            pltpu.make_async_copy(cols_hbm.at[layer, b], cols_buf.at[buf],
                                  sem.at[1, buf])]

    @pl.when(total > 0)
    def _():
        for c in copies(0, False):
            c.start()

    def unit(j, _):
        buf = j % N

        @pl.when(j + 1 < total)
        def _():
            @pl.when(j + 1 >= N)
            def _():  # the buffer's last tenant has to be back in the cache
                for c in copies(j + 1 - N, True):
                    c.wait()

            for c in copies(j + 1, False):
                c.start()

        for c in copies(j, False):
            c.wait()
        _, b, t = unit_of(j)
        # column i of the chunk lands on lane start + i of this tile, if there
        first = plan_ref[1 + b] - t * T
        lane = lax.broadcasted_iota(jnp.int32, (steps, T), 1) - first
        step = lax.broadcasted_iota(jnp.int32, (steps, T), 0)
        cols = cols_buf[buf]                # [steps, KV * dh and padding]
        placed = lax.dot_general(
            cols, (lane == step).astype(cols.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=(lax.Precision.HIGHEST if cols.dtype == jnp.float32
                       else None))[:kv_heads * dh]          # [KV * dh, 128]
        new = (lane[:1] >= 0) & (lane[:1] < steps)          # [1, 128]
        tile_buf[buf] = jnp.where(
            new[None], placed.reshape(kv_heads, dh, T).astype(tile_buf.dtype),
            tile_buf[buf])
        for c in copies(j, True):
            c.start()

    lax.fori_loop(0, total, unit, None)

    def settle(j, _):  # the last units' copies back
        for c in copies(j, True):
            c.wait()

    lax.fori_loop(jnp.maximum(total - N, 0), total, settle, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cache_flush(slab: jax.Array, new: jax.Array, plan: jax.Array, *,
                interpret=False) -> jax.Array:
    """A decode chunk's columns into the cache, touching only what they
    change: ``new [L, steps, B, KV, dh]`` (the chunk-local buffer of one
    cached tensor) into ``slab [L, B, KV, dh, S]``, slot ``b``'s ``steps``
    columns at positions ``start[b] ..`` for the slots ``plan`` lists
    (:func:`cache_flush_plan`: those active when the chunk began).  The slab
    stays in HBM and is updated IN PLACE (the result aliases it): the kernel
    copies in the one or two 128-position tiles a listed slot's columns fall
    in, a layer at a time, merges the columns at their lanes and copies the
    tile back.  A slot that is not listed is not visited: no byte of its row
    moves.  Positions are on the lanes, so what a ``dynamic_update_slice`` of
    ``steps`` columns at an arbitrary lane pays in masked partial stores, a
    row of the slab at a time, is here two copies of a tile (the
    ``cache_flush`` row of a traced run's ``breakdown.device_ops``; PERF.md
    section 6, PR 38).  Needs ``S % 128 == 0``, ``dh % 8 == 0`` and ``steps
    <= 128``."""
    L, steps, B, KV, dh = new.shape
    S, T = slab.shape[-1], DECODE_TILE
    assert slab.shape == (L, B, KV, dh, S) and new.dtype == slab.dtype
    assert S % T == 0 and dh % 8 == 0 and steps <= min(S, T), (S, dh, steps)
    # a slot's columns of a layer as one block, values on whole lane tiles
    cols = jnp.transpose(new, (0, 2, 1, 3, 4)).reshape(L, B, steps, KV * dh)
    cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, -(KV * dh) % T),))
    return pl.pallas_call(
        functools.partial(_cache_flush_kernel, steps=steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((FLUSH_BUFFERS, KV, dh, T), slab.dtype),
                pltpu.VMEM((FLUSH_BUFFERS, *cols.shape[2:]), new.dtype),
                pltpu.SemaphoreType.DMA((3, FLUSH_BUFFERS)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(slab.shape, slab.dtype),
        # operands: plan, cols, slab -> the slab is the result
        input_output_aliases={2: 0},
        name="cache_flush",
        interpret=interpret,
    )(plan, cols, slab)


# Causal self-attention over a whole sequence goes to the Pallas pair from this
# many positions up, where it is lowered for a TPU: the measured crossover on
# the v5e (PERF.md section 6, PR 43: the op-level table).  At 512 the cell that
# runs it (serve-gpt2-xl-chat) is judged on a tail that would not resolve it.
FLASH_MIN_T = 1024


def flash_plan(q_shape, k_shape, v_shape=None, *, causal: bool, window: int = 0,
               block_q: int = 128, block_k: int = 128):
    """What :func:`attention` gives the Pallas pair for these shapes where it
    is lowered for a TPU: the forward's ``(block_q, block_k)``, or None where
    the XLA paths take the call on every platform.  By shape alone.  Blocks of
    1,024, else 512, where the lengths allow (the chip's sweep: a grid step's
    fixed cost outweighs what a finer causal staircase skips; PERF.md section
    6, PR 43); no plan where a row's queries, dO and float32 dQ would not sit
    in the backward kernel's VMEM together (T = 32k with heads of 64).  A
    window layer's band goes to the pair from ``FLASH_MIN_T`` positions a
    window up (a training step at 8k under a 4,096 band, whose masked scores a
    block of queries would be gigabytes); the served families' narrower bands
    (128 - 513) keep :func:`band_attention`."""
    t_q, t_k = q_shape[-2], k_shape[-2]
    square = causal and t_q == t_k and t_q >= FLASH_MIN_T
    if window and not (square and window >= FLASH_MIN_T):
        return None
    if len(q_shape) != 4 or not (square or t_k >= 8192):
        return None
    wide = lambda b, t: max(b, next(  # noqa: E731
        (w for w in (1024, 512) if t % w == 0), b))
    bq, bk = wide(block_q, t_q), wide(block_k, t_k)
    lanes = lambda d: -(-d // 128) * 128  # noqa: E731
    dv = (v_shape or k_shape)[-1]
    resident = t_q * (12 * lanes(q_shape[-1]) + 4 * lanes(dv))
    if t_q % bq or t_k % bk or resident > _FLASH_VMEM * 5 // 8:
        return None
    return bq, bk


def attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False,
    scale: Optional[float] = None, block_q: int = 128, block_k: int = 128,
    window: int = 0,
) -> jax.Array:
    """Dispatch to an implementation by shape and, for the Pallas pair, by
    the platform the program is lowered for (module docstring: which cell
    runs which).  Single entry point used by the model zoo.

    - a window layer (``window > 0``: causal, and position ``i`` attends
      ``i - window < j <= i``) → :func:`band_attention`, or the Pallas pair
      under its band where :func:`flash_plan` says so (wide windows)
    - lowered for a TPU, block-divisible: causal self-attention from
      ``FLASH_MIN_T`` (1,024) positions up, and anything from 8k keys up →
      :func:`flash_attention_tpu` (pallas fwd + recompute-free bwd kernels;
      :func:`flash_plan`)
    - else, causal, square, block-divisible, T ≤ 4k → :func:`causal_skip_attention`
    - else T ≤ 4k → :func:`full_attention` (masked, MXU dtypes)
    - else → :func:`blockwise_attention` (O(block) memory, pads+masks any
      length; ring attention covers sharded-T)
    """
    t_q, t_k = q.shape[-2], k.shape[-2]
    assert not window or (causal and t_q == t_k), (window, causal, t_q, t_k)

    def xla(q, k, v):
        if window:
            return band_attention(q, k, v, window=window, scale=scale)
        if t_q <= _MAX_MATERIALIZED_T and t_k <= _MAX_MATERIALIZED_T:
            if causal and t_q == t_k and t_q % 256 == 0 and t_q >= 512:
                return causal_skip_attention(q, k, v, scale=scale, block=256)
            return full_attention(q, k, v, causal=causal, scale=scale)
        return blockwise_attention(
            q, k, v, causal=causal, scale=scale, block_k=block_k)

    plan = flash_plan(q.shape, k.shape, v.shape, causal=causal, window=window,
                      block_q=block_q, block_k=block_k)
    if plan is None:
        return xla(q, k, v)
    return lax.platform_dependent(
        q, k, v,
        tpu=lambda q, k, v: flash_attention_tpu(
            q, k, v, causal, scale, *plan, False, window),
        default=xla)


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
    dots would need a concat).  The dispatcher's causal path up to 4k tokens
    wherever the Pallas pair does not take the call: below ``FLASH_MIN_T``
    (the 512 prefill bucket) and off the TPU.  Inside a train step its
    materialized scores were the largest single item of the medium cell's
    step (PERF.md section 6, PR 43), which is why training left it.
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


def band_block(window: int, t: int) -> int:
    """Queries a block of a band over ``t`` positions: the smallest whole
    number of 128-position tiles that holds the window and divides the
    sequence (window 513: 1,024 of a 2,048-token bucket), else all of it."""
    return next((b for b in range(-(-window // 128) * 128, t, 128)
                 if t % b == 0), t)


def band_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, window: int,
    scale: Optional[float] = None,
) -> jax.Array:
    """A window layer's attention over a whole sequence: position ``i``
    attends ``i - window < j <= i``.  Where the sequence is whole blocks of
    ``ceil(window / 128) * 128`` positions and more than one, each block of
    queries contracts only its own block of keys and the one before it (the
    band lies inside them): ``2 * block`` keys a query whatever the length,
    where the masked full scores are ``T``.  Else (a short prompt bucket, a
    test's odd length) the band is a mask on the materialized scores."""
    *lead, t, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    # the smallest whole number of 128-position tiles that holds the window
    # and divides the sequence (window 513: 1,024 of a 2,048-token bucket)
    block = band_block(window, t)
    if t == block:
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        s = jnp.where((j <= i) & (j > i - window), _scores(q, k, scale), NEG_INF)
        return _weighted_values(jax.nn.softmax(s, axis=-1), v)
    n = t // block
    if math.prod(lead) * t * 2 * block * 4 > _BAND_SCORES_BYTES:
        return _band_attention_by_block(q, k, v, window, scale, block)
    blocks = lambda a: a.reshape(*lead, n, block, a.shape[-1])  # noqa: E731
    # block b's keys: block b - 1 (zeros before the first, masked) then b
    before = lambda a: jnp.concatenate(  # noqa: E731
        [jnp.zeros_like(a[..., :1, :, :]), a[..., :-1, :, :]], axis=-3)
    kb, vb = blocks(k), blocks(v)
    k2 = jnp.concatenate([before(kb), kb], axis=-2)  # [.., n, 2 * block, d]
    v2 = jnp.concatenate([before(vb), vb], axis=-2)
    i = block + jnp.arange(block)[:, None]           # a query's place in k2
    j = jnp.arange(2 * block)[None, :]
    first = (jnp.arange(n) == 0)[:, None, None]      # no block before block 0
    mask = (j <= i) & (j > i - window) & ~(first & (j < block))
    s = jnp.where(mask, _scores(blocks(q), k2, scale), NEG_INF)
    out = _weighted_values(jax.nn.softmax(s, axis=-1), v2)
    return out.reshape(*lead, t, v.shape[-1])


# float32 scores of a whole band above which its blocks are walked one at a
# time: 64 heads x 16,384 queries x 2,048 keys are 8.6 GB at once
_BAND_SCORES_BYTES = 2 ** 30


def _band_attention_by_block(q, k, v, window: int, scale: float, block: int):
    """:func:`band_attention`'s blocks one after another (``lax.map``): block
    ``b``'s queries against the keys of blocks ``b - 1`` and ``b`` sliced out
    of the sequence, so that one block's scores are what is held."""
    *lead, t, _ = q.shape
    ax = len(lead)
    i = block + jnp.arange(block)[:, None]           # a query's place in its keys
    j = jnp.arange(2 * block)[None, :]

    def one(b):
        # block 0 reads blocks 0 and 1 and masks the second: no block before it
        first = jnp.maximum(b - 1, 0) * block
        qb = lax.dynamic_slice_in_dim(q, b * block, block, ax)
        kb, vb = (lax.dynamic_slice_in_dim(a, first, 2 * block, ax)
                  for a in (k, v))
        at = jnp.where(b == 0, i - block, i)
        mask = (j <= at) & (j > at - window)
        s = jnp.where(mask, _scores(qb, kb, scale), NEG_INF)
        return _weighted_values(jax.nn.softmax(s, axis=-1), vb)

    out = lax.map(one, jnp.arange(t // block))       # [n, *lead, block, dv]
    return jnp.moveaxis(out, 0, ax).reshape(*lead, t, v.shape[-1])


def band_attention_after(q: jax.Array, k: jax.Array, v: jax.Array,
                         first: jax.Array, *, window: int,
                         scale: Optional[float] = None,
                         interpret: bool = False) -> jax.Array:
    """:func:`band_attention` for a prompt's PART: ``q [B, H, P, d]``, row
    ``b``'s queries at positions ``first[b] + 0..P-1``; ``k, v [B, H, before +
    P, *]``: the ``before >= window - 1`` positions that precede the part
    (whatever a ring still holds of them: one below 0 is masked, one older
    than the ring lies outside every band), then the part's own.  Lowered
    for a TPU, whole blocks of 512 on both sides: the Pallas forward kernel
    with the band as its mask, which folds the two or three key blocks a query
    block's band crosses and no score leaves VMEM (the masked scores of a
    window-513 layer's part are 0.4 GB a block of queries in float32).
    Anywhere else a block of queries (:func:`band_block`) at a time against
    the ``before + block`` keys its band lies in: the reference the kernel is
    tested against."""
    *lead, t, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    before = k.shape[-2] - t
    assert before >= window - 1, (before, window)
    first = first.astype(jnp.int32)
    if len(lead) != 2 or t % 512 or before % 512:
        return _band_after_by_block(q, k, v, first, window, scale)

    def kernel(q, k, v, first):
        # positions are the keys' indices here: the first query at ``before``,
        # and the keys that precede position 0 do not count
        at = jnp.full_like(first, before)
        pack, unpack, layout = _flash_pack(q, k, v)
        out, _ = _flash_forward(
            pack(q), pack(k), pack(v), layout=layout, causal=True, scale=scale,
            block_q=512, block_k=512, interpret=interpret, window=window,
            bounds=jnp.stack([at, at + t, jnp.maximum(before - first, 0)],
                             axis=1).reshape(-1))
        return unpack(out)

    if interpret:
        return kernel(q, k, v, first)
    return lax.platform_dependent(
        q, k, v, first, tpu=kernel,
        default=lambda q, k, v, first: _band_after_by_block(
            q, k, v, first, window, scale))


def _band_after_by_block(q, k, v, first, window: int, scale: float):
    """:func:`band_attention_after` on every platform."""
    *lead, t, d = q.shape
    before = k.shape[-2] - t
    block, ax = band_block(window, t), len(lead)

    def one(at):
        qb = lax.dynamic_slice_in_dim(q, at, block, ax)
        kb, vb = (lax.dynamic_slice_in_dim(a, at, before + block, ax)
                  for a in (k, v))
        i = (first[:, None] + at + jnp.arange(block))[:, :, None]
        j = (first[:, None] + at - before + jnp.arange(before + block))[:, None, :]
        mask = (j <= i) & (j > i - window) & (j >= 0)
        s = jnp.where(mask[:, None], _scores(qb, kb, scale), NEG_INF)
        return _weighted_values(jax.nn.softmax(s, axis=-1), vb)

    out = lax.map(one, jnp.arange(t // block) * block)  # [n, *lead, block, dv]
    return jnp.moveaxis(out, 0, ax).reshape(*lead, t, v.shape[-1])


def _attention_by_query_block(q, k, v, keep, first, scale: float):
    """The plain form of :func:`masked_attention` and
    :func:`continued_attention`, on every platform: a block of queries at a
    time against all the keys, the scores masked (query ``i`` of row ``b``
    sits at position ``first[b] + i``, 0 where ``first`` is None, and attends
    the keys at or below it that ``keep``, where there is one, lets it)."""
    t = q.shape[2]
    block = next(b for b in (256, t) if t % b == 0)
    positions = jnp.arange(k.shape[2])

    def rows(at):
        qb = lax.dynamic_slice_in_dim(q, at, block, 2)
        at_q = at + jnp.arange(block)
        if first is not None:
            at_q = first[:, None] + at_q                     # [B, block]
        mask = positions <= at_q[..., None]
        if keep is not None:
            mask = mask & (lax.dynamic_slice_in_dim(keep, at, block, 1) != 0)
        s = jnp.where(mask[:, None] if mask.ndim == 3 else mask,
                      _scores(qb, k, scale), NEG_INF)
        return _weighted_values(jax.nn.softmax(s, axis=-1), v)

    out = lax.map(rows, jnp.arange(t // block) * block)
    return jnp.moveaxis(out, 0, 2).reshape(*q.shape[:2], t, v.shape[-1])


def live_blocks(prepare, rows, live: jax.Array, block: int):
    """What a prompt's PART prepares of a slot's positions for its keys, the
    positions that are LIVE alone.  ``rows``: a tuple of ``[B, KV, bound, *]``
    arrays with something a position up to a static bound (whole blocks of
    ``block``), the cached prefix with the part's own among them;
    ``prepare(a block of each) -> a tuple of [B, H, block, *]``.  Returns the
    same for all the bound's positions IN BLOCKS, ``[bound // block, B, H,
    block, *]``, of which the blocks below ``live`` (int32, a RUNTIME value:
    the trip count of the one loop) are written and the others are NOT: they
    hold whatever the buffer held (nobody fills it; off the TPU zeros), and
    whoever reads the result keeps below ``live``, as
    :func:`continued_attention`'s kernel does.  In blocks because a trip then
    writes one entry of the leading axis, which the compiler does where the
    block is computed (a block stored among ``[B, H, bound, *]`` was a copy of
    its own, at a quarter of the HBM's rate: PERF.md section 6, PR 50)."""
    bound = rows[0].shape[2]
    assert bound % block == 0, (bound, block)
    take = lambda i: prepare(*(  # noqa: E731
        lax.dynamic_slice_in_dim(t, i * block, block, 2) for t in rows))

    def trip(i, blocks):
        # row-major, as a kernel takes its operands: left to itself the
        # compiler lays the loop's buffers out as ``prepare``'s matmuls like
        # them and re-lays ALL the bound's positions out after the loop
        return tuple(lax.dynamic_update_slice_in_dim(
            with_layout_constraint(
                t, Layout(major_to_minor=tuple(range(t.ndim)))), new[None], i, 0)
            for t, new in zip(blocks, take(i)))

    return lax.fori_loop(
        0, (live + block - 1) // block, trip,
        tuple(lax.empty((bound // block, *s.shape), s.dtype)
              for s in jax.eval_shape(take, 0)))


def continued_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        first: jax.Array, *, keep: Optional[jax.Array] = None,
                        scale: Optional[float] = None,
                        interpret: bool = False) -> jax.Array:
    """Causal attention of a prompt's PART over what precedes it and itself:
    ``q [B, H, P, dk]``, row ``b``'s queries at positions ``first[b] + 0..P-1``
    (``first [B]`` int32, a RUNTIME value: one program whatever the position);
    ``k [B, H, Tk, dk]``, ``v [B, H, Tk, dv]`` BY POSITION, index ``j`` the key
    of position ``j``: a slot's cached prefix with the part's own keys at
    ``first[b] ..``, ``Tk`` a static bound.  Query ``i`` attends ``j <=
    first[b] + i``; ``keep [B, P, Tk]`` int8 (None: all of them): of those, the
    positions a layer that selects lets the row attend, the same for every head
    (:func:`masked_attention`).  ``k`` and ``v`` may come IN BLOCKS of
    positions, ``[Tk // a, B, H, a, *]`` (:func:`live_blocks`, which writes
    only the blocks a live position falls in).

    Lowered for a TPU, whole blocks of 512: the Pallas forward kernel with the
    first position and the key length ``first[b] + P`` as prefetched scalars,
    which folds, and fetches, no key block at or beyond the length: a prompt's
    parts add up to the whole call's cells whatever ``Tk`` is.  Anywhere else
    the masked scores a block of queries at a time, the reference the kernel is
    tested against.  Forward only."""
    in_blocks = k.ndim == 5
    t_q, t_k = q.shape[2], k.shape[-2] * (k.shape[0] if in_blocks else 1)
    a_block = k.shape[-2]  # (positions a block; not in blocks: all of them)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    first = first.astype(jnp.int32)
    by_position = lambda t: t if t.ndim == 4 else jnp.moveaxis(  # noqa: E731
        t, 0, 2).reshape(*t.shape[1:3], -1, t.shape[-1])
    xla = lambda q, k, v, first, keep: _attention_by_query_block(  # noqa: E731
        q, by_position(k), by_position(v), keep, first, scale)
    block = next((b for b in (1024, 512)
                  if t_q % b == 0 and t_k % b == 0 and a_block % b == 0), None)
    if block is None:
        return xla(q, k, v, first, keep)

    def kernel(q, k, v, first, keep):
        pack, unpack, layout = _flash_pack(q, k, v)
        pack_keys = jax.vmap(pack) if in_blocks else pack  # (a block at a time)
        out, _ = _flash_forward(
            pack(q), pack_keys(k), pack_keys(v), layout=layout, causal=True,
            scale=scale,
            block_q=block, block_k=block, interpret=interpret, keep=keep,
            bounds=jnp.stack([first, first + t_q, jnp.zeros_like(first)],
                             axis=1).reshape(-1))
        return unpack(out)

    if interpret:
        return kernel(q, k, v, first, keep)
    return lax.platform_dependent(q, k, v, first, keep, tpu=kernel, default=xla)


def masked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     keep: jax.Array, *, scale: Optional[float] = None,
                     interpret: bool = False) -> jax.Array:
    """Causal self-attention whose query ROWS each attend a chosen set of
    positions: ``q, k [B, H, T, dk]``, ``v [B, H, T, dv]``, ``keep [B, T, T]``
    int8, row ``t`` non-zero where query ``t`` attends (``s <= t`` is applied
    besides), the same for every head: the softmax runs over the chosen
    positions alone.  A ``[H, T, T]`` score tensor never exists: lowered for a
    TPU (whole blocks of 512 from 1,024 positions up) the Pallas forward
    kernel takes the mask as a fourth operand, a ``[block, block]`` cell at a
    time; anywhere else a block of queries at a time against all keys.
    Forward only: the serving path's prefill."""
    *_, t, d = q.shape
    scale = scale if scale is not None else d ** -0.5

    xla = lambda q, k, v, keep: _attention_by_query_block(  # noqa: E731
        q, k, v, keep, None, scale)
    if t < FLASH_MIN_T or t % 512:
        return xla(q, k, v, keep)

    def kernel(q, k, v, keep):
        block = 1024 if t % 1024 == 0 else 512
        pack, unpack, layout = _flash_pack(q, k, v)
        out, _ = _flash_forward(
            pack(q), pack(k), pack(v), layout=layout, causal=True, scale=scale,
            block_q=block, block_k=block, interpret=interpret, keep=keep)
        return unpack(out)

    if interpret:
        return kernel(q, k, v, keep)
    return lax.platform_dependent(q, k, v, keep, tpu=kernel, default=xla)


# Off the TPU (and for what the Pallas pair does not take): above this,
# materialized scores risk HBM pressure; the O(block) blockwise path takes over.
_MAX_MATERIALIZED_T = 4096
