"""Mamba-2 state-space layer ops: what a hybrid family's recurrent mixer runs
between its input and output projections.

A head ``h`` of a request carries a state ``H [P, N]`` (``P`` values a head,
``N`` the state size) that is overwritten every token and grows with nothing::

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t        y_t = H_t C_t

with ``dt_t > 0`` a head (after the softplus), ``A < 0`` a head, ``x_t [P]`` a
head, ``B_t, C_t [N]`` shared by the heads (one group).  The ``D x_t`` skip,
the gate and the norm are the family's (:mod:`ray_tpu.models.granite_hybrid`).

- :func:`causal_conv` — the depthwise causal convolution over the last
  ``d_conv`` inputs that feeds ``x, B, C``, and :func:`conv_tail`, the last
  ``d_conv - 1`` REAL inputs of each right-padded row: what a cache keeps.
- :func:`ssd_scan` — a whole prompt: the chunked form (state-space duality).
  Inside a chunk of ``chunk`` positions the recurrence is a masked quadratic
  form, three MXU einsums; across chunks the state is carried by a
  ``lax.scan``.  A position with ``dt = 0`` changes nothing (decay 1, input
  0): how padding is kept out of the state.
- :func:`state_update` — one decode step over a cache of states ``[L, B,
  tiles, N, g * P]`` (:func:`pack_state`: ``g`` heads side by side on a
  tile's lanes): the new state written IN PLACE and ``y`` returned.  Lowered
  for a TPU at the kernel's shapes it is :func:`state_update_kernel`, a
  Pallas kernel that walks the slots :func:`state_update_plan` lists (those
  active when the chunk began), copies a slot's state in a block of tiles at
  a time, updates it and copies it back where it came from; a slot that is
  not listed moves no byte.  Anywhere else :func:`state_update_masked`, the
  ``jax.numpy`` form over every row with the rows that sit out masked, which
  is also what the tests hold the kernel to.  Chosen by
  ``lax.platform_dependent`` and the shapes; there is no flag.
- Mamba-1 (a decay per channel AND state element; the module's last
  section): :func:`selective_scan` for a prompt or a prompt's part with a
  state carried IN, :func:`selective_state_update` for a decode step over the
  same plan.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# tiles a unit of the kernel's work holds (1 MB of float32 state at the
# published 128 x 128 a tile of two heads), and units in flight: one being
# copied in, one being updated, one being copied back
TILE_BLOCK = 16
UPDATE_BUFFERS = 3


# ---------------------------------------------------------------------------
# The convolution in front of x, B, C
# ---------------------------------------------------------------------------


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                before: Optional[jax.Array] = None) -> jax.Array:
    """``silu(b + sum_k w[:, k] x_{t - (K - 1) + k})``, depthwise and causal:
    ``x [B, T, C]``, ``w [C, K]``, ``b [C]``; ``before [B, K - 1, C]`` are the
    inputs that came before position 0 (None: zeros, a prompt's start).
    Summed in float32, returned in ``x.dtype``."""
    K = w.shape[1]
    if before is None:
        before = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([before.astype(x.dtype), x], axis=1).astype(jnp.float32)
    T = x.shape[1]
    out = b.astype(jnp.float32) + sum(
        w[:, k].astype(jnp.float32) * xp[:, k:k + T] for k in range(K))
    return jax.nn.silu(out).astype(x.dtype)


def conv_tail(x: jax.Array, lengths: jax.Array, k: int,
              before: Optional[jax.Array] = None) -> jax.Array:
    """The last ``k`` inputs of each row of ``x [B, T, C]`` that are real
    (``lengths [B]``; the rows are right-padded), oldest first, zeros where a
    row has fewer: ``[B, k, C]``.  ``before [B, k, C]`` (None: zeros): the
    inputs that came before position 0 (a prompt's PART: what the slot kept
    of the part before it), which a row of fewer than ``k`` still shows."""
    if before is None:
        xp = jnp.pad(x, ((0, 0), (k, 0), (0, 0)))
    else:
        xp = jnp.concatenate([before.astype(x.dtype), x], axis=1)
    at = lengths.astype(jnp.int32)[:, None] + jnp.arange(k)[None, :]
    return jnp.take_along_axis(xp, at[:, :, None], axis=1)


# ---------------------------------------------------------------------------
# A whole prompt: the chunked scan
# ---------------------------------------------------------------------------


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over ``T`` positions from a zero state, chunked.

    Args:
        x: ``[B, T, H, P]`` (compute dtype); dt: ``[B, T, H]`` float32, the
            step after its softplus, 0 at a position that must change nothing
            (padding); a: ``[H]`` float32, negative; b, c: ``[B, T, N]``.
        chunk: positions a chunk (``mamba_chunk_size``); a ``T`` that is not
            a multiple is padded with ``dt = 0`` positions.

    Inside a chunk, with ``s_i = sum_{t <= i} dt_t a`` (the log decay from the
    chunk's start), ``y_i = sum_{j <= i} (c_i . b_j) exp(s_i - s_j) dt_j x_j +
    exp(s_i) c_i . H_in`` and ``H_out = exp(s_last) H_in + sum_j exp(s_last -
    s_j) dt_j x_j (outer) b_j``: three einsums on the MXU (operands in the
    compute dtype, float32 sums) and a ``[Q, Q]`` mask a head.  Returns ``(y
    [B, T, H, P] float32, H [B, H, P, N] float32)``: without the skip, and
    the state after the last position.
    """
    B, T, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    n_chunks = (T + pad) // Q
    by_chunk = lambda t: jnp.swapaxes(  # noqa: E731 — [chunks, B, Q, ...]
        t.reshape(B, n_chunks, Q, *t.shape[2:]), 0, 1)
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]

    def one(state, inputs):
        xc, dtc, bc, cc = inputs
        s = jnp.cumsum(dtc * a, axis=1)                       # [B, Q, H], <= 0
        s_h = jnp.swapaxes(s, 1, 2)                           # [B, H, Q]
        dtx = (dtc[..., None] * xc.astype(jnp.float32)).astype(xc.dtype)
        g = jnp.einsum("bin,bjn->bij", cc, bc,
                       preferred_element_type=jnp.float32)    # [B, Q, Q]
        # exp(s_i - s_j) below the diagonal (<= 1), nothing above it: masked
        # in the exponent, so that no position overflows
        seg = jnp.where(causal, s_h[..., :, None] - s_h[..., None, :], -jnp.inf)
        m = (g[:, None] * jnp.exp(seg)).astype(xc.dtype)      # [B, H, Q, Q]
        y = jnp.einsum("bhij,bjhp->bihp", m, dtx,
                       preferred_element_type=jnp.float32)
        y = y + jnp.exp(s)[..., None] * jnp.einsum(
            "bin,bhpn->bihp", cc.astype(jnp.float32), state)
        to_end = jnp.exp(s[:, -1:, :] - s)                    # [B, Q, H]
        state = (jnp.exp(s[:, -1, :])[:, :, None, None] * state
                 + jnp.einsum(
                     "bjhp,bjn->bhpn",
                     (to_end[..., None] * dtx.astype(jnp.float32)).astype(xc.dtype),
                     bc, preferred_element_type=jnp.float32))
        return state, y

    state, y = lax.scan(one, jnp.zeros((B, H, P, N), jnp.float32),
                        tuple(by_chunk(t) for t in (x, dt, b, c)))
    y = jnp.swapaxes(y, 0, 1).reshape(B, T + pad, H, P)
    return y[:, :T], state


# ---------------------------------------------------------------------------
# One decode step over the cache of states
# ---------------------------------------------------------------------------
#
# How a cache lays a state out: ``[tiles, N, g * P]``, ``g`` heads side by
# side on the lanes of a tile (``g * P`` = 128 at the published ``P`` = 64: two
# heads a tile) and the state size ``N`` on the sublanes.  A step is then
# ``tile = tile * decay_row + b_column * dtx_row`` and ``y_row = sum over the
# sublanes of tile * c_column``: what differs a head (the decay, ``dt x``, and
# ``y``) is a ROW, which the chip broadcasts and stores for nothing, and what
# would need a column a head (the layout ``[H, P, N]``: three lane reductions
# a head, 45 % of the roofline on the chip: PERF.md section 6, PR 42) is the
# ``b`` and ``c`` of the whole slot, made once a block of tiles.


def heads_per_tile(heads: int, head_dim: int) -> int:
    """Heads a tile of the cache's layout holds side by side: as many as
    fill the 128 lanes, at most all of them."""
    return min(heads, max(1, LANES // head_dim))


def pack_state(state: jax.Array, g: int) -> jax.Array:
    """``[B, H, P, N]`` (a head's state as the recurrence writes it) -> the
    cache's layout ``[B, H / g, N, g * P]``."""
    B, H, P, N = state.shape
    return state.reshape(B, H // g, g, P, N).transpose(0, 1, 4, 2, 3).reshape(
        B, H // g, N, g * P)


def unpack_state(tiles: jax.Array, g: int) -> jax.Array:
    """:func:`pack_state`'s inverse: ``[B, T, N, g * P]`` -> ``[B, T * g, P,
    N]``."""
    B, T, N, W = tiles.shape
    return tiles.reshape(B, T, N, g, W // g).transpose(0, 1, 3, 4, 2).reshape(
        B, T * g, W // g, N)


def _rows(decay, dtx, tiles: int):
    """The heads' decay ``[B, H]`` and ``dt x`` ``[B, H, P]`` as rows of the
    cache's tiles: ``[B, tiles, g * P]`` each."""
    B, H, P = dtx.shape
    return (jnp.broadcast_to(decay[:, :, None], (B, H, P)).reshape(B, tiles, -1),
            dtx.reshape(B, tiles, -1))


def state_update_plan(active: jax.Array) -> jax.Array:
    """:func:`state_update_kernel`'s work list as ONE int32 vector: ``[count,
    slot[0..B)]``, the ``active`` slots first and in slot order; the rest are
    never read.  A function of who was active when the chunk began alone:
    built once a chunk for every layer and step (as
    :func:`ray_tpu.ops.attention.cache_flush_plan` is)."""
    active = active.astype(bool)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    return jnp.concatenate([active.sum(dtype=jnp.int32)[None], order])


def kernel_shapes(state: jax.Array) -> bool:
    """Whether :func:`state_update_kernel` takes a cache of this shape: tiles
    one lane tile wide (two heads of the published 64), the state size whole
    sublane tiles, float32."""
    _, _, _, N, W = state.shape
    return W == LANES and N % 8 == 0 and state.dtype == jnp.float32


def state_update_masked(state, layer, decay, dtx, b, c, active):
    """The step in ``jax.numpy`` over EVERY row of layer ``layer`` of ``state
    [L, B, tiles, N, g * P]``: ``H = decay H + dtx (outer) b`` for the
    ``active [B]`` rows, the others kept bit for bit; ``y = H c``.  ``decay
    [B, H]``, ``dtx [B, H, P]``, ``b, c [B, N]``, all float32.  What every
    platform can run, and the reference the kernel is held to.  Returns
    ``(state, y [B, H, P])``; ``y`` of a row that sat out is of its unchanged
    state."""
    old = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    decay_rows, dtx_rows = _rows(decay, dtx, old.shape[1])
    new = (old * decay_rows[:, :, None, :]
           + b[:, None, :, None] * dtx_rows[:, :, None, :])
    new = jnp.where(active[:, None, None, None], new, old)
    y = (new * c[:, None, :, None]).sum(2)
    return (lax.dynamic_update_index_in_dim(state, new, layer, 0),
            y.reshape(dtx.shape))


def _state_update_kernel(layer_ref, plan_ref, u_ref, state_hbm, out_hbm, y_ref,
                         buf, sem):
    """One invocation walks :func:`state_update_plan`'s list, and under every
    slot its blocks of ``TILE_BLOCK`` tiles: a unit of work is ``[TILE_BLOCK,
    N, 128]`` of one slot's state.  It is copied in, every tile of it updated
    where it lies (a row broadcast, a column broadcast, a multiply-add: no
    reduction over the lanes) and its ``y`` row summed over the sublanes, and
    copied back to where it came from: ``state_hbm`` and ``out_hbm`` are one
    buffer.  ``UPDATE_BUFFERS`` units are in flight.  The slot's small inputs
    are ``u_ref[b]`` (``[2 tiles + 8, 128]``: the tiles' ``dt x`` rows, their
    decay rows, then ``b`` and ``c``); ``b`` and ``c`` become columns once a
    unit, by a masked sum over the lanes of their rows."""
    n_buf, tb, N, W = buf.shape
    tiles = state_hbm.shape[2]
    n_blocks = tiles // tb
    layer, total = layer_ref[0], plan_ref[0] * n_blocks

    def unit_of(j):
        return plan_ref[1 + j // n_blocks], j % n_blocks

    def copy(j, back: bool):
        (b, k), at = unit_of(j), j % n_buf
        rows = (layer, b, pl.ds(pl.multiple_of(k * tb, tb), tb))
        if back:
            return pltpu.make_async_copy(buf.at[at], out_hbm.at[rows],
                                         sem.at[1, at])
        return pltpu.make_async_copy(state_hbm.at[rows], buf.at[at],
                                     sem.at[0, at])

    @pl.when(total > 0)
    def _():
        copy(0, False).start()

    diagonal = (lax.broadcasted_iota(jnp.int32, (N, W), 0)
                == lax.broadcasted_iota(jnp.int32, (N, W), 1))
    which = lax.broadcasted_iota(jnp.int32, (tb, W), 0)

    def unit(j, _):
        at = j % n_buf

        @pl.when(j + 1 < total)
        def _():
            @pl.when(j + 1 >= n_buf)
            def _():  # the buffer's last tenant has to be back in the cache
                copy(j + 1 - n_buf, True).wait()

            copy(j + 1, False).start()

        copy(j, False).wait()
        b, k = unit_of(j)
        first = pl.multiple_of(k * tb, tb)
        dtx = u_ref[b, pl.ds(first, tb), :]                   # [tb, 128]
        decay = u_ref[b, pl.ds(tiles + first, tb), :]
        # b and c, rows of the state size, as columns broadcast over the lanes
        column = lambda row: jnp.sum(  # noqa: E731 — [N, 1]
            jnp.where(diagonal, row, 0.0), axis=-1, keepdims=True)
        b_col = column(u_ref[b, 2 * tiles:2 * tiles + 1, :][:, :N])
        c_col = column(u_ref[b, 2 * tiles + 1:2 * tiles + 2, :][:, :N])

        def tile(i, y):
            mine = which == i
            row = lambda t: jnp.sum(  # noqa: E731 — [1, 128], this tile's
                jnp.where(mine, t, 0.0), axis=0, keepdims=True)
            new = buf[at, i] * row(decay) + b_col * row(dtx)
            buf[at, i] = new
            return jnp.where(
                mine, jnp.sum(new * c_col, axis=0, keepdims=True), y)

        y_ref[b, pl.ds(first, tb), :] = lax.fori_loop(
            0, tb, tile, jnp.zeros((tb, W), jnp.float32))
        copy(j, True).start()

    lax.fori_loop(0, total, unit, None)

    def settle(j, _):  # the last units' copies back
        copy(j, True).wait()

    lax.fori_loop(jnp.maximum(total - n_buf, 0), total, settle, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_update_kernel(state, layer, decay, dtx, b, c, plan, *,
                        interpret=False):
    """:func:`state_update_masked` for the slots ``plan`` lists
    (:func:`state_update_plan`), touching nothing else: ``state [L, B, tiles,
    N, 128]`` float32 stays in HBM and is updated IN PLACE (the result aliases
    it); a listed slot's state of layer ``layer`` is copied in ``TILE_BLOCK``
    tiles at a time, updated and copied back.  A slot that is not listed is
    not visited: no byte of its state moves, and its ``y`` is 0.  A listed
    slot that stopped mid-chunk is frozen by its inputs (decay 1, ``dtx`` 0:
    the caller's).  ``ssm.state_update`` in a traced run."""
    L, B, tiles, N, W = state.shape
    assert kernel_shapes(state) and N <= LANES, state.shape
    tb = min(TILE_BLOCK, tiles)
    assert tiles % tb == 0 and tb % 8 == 0, (tiles, tb)
    decay_rows, dtx_rows = _rows(decay, dtx, tiles)
    lanes = lambda row: jnp.pad(row, ((0, 0), (0, W - N)))[:, None, :]  # noqa: E731
    # a slot's small inputs as one block
    u = jnp.concatenate([
        dtx_rows, decay_rows, lanes(b), lanes(c),
        jnp.zeros((B, 6, W), jnp.float32)], axis=1).astype(jnp.float32)
    whole = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    new, y = pl.pallas_call(
        _state_update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole(*u.shape), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), whole(B, tiles, W)],
            scratch_shapes=[
                pltpu.VMEM((UPDATE_BUFFERS, tb, N, W), jnp.float32),
                pltpu.SemaphoreType.DMA((2, UPDATE_BUFFERS)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, tiles, W), jnp.float32)],
        # operands: layer, plan, u, state -> the state is the first result
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=32 << 20),
        name="ssm_state_update",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), plan, u, state)
    # the slots the plan lists (a comparison, not a scatter: B x B bits)
    listed = ((plan[1:][None, :] == jnp.arange(B)[:, None])
              & (jnp.arange(B)[None, :] < plan[0])).any(1)
    return new, jnp.where(listed[:, None, None], y.reshape(dtx.shape), 0.0)


def state_update(state, layer, decay, dtx, b, c, active, plan=None):
    """One decode step of layer ``layer`` over the cache of states, in place
    -> ``(state, y [B, H, P] float32)``.  ``active [B]``: the rows that take
    the step NOW (a row that stopped mid-chunk is frozen).  ``plan``
    (:func:`state_update_plan` of the rows active when the chunk began; None:
    no kernel at these shapes): lowered for a TPU the kernel over those rows,
    anywhere else the masked form over every row."""
    args = (state, layer, jnp.where(active[:, None], decay, 1.0),
            jnp.where(active[:, None, None], dtx, 0.0), b, c)
    if plan is None:
        return state_update_masked(*args, active)
    return lax.platform_dependent(
        *args, active, plan,
        tpu=lambda s, l, da, dx, b, c, act, plan: state_update_kernel(
            s, l, da, dx, b, c, plan),
        default=lambda s, l, da, dx, b, c, act, plan: state_update_masked(
            s, l, da, dx, b, c, act))


# ---------------------------------------------------------------------------
# Mamba-1: a decay PER (channel, state) element
# ---------------------------------------------------------------------------
#
# A channel ``c`` of a request carries ``H[c, :] in R^N`` and
#
#     H_t[c, n] = exp(dt_t[c] A[c, n]) H_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
#     y_t[c] = sum_n H_t[c, n] C_t[n]
#
# with ``dt`` a CHANNEL (after its softplus) and ``A [C, N]`` learned: the
# decay is no scalar a head, so neither :func:`ssd_scan`'s matmul form nor
# :func:`state_update_kernel`'s ``tile x decay + b x dtx`` computes it.  Both
# forms below keep the state as ``[N, R, LANES]``: the state index LEADING and
# the channels as ``R`` rows of 128 lanes (``C = R x 128``; a narrower model:
# one row of ``C``), so that what a step does to one ``n`` is elementwise on
# dense ``[R, 128]`` tiles, ``B_t[n]`` and ``C_t[n]`` are scalars of the
# step, and ``y`` is a plain running sum over ``n``: no reduction over lanes
# or sublanes anywhere.


def channel_tiles(channels: int) -> Tuple[int, int]:
    """``(rows, lanes)`` of a channel vector in the state's layout."""
    lanes = min(LANES, channels)
    assert channels % lanes == 0, channels
    return channels // lanes, lanes


# positions a grid step of :func:`_selective_scan_kernel` walks
SCAN_CHUNK = 64


def selective_scan_steps(dtx, dt, a, b, c, state):
    """The recurrence a position at a time (``lax.scan``): what every platform
    can run, and the plain form the kernel is held to.  ``dtx, dt [B, T, C]``
    float32, ``a [N, C]``, ``b, c [B, T, N]``, ``state [B, N, C]`` -> ``(y [B,
    T, C], state)``."""
    def one(h, inputs):
        dtx_t, dt_t, b_t, c_t = inputs
        h = (jnp.exp(dt_t[:, None, :] * a) * h
             + b_t[:, :, None] * dtx_t[:, None, :])
        return h, (h * c_t[:, :, None]).sum(1)

    state, y = lax.scan(one, state, tuple(
        jnp.swapaxes(t, 0, 1) for t in (dtx, dt, b, c)))
    return jnp.swapaxes(y, 0, 1), state


def _selective_scan_kernel(dtx_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
                           y_ref, h_ref):
    """One grid step is ``SCAN_CHUNK`` positions of one row; the state is the
    OUTPUT block ``h_ref [N, R, 128]``, resident across the row's grid steps
    (taken from ``h0_ref`` at the first).  A position updates the state an
    ``n`` at a time, every operand a dense ``[R, 128]`` tile; ``b_ref``,
    ``c_ref`` hold ``B_t[n]``, ``C_t[n]`` on all 128 lanes of a row."""
    N = h_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = h0_ref[...]

    def step(t, _):
        dt, dtx = dt_ref[t], dtx_ref[t]
        y = jnp.zeros_like(dt)
        for n in range(N):
            h = (h_ref[n] * jnp.exp(dt * a_ref[n])
                 + b_ref[t, pl.ds(n, 1), :] * dtx)
            h_ref[n] = h
            y = y + h * c_ref[t, pl.ds(n, 1), :]
        y_ref[t] = y

    lax.fori_loop(0, dtx_ref.shape[0], step, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_kernel(dtx, dt, a, b, c, state, *, interpret=False):
    """:func:`selective_scan_steps` as a Pallas kernel that walks time with
    the state resident in VMEM (``[N, R, 128]`` float32: 328 KB at the
    published 5,120 channels x 16): ``T`` a multiple of ``SCAN_CHUNK``, the
    channels whole lanes.  ``state [B, N, R, 128]`` in and out (the cache's
    layout).  ``ssm_selective_scan`` in a traced run."""
    B, T, C = dtx.shape
    N = a.shape[0]
    R, L = channel_tiles(C)
    assert T % SCAN_CHUNK == 0 and state.shape == (B, N, R, L), (T, state.shape)
    tiles = lambda t: t.astype(jnp.float32).reshape(B, T, R, L)  # noqa: E731
    lanes = lambda t: jnp.broadcast_to(  # noqa: E731 — B_t[n] on every lane
        t.astype(jnp.float32)[..., None], (B, T, N, L))
    by_time = lambda *tail: pl.BlockSpec(  # noqa: E731
        (None, SCAN_CHUNK, *tail), lambda i, j: (i, j, 0, 0))
    a_row = lambda *tail: pl.BlockSpec(  # noqa: E731
        (None, *tail), lambda i, j: (i, 0, 0, 0))
    y, state = pl.pallas_call(
        _selective_scan_kernel,
        grid=(B, T // SCAN_CHUNK),
        in_specs=[by_time(R, L), by_time(R, L),
                  pl.BlockSpec((N, R, L), lambda i, j: (0, 0, 0)),
                  by_time(N, L), by_time(N, L), a_row(N, R, L)],
        out_specs=[by_time(R, L), a_row(N, R, L)],
        out_shape=[jax.ShapeDtypeStruct((B, T, R, L), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, R, L), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        name="ssm_selective_scan",
        interpret=interpret,
    )(tiles(dtx), tiles(dt), a.astype(jnp.float32).reshape(N, R, L),
      lanes(b), lanes(c), state)
    return y.reshape(B, T, C), state


def selective_scan(x, dt, a, b, c, d, state_in=None, lengths=None):
    """A prompt, or a prompt's PART, through the recurrence: ``x [B, T, C]``
    (the convolved inputs), ``dt [B, T, C]`` float32 after its softplus, ``a
    [N, C]`` float32 (negative), ``b, c [B, T, N]``, ``d [C]`` the skip,
    ``state_in [B, N, R, 128]`` float32 in the cache's layout (None: zeros, a
    prompt's start), ``lengths [B]`` the real positions of right-padded rows
    (None: all).  A padded position changes nothing (its ``dt`` is 0: decay 1,
    input 0).  Returns ``(y [B, T, C] float32 with the skip, the state after
    the last REAL position, in the cache's layout)``.  Lowered for a TPU at
    the kernel's shapes :func:`selective_scan_kernel`, anywhere else
    :func:`selective_scan_steps`; there is no flag."""
    B, T, C = x.shape
    N = a.shape[0]
    R, L = channel_tiles(C)
    xf = x.astype(jnp.float32)
    if lengths is not None:
        dt = jnp.where((jnp.arange(T)[None, :] < lengths[:, None])[..., None],
                       dt, 0.0)
    if state_in is None:
        state_in = jnp.zeros((B, N, R, L), jnp.float32)
    dtx = dt * xf

    def steps(dtx, dt, a, b, c, state):
        y, state = selective_scan_steps(
            dtx, dt, a, b.astype(jnp.float32), c.astype(jnp.float32),
            state.reshape(B, N, C))
        return y, state.reshape(B, N, R, L)

    if L == LANES and T % SCAN_CHUNK == 0:
        y, state = lax.platform_dependent(
            dtx, dt, a, b, c, state_in, tpu=selective_scan_kernel, default=steps)
    else:
        y, state = steps(dtx, dt, a, b, c, state_in)
    return y + d.astype(jnp.float32) * xf, state


def selective_kernel_shapes(state: jax.Array) -> bool:
    """Whether :func:`selective_state_update_kernel` takes a cache of this
    shape ``[L, B, N, R, 128]``: whole lanes, whole sublane tiles, float32."""
    return (state.shape[-1] == LANES and state.shape[-2] % 8 == 0
            and state.dtype == jnp.float32)


def selective_state_update_masked(state, layer, dt, dtx, a, b, c):
    """One decode step of layer ``layer`` over EVERY row of ``state [L, B, N,
    R, 128]``: ``dt, dtx [B, C]`` float32 (0 and 0 for a row that sits the
    step out: its state is then kept bit for bit), ``a [N, C]``, ``b, c [B,
    N]``.  What every platform can run, and the reference the kernel is held
    to.  Returns ``(state, y [B, C])``."""
    old = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    B, N, R, L = old.shape
    rows = lambda t: t.reshape(B, 1, R, L)  # noqa: E731
    new = (old * jnp.exp(rows(dt) * a.reshape(1, N, R, L))
           + b[:, :, None, None] * rows(dtx))
    y = (new * c[:, :, None, None]).sum(1)
    return (lax.dynamic_update_index_in_dim(state, new, layer, 0),
            y.reshape(B, R * L))


def _selective_update_kernel(layer_ref, plan_ref, dt_ref, dtx_ref, a_ref,
                             b_ref, c_ref, state_hbm, out_hbm, y_ref, buf, sem):
    """One invocation walks :func:`state_update_plan`'s list: a unit of work
    is one slot's whole state of the layer, ``[N, R, 128]`` (328 KB as
    published), copied in, updated an ``n`` at a time on dense tiles and
    copied back to where it came from (``state_hbm`` and ``out_hbm`` are one
    buffer), ``UPDATE_BUFFERS`` units in flight."""
    n_buf, N = buf.shape[0], buf.shape[1]
    layer, total = layer_ref[0], plan_ref[0]

    def copy(j, back: bool):
        b, at = plan_ref[1 + j], j % n_buf
        if back:
            return pltpu.make_async_copy(buf.at[at], out_hbm.at[layer, b],
                                         sem.at[1, at])
        return pltpu.make_async_copy(state_hbm.at[layer, b], buf.at[at],
                                     sem.at[0, at])

    y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(total > 0)
    def _():
        copy(0, False).start()

    def unit(j, _):
        at = j % n_buf

        @pl.when(j + 1 < total)
        def _():
            @pl.when(j + 1 >= n_buf)
            def _():  # the buffer's last tenant has to be back in the cache
                copy(j + 1 - n_buf, True).wait()

            copy(j + 1, False).start()

        copy(j, False).wait()
        b = plan_ref[1 + j]
        dt, dtx = dt_ref[b], dtx_ref[b]
        y = jnp.zeros_like(dt)
        for n in range(N):
            h = (buf[at, n] * jnp.exp(dt * a_ref[n])
                 + b_ref[b, pl.ds(n, 1), :] * dtx)
            buf[at, n] = h
            y = y + h * c_ref[b, pl.ds(n, 1), :]
        y_ref[b] = y
        copy(j, True).start()

    lax.fori_loop(0, total, unit, None)

    def settle(j, _):  # the last units' copies back
        copy(j, True).wait()

    lax.fori_loop(jnp.maximum(total - n_buf, 0), total, settle, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_state_update_kernel(state, layer, dt, dtx, a, b, c, plan, *,
                                  interpret=False):
    """:func:`selective_state_update_masked` for the slots ``plan`` lists
    (:func:`state_update_plan`), touching nothing else: ``state [L, B, N, R,
    128]`` stays in HBM and is updated IN PLACE (the result aliases it).  A
    slot that is not listed moves no byte and its ``y`` is 0; a listed slot
    that stopped mid-chunk is frozen by its inputs (``dt`` 0, ``dtx`` 0: the
    caller's).  ``ssm_selective_state_update`` in a traced run."""
    L, B, N, R, W = state.shape
    assert selective_kernel_shapes(state), state.shape
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    lanes = lambda t: jnp.broadcast_to(f32(t)[..., None], (B, N, W))  # noqa: E731
    whole = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    new, y = pl.pallas_call(
        _selective_update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole(B, R, W), whole(B, R, W), whole(N, R, W),
                      whole(B, N, W), whole(B, N, W),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), whole(B, R, W)],
            scratch_shapes=[
                pltpu.VMEM((UPDATE_BUFFERS, N, R, W), jnp.float32),
                pltpu.SemaphoreType.DMA((2, UPDATE_BUFFERS)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, R, W), jnp.float32)],
        # operands: layer, plan, dt, dtx, a, b, c, state -> the first result
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=32 << 20),
        name="ssm_selective_state_update",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), plan,
      f32(dt).reshape(B, R, W), f32(dtx).reshape(B, R, W),
      f32(a).reshape(N, R, W), lanes(b), lanes(c), state)
    return new, y.reshape(B, R * W)


def selective_state_update(state, layer, dt, dtx, a, b, c, active, plan=None):
    """One Mamba-1 decode step of layer ``layer`` over the cache of states, in
    place -> ``(state, y [B, C] float32)``, without the skip.  ``active [B]``:
    the rows that take the step NOW (a row that sits out or stopped mid-chunk
    is frozen: ``dt`` and ``dtx`` 0).  ``plan`` (:func:`state_update_plan` of
    the rows active when the chunk began; None: no kernel at these shapes):
    lowered for a TPU the kernel over those rows, anywhere else the masked
    form over every row."""
    args = (state, layer, jnp.where(active[:, None], dt, 0.0),
            jnp.where(active[:, None], dtx, 0.0), a,
            b.astype(jnp.float32), c.astype(jnp.float32))
    if plan is None:
        return selective_state_update_masked(*args)
    return lax.platform_dependent(
        *args, plan,
        tpu=selective_state_update_kernel,
        default=lambda *args: selective_state_update_masked(*args[:-1]))
