"""KV-cache autoregressive generation for the decoder LMs (GPT-2, Llama,
EXAONE-MoE, Kimi-K2, Granite-hybrid, dots3-note, EvaByte, Phi-4-flash,
Keye-VL).

The reference snapshot has no inference engine at all — serving wraps a
plain forward (``python/ray/serve/_private/replica.py:250`` calls the user
callable); generation/KV-cache is delegated to user code.  Here decode is a
first-class TPU path, designed for XLA:

- **What a config's cache holds is ONE table** (:func:`cache_layout`: a row
  a cached tensor, with whose it is, how it is arranged and what a position
  of it holds; its docstring lists the rows).  :func:`init_cache`, a prompt's
  writes (:func:`_write_prompt`), a decode chunk's (:func:`decode_chunk`'s
  tail) and the serve engine's byte counts walk it, and no other line spells
  a tensor's name.  What the table cannot say is how each arrangement is
  READ, which is where the kernels are chosen:

  1. a SLAB of K and V per KV head is read below a slot's live length
     (:func:`_cache_scores`); a slab of latent rows (multi-head latent
     attention; :mod:`ray_tpu.models.kimi_k2`) the same way by the latent
     kernel, absorbed: the row is every head's key and its first values are
     the position's value vector; prefill runs un-absorbed, k and v a head for
     the call only, and keeps the rows its block hands over.
  2. a full layer that SELECTS (:mod:`ray_tpu.ops.dsa`;
     :mod:`ray_tpu.models.dots3_note`, :mod:`ray_tpu.models.keye_vl`) scores
     the slab's index keys below the slot's live length AND the chunk-local
     ones of the steps up to its own, keeps the ``top-k`` under ONE exact
     threshold (:func:`_select`), and its attention's softmax runs over those
     alone: the kernel still reads every live tile and is handed the selection
     as a mask; a cut chunk selects as a whole one does.
  3. a RING is read by every row, whole and masked by the step's window
     (:func:`_ring_mask`), or, where it holds latent rows (or the config says
     so: ``cfg.window_rings_by_tile``) in whole tiles, the tiles of the
     entries a live slot's ring holds (:func:`ring_read_by_tile`;
     :func:`_latent_ring_scores`, :func:`_ring_scores`).
  4. a STATE (a Mamba-2 mixer, :mod:`ray_tpu.models.granite_hybrid`; Mamba-1,
     a decay per channel AND state element, :mod:`ray_tpu.models.phi4_flash`)
     is overwritten in place by a decode step, and only for the rows that
     take the step (:func:`ray_tpu.ops.ssm.state_update`,
     :func:`ray_tpu.ops.ssm.selective_state_update`: lowered for a TPU a
     Pallas kernel over the slots that were active when the chunk began); a
     cut chunk has advanced it ``n`` steps; there is nothing to flush.
  5. a WINDOW AND ITS SUMMARIES (:mod:`ray_tpu.models.evabyte`,
     :mod:`ray_tpu.ops.eva`) trade places as a request grows: a query at
     ``i`` reads places ``0 .. i % window`` of the window and the first ``(i
     // window) x (window // chunk)`` summary rows, each through
     :func:`_cache_scores` over its own live tiles, merged
     (:func:`ray_tpu.ops.eva.merge`) before the chunk-local columns join.  The
     moment a slot's window fills, its positions are pooled
     (:func:`ray_tpu.ops.eva.pool_chunks`, by the layer's learned vectors),
     appended to the summaries, and the window starts again at place 0: in a
     prefill call for every window the call's tokens fill, in decode WITH THE
     CHUNK'S FLUSH (:func:`_roll_over`), which is why no chunk may straddle a
     window's end: the caller cuts the chunk there (``n``; the serve engine's
     second reason for a cut; :func:`generate` does the same on the device).
     A prompt's PART (whole windows, at an offset that is a multiple of the
     window) attends the cached summaries laid ahead of its own keys
     (:func:`ray_tpu.ops.eva.windowed_attention`).

  None of these has to be OWNED by the layer that reads it: a family may say
  that some layers read ANOTHER layer's slab and that some cache nothing
  (:func:`layer_windows`'s entries :func:`reads_layer` and ``UNCACHED``;
  :func:`shared_cache`; :mod:`ray_tpu.models.phi4_flash`: ONE full layer's K
  and V read by every cross-attention layer above it, gated memory units
  between them).  The table then has ONE slab a tensor (``[1, B, ...]``)
  whatever the readers; a decode step's readers attend the cache below
  ``live`` and the owner's chunk-local columns (:func:`_decode_attend`), and a
  prefill runs the layers above the slab for a row's LAST position alone
  (:func:`prefill_at`'s ``final``).

- **A prefill that continues** (:func:`prefill_at`'s ``offsets``): a prompt
  need not go into its slot in ONE call.  A call's rows may be PARTS: row
  ``b``'s tokens sit at positions ``offsets[b] ..`` of a slot whose earlier
  positions an earlier call prefilled, its queries attend what the cache holds
  of those AND the part's own keys under one softmax, its columns are written
  at ``offsets[b]``, and ``pos`` becomes ``offsets + lengths``.  The offsets
  are runtime VALUES (one program whatever they are; the serve engine
  interleaves the parts of a long prompt with its decode chunks:
  :mod:`ray_tpu.serve.llm`).  By arrangement: a slab's layer reads the slot's
  cached positions up to a static bound with the part's own placed among them,
  a latent layer's rows up-projected to per-head k and v by the block's own
  weights as the part's are, and a layer that selects scores cached and own
  index keys under ONE threshold (the whole prompt's selection); lowered for a
  TPU the flash kernel takes the key length as a prefetched scalar and
  neither folds nor fetches a block beyond it, so a prompt's parts add up to
  the whole call's cells.  A ring's reads the positions just ahead of the part
  by their places in the ring and leaves the last ``ring`` positions of
  prefix-and-part behind.  A state's can where the family says so
  (``cfg.state_carried_in``: Mamba-1's scan takes the slot's state IN and its
  convolution the slot's last inputs, zeros for a part at offset 0); a family
  that does not (Granite's Mamba-2: its chunked scan starts from zero) keeps
  whole prompts (:func:`can_continue`).  Without ``offsets`` a call is a whole
  prompt from position 0, the slot written from scratch.
- **One block per family**: prefill and decode run the block training
  runs (``gpt2.block``, ``llama.block``) and hand it their attention middle
  (:mod:`ray_tpu.models.transformer`): prefill the full causal attention,
  keeping the layer's k, v for the cache; decode the write into the
  chunk-local buffer and :func:`_decode_attend`.  ``FAMILIES`` below is
  the one place that knows the families: a new one is one module.
- **Static shapes everywhere**: the cache is a fixed ``[L, B, KV, dh, S]``
  buffer (positions last: the decode step's scores come out with S on the
  lanes, and the chip stores the cache unpadded); positions are dynamic
  *values*, never dynamic shapes, so the decode chunk compiles once and
  runs for every token.  The ALLOCATION is padded; what a step reads of it
  is not (last point below).
- **Per-slot positions**: each batch slot sits at its own offset (``pos``
  vector), which is what iteration-level continuous batching needs
  (Orca-style; see :mod:`ray_tpu.serve.llm`).
- **Chunked decode**: ``decode_chunk`` runs N decode+sample steps inside
  one device computation (``lax.scan``) so the host syncs once per chunk,
  not per token.  A caller that knows an answer ends ``n < N`` steps into a
  chunk CUTS it (``n``, a runtime scalar): the same step in a loop with
  that bound, one further program whatever ``n`` is, so the answer's last
  token does not wait for steps nobody needs (the serve engine's rule:
  :meth:`ray_tpu.serve.llm.GenerationEngine.step`).  The chunk-local buffer
  stays ``N`` wide and is flushed as ever.
- **No step writes the cache**: a chunk's new K/V columns live in a
  chunk-local buffer ``[L, steps, B, KV, dh]``.  Layer ``l`` of step ``i``
  writes there with one ``dynamic_update_slice`` at ``(l, i)`` — the same
  index for every slot, a contiguous block — and attends the cache below
  ``pos0`` (the slot's position when the chunk began) together with the
  buffer up to ``i``, under one softmax.  (A latent layer's buffer holds the
  chunk's rows, ``[L, steps, B, 1, row]``, one tensor again.)  After the
  last step the columns go into the cache at ``pos0[b]``, once, in place:
  the FLUSH (:func:`_flush`), a cached tensor at a time.
- **The flush touches only what it changes**: positions are on the lanes,
  so ``steps`` columns at an arbitrary ``pos0[b]`` are a partial store into
  every row of a 128-lane tile.  Lowered for a TPU with a cache of whole
  tiles, the flush is a Pallas kernel over the slots that were ``active``
  when the chunk began (:func:`ray_tpu.ops.attention.cache_flush`): it
  copies in the one tile ``[KV, dh, 128]`` of a layer that holds
  ``pos0[b]`` (and the next where the columns cross into it), places the
  columns at their lanes, keeps every other lane and copies the tile back;
  the slab stays in HBM, aliased input to output, so the donated cache is
  merged where it lies.  A slot that sat the chunk out is not visited.
  Anywhere else one ``dynamic_update_slice`` a slot, every slot's
  (:func:`_flush_slices`, the reference the kernel is tested against).  The
  rings keep their 0/1-matrix flush.  On the chip the kernel is the
  ``cache_flush`` row of a traced run's ``breakdown.device_ops``; how much of
  the slab it touches is ``perf_stats()["cache_tiles"]``: ``flushed``
  against ``padded`` (numbers: PERF.md section 6, PRs 28 and 38).
- **A step reads only the live cache**: a slot attends the cache below
  ``live[b]`` — ``pos0[b]``, or 0 for a slot that sits the chunk out (the
  scratch row, idle slots, finished requests).  Lowered for a TPU with a
  cache of whole 128-position tiles (``S % 128 == 0``; the engine rounds
  its cache up to that), the cache half of the attention is a Pallas kernel
  that copies in, per slot, only the tiles below ``live[b]`` and returns the
  softmax un-normalised (:func:`ray_tpu.ops.attention.ragged_decode_attention`;
  :func:`_decode_attend` merges it with the chunk-local columns under one
  max and denominator; a latent layer walks the same list with
  :func:`ray_tpu.ops.attention.ragged_latent_decode_attention`, two MXU
  matmuls over a tile copied in once).  Anywhere else — the CPU, a cache of
  another
  length such as :func:`generate`'s — the same sums run as masked einsums
  over layer ``l``'s whole padded slab (:func:`_cache_scores_slab`), which
  is also the reference the kernel is tested against.  Chosen by
  ``lax.platform_dependent`` and the cache's shape; there is no flag.
  (What reading the padded slab cost: PERF.md section 6, PR 30.)

The flush invariant: after a chunk, every position ``j < pos[b]`` of slot
``b`` holds a column that prefill or an ACTIVE step wrote.  For a slot that
was active when the chunk began the flush writes all ``steps`` columns at
``pos0[b] ..``, so those after a mid-chunk EOS land at or beyond its frozen
``pos`` — harmless: a slot never attends an index its own ``pos`` hasn't
covered, the next flush starts at ``pos`` again, and prefill overwrites
``[0, Tp)`` (a prompt in parts: ``[offset, offset + Tp)``, part after part
from 0) and resets ``pos`` when the slot is reused.  The columns a CUT
chunk flushes beyond its ``n`` steps (zeros) land past ``pos`` the same way;
in a ring they overwrite entries that held positions a whole ring back,
outside every window still to come.  A slot that
decodes needs ``pos0 + steps <= S`` (the engine sizes the cache ``bucket +
max_new + chunk``).  For a slot that sat the chunk out the kernel writes
NOTHING; the slice updates write its ``steps`` columns at its frozen
``pos`` too (where that is nearer the end than ``steps`` the update clamps),
onto the slot's own dead columns, which is as harmless.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import (
    dots3_note,
    evabyte,
    exaone_moe,
    gpt2,
    granite_hybrid,
    keye_vl,
    kimi_k2,
    llama,
    phi4_flash,
)
from ray_tpu.models.transformer import _attend
from ray_tpu.ops import dsa, eva, ssm
from ray_tpu.ops.attention import (
    DECODE_TILE,
    band_attention_after,
    cache_flush,
    cache_flush_plan,
    continued_attention,
    latent_slab_attention,
    live_blocks,
    ragged_decode_attention,
    ragged_decode_plan,
    ragged_latent_decode_attention,
)

# The one table that knows the families: name -> the family's module.  A
# module here names its config class (``Config``) and presets (``SIZES``)
# and has ``init``, ``kv_heads``, ``embed``, ``block`` and ``unembed``.  A
# family whose layers are all alike keeps their parameters stacked
# (``params["blocks"]``, leaves ``[L, ...]``) and the layer loops below run
# them rolled; one that mixes kinds of layer lists them (``params["layers"]``),
# its config says which layers attend a window (``sliding_windows``), its
# ``block`` takes the layer's ``window`` and the ``valid`` tokens, and the
# loops run unrolled, each kind of layer against its own kind of cache.  A
# family whose layers cache ONE latent row a position instead of K and V per
# head says so in its config (``latent_cache``, with its own
# ``attention_scale``), and its ``block`` hands the attention middle that row
# as a fourth argument; where its full layers select the positions they read
# (``index_cache``) the block hands the middle the layer's index queries, head
# weights and index keys as a fifth, and where its window layers cache latent
# rows too, their width and scale (``window_latent_cache``,
# ``window_attention_scale``); its ``block`` also takes ``context``, which is
# handed the call's rows and the layer's up-projection of any rows and gives the
# call's keys and values (a prompt's part: those of what the cache holds ahead
# of it, then its own).  A family some of whose layers attend NOTHING and
# carry a per-request state instead says so in ``sliding_windows``
# (``RECURRENT``) and ``state_cache``; its parameters are a stack of the
# recurrent layers (``params[kind]``, leaves ``[L_kind, ...]``) beside a list
# of the others, ``cfg.layer_runs`` says which layers follow each other, its
# ``block`` takes the layer's ``kind`` and the mixer's middle, and the loops
# roll each run of recurrent layers.  A family whose layers keep an exact
# window and pooled summaries of the windows before it says so in its config
# (``summary_cache``: window and chunk), keeps its parameters stacked, and has
# ``pooling(p)``: a layer's (or the stack's) pooling vectors, which the prefill
# hands the attention middle and the decode's roll-over pools a full window by.
# A family whose upper layers read ONE lower layer's slab says so in
# ``sliding_windows`` (entries ``READS - k`` and ``UNCACHED``) and has
# ``lower_stack`` (the layers below the slab, rolled, threading the caller's
# carry through middles ``mamba(at, x, p, carry)`` and ``attend(at, q, k, v,
# carry)``), ``shared_kv``, ``shared_layer`` and ``upper_stack`` (the layers
# above, handed the slab's read), ``mamba_whole`` and ``mamba_step``.  A family
# whose requests are not token ids alone (a vision tower in front of the text
# path: :mod:`ray_tpu.models.keye_vl`) says that a slot's rotary position is
# its cached length plus an offset of its own (``rope_delta_cache``), its
# ``embed`` takes ``visual`` (the tower's rows and where they stand) and its
# ``block`` positions ``[B, 3, T]``; where such a family SELECTS over a slab of
# K and V per head (``index_cache`` without ``latent_cache``) its block hands
# the middle ``None`` for the row and the index as a fifth argument.
FAMILIES = {"gpt2": gpt2, "llama": llama, "exaone_moe": exaone_moe,
            "kimi_k2": kimi_k2, "granite_hybrid": granite_hybrid,
            "dots3_note": dots3_note, "evabyte": evabyte,
            "phi4_flash": phi4_flash, "keye_vl": keye_vl}

# a layer's entry in :func:`layer_windows` that attends no position at all
RECURRENT = granite_hybrid.RECURRENT
# ... that caches nothing and mixes no positions either (a gated memory unit)
UNCACHED = phi4_flash.UNCACHED


def reads_layer(w: int) -> Optional[int]:
    """The layer whose slab a layer with entry ``w`` of :func:`layer_windows`
    reads (it caches nothing of its own: cross attention over ANOTHER layer's
    K and V), or None."""
    return phi4_flash.READS - w if w <= phi4_flash.READS else None


def family_of(cfg):
    """The module of the family ``cfg`` is a config of."""
    for fam in FAMILIES.values():
        if type(cfg) is fam.Config:
            return fam
    raise TypeError(f"no generation support for config {type(cfg).__name__}")


def serving_layout(cfg, params):
    """The family's ``init`` tree as :func:`prefill_at`, :func:`decode_chunk`
    and :func:`generate` take it: what a family lays out ONCE when an engine
    takes its parameters and never in a step (its ``serving_layout`` hook: the
    held experts' gate and up matrices side by side,
    :func:`ray_tpu.ops.moe.gate_up_side_by_side`; a family without a hook
    serves from ``init``'s tree as it is).  IN PLACE on ``params``' dicts and
    lists, never on a leaf, so that a source is let go before the next copy is
    made: hand over a tree whose containers are yours (``jax.tree.map(lambda
    a: a, params)`` makes one; the engine's cast of its parameters is one)."""
    lay = getattr(family_of(cfg), "serving_layout", None)
    return lay(params) if lay else params


def kv_heads(cfg) -> int:
    return family_of(cfg).kv_heads(cfg)


def layer_windows(cfg) -> Tuple[int, ...]:
    """Per layer, the positions it attends: 0 is every one (a full layer), a
    window size ``W`` the last ``W`` (a window layer; ``cfg.sliding_windows``
    of a family that mixes them, with one size), ``RECURRENT`` none (a layer
    that carries a state: :func:`state_cache`), ``UNCACHED`` none and no state
    either, and an entry :func:`reads_layer` knows the slab of the full layer
    it names (:func:`shared_cache`)."""
    windows = tuple(getattr(cfg, "sliding_windows", ()) or (0,) * cfg.n_layers)
    assert len(windows) == cfg.n_layers and len(
        {w for w in windows if w > 0}) <= 1, windows
    return windows


def shared_cache(cfg) -> Optional[int]:
    """For a family whose upper layers read ONE lower layer's K and V (a
    decoder-hybrid-decoder: :mod:`ray_tpu.models.phi4_flash`): the layer that
    owns the slab, the family's only full layer.  Such a family's module has
    ``lower_stack``, ``shared_kv``, ``shared_layer`` and ``upper_stack``, and
    its prefill runs the layers above the slab for a prompt's LAST position
    alone.  None: every attention layer owns what it reads."""
    owners = {reads_layer(w) for w in layer_windows(cfg)} - {None}
    assert len(owners) <= 1, owners
    return owners.pop() if owners else None


def state_cache(cfg) -> Optional[dict]:
    """For a family with recurrent layers: ``{"ssm": (shape a slot a layer,
    dtype), "conv": ((inputs kept, width), dtype)}``, what a slot holds of
    such a layer whatever its position (``cfg.state_cache``).  None: every
    layer caches positions."""
    return getattr(cfg, "state_cache", None)


def latent_cache(cfg, window: bool = False) -> Optional[Tuple[int, int]]:
    """For a family whose layers cache one latent row a position: ``(values a
    row holds, of which the first are the position's value vector)``, the
    row's other use being the position's key for every head
    (``cfg.latent_cache``; ``window``: the same of its window layers, whose
    row may be another width, ``cfg.window_latent_cache``).  None: K and V per
    KV head."""
    return getattr(cfg, "window_latent_cache" if window else "latent_cache", None)


def index_cache(cfg) -> Optional[Tuple[int, int]]:
    """For a family whose full layers SELECT the positions a query attends
    (:mod:`ray_tpu.ops.dsa`): ``(values of the index key a position caches
    beside its row, positions a query selects)`` (``cfg.index_cache``).  None:
    a query attends every position."""
    return getattr(cfg, "index_cache", None)


def rope_offset(cfg) -> bool:
    """Whether a slot's ROTARY position is its cached length plus an offset a
    slot (``cfg.rope_delta_cache``: positions on three axes, where a video's
    tokens advance the position by less than their count:
    :func:`ray_tpu.models.keye_vl.rope_index`).  The cache then holds
    ``rope_delta``; causality, the selection and every write stay on cache
    positions."""
    return bool(getattr(cfg, "rope_delta_cache", False))


def summary_cache(cfg) -> Optional[Tuple[int, int]]:
    """For a family whose layers keep an EXACT window and a compressed memory
    of what came before it (:mod:`ray_tpu.ops.eva`): ``(window, chunk)``: the
    positions of a block-aligned window, and how many of them one pooled key
    and value stand for once the window is full (``cfg.summary_cache``).
    None: a cached position is never rewritten."""
    return getattr(cfg, "summary_cache", None)


def window_positions(window: int) -> int:
    """Places a slot's exact window holds: the window and a tile of slack (the
    window again where it is shorter than a tile), because a chunk's flush
    writes all its ``steps`` columns from the slot's place on, those a cut
    chunk did not run too, and the last may start one place short of the
    window's end.  A decode chunk needs ``steps`` at most the slack."""
    return window + min(window, DECODE_TILE)


def summary_rows(cfg, max_len: int) -> int:
    """Summary rows a slot of ``max_len`` positions can come to hold: a row a
    chunk of every window it can FILL (whole tiles where a window is)."""
    return _summary_rows(*summary_cache(cfg), max_len)


def _summary_rows(window: int, chunk: int, max_len: int) -> int:
    rows = max(1, max_len // window) * (window // chunk)
    return -(-rows // DECODE_TILE) * DECODE_TILE if window % DECODE_TILE == 0 else rows


def attention_scale(cfg, window: bool = False) -> Optional[float]:
    """A family's own score scale for one kind of layer (None: ``dh **
    -0.5``)."""
    return getattr(
        cfg, "window_attention_scale" if window else "attention_scale", None)


def ring_positions(window: int) -> int:
    """Positions a slot's ring holds for a window layer: twice the window,
    and from one tile up whole 128-position tiles (256 at the published 128,
    1,152 for 513: what the chip stores either way, lanes being padded to
    whole tiles, and what a kernel that walks tiles can read), a ring under
    a tile just twice the window.  A decode chunk needs ``steps <= ring -
    window + 1``: the flush writes all ``steps`` columns, those of a slot
    that stopped mid-chunk too, and what they overwrite has to lie outside
    every window still to come."""
    ring = 2 * window
    return ring if ring < DECODE_TILE else -(-ring // DECODE_TILE) * DECODE_TILE


# whose a cached tensor is (``Cached.layers``): the layers that attend every
# position, those that attend a window, those that carry a state, or no
# layer's: the slot's own
FULL_LAYERS, WINDOW_LAYERS, STATE_LAYERS, THE_SLOT = (
    "full", "window", "state", "slot")
# how a cached tensor is arranged (``Cached.arrangement``):
# - SLAB ``[L, B, *row, max_len]``: positions last, every position kept;
# - RING ``[L, B, *row, ring_positions(window)]``: position ``j`` at ``j %
#   ring``, so a slot costs the ring however long its context;
# - WINDOW ``[L, B, *row, window_positions(window)]``: the CURRENT window's
#   positions, ``j`` at ``j % window``, BLOCK-ALIGNED: when a slot reaches a
#   multiple of the window every place is dead at once (no ring: none of
#   :func:`_ring_mask`, :func:`_ring_holds` describes it);
# - SUMMARY ``[L, B, *row, summary_rows]``: one pooled row a ``chunk`` of
#   positions of every window the slot has FILLED, of the WINDOW tensor the
#   row names (``pools``);
# - STATE ``[L, ...]`` with the slots at ``slot_axis``: nothing by position,
#   a slot costs the same at position 10 and at 100,000;
# - SLOT ``[B]``: a scalar a slot.
SLAB, RING, WINDOW, SUMMARY, STATE, SLOT = (
    "slab", "ring", "window", "summary", "state", "slot")


@dataclasses.dataclass(frozen=True)
class Cached:
    """One cached tensor of a config: a row of :func:`cache_layout`."""
    name: str                    # its key in the cache
    layers: str                  # whose: FULL_LAYERS, ..., THE_SLOT
    count: int                   # ... and how many of them (0: the slot's)
    arrangement: str             # SLAB, RING, WINDOW, SUMMARY, STATE, SLOT
    row: Tuple[int, ...]         # what one position (STATE: one slot) holds
    dtype: Any
    window: int = 0              # RING, WINDOW, SUMMARY: the layer's window
    chunk: int = 0               # SUMMARY: the positions a row stands for
    pools: Optional[str] = None  # SUMMARY: the WINDOW tensor it pools
    slot_axis: int = 1           # STATE: where the slots stand

    def positions(self, max_len: int) -> int:
        """Positions (SUMMARY: rows) a slot of ``max_len`` holds here."""
        if self.arrangement == RING:
            return ring_positions(self.window)
        if self.arrangement == WINDOW:
            return window_positions(self.window)
        if self.arrangement == SUMMARY:
            return _summary_rows(self.window, self.chunk, max_len)
        assert self.arrangement == SLAB, self
        return max_len

    def shape(self, n_slots: int, max_len: int) -> Tuple[int, ...]:
        if self.arrangement == SLOT:
            return (n_slots,)
        if self.arrangement == STATE:
            shape = [self.count, *self.row]
            shape.insert(self.slot_axis, n_slots)
            return tuple(shape)
        return (self.count, n_slots, *self.row, self.positions(max_len))

    def row_bytes(self) -> int:
        """Bytes one position (STATE: one slot) of one layer holds."""
        return math.prod(self.row) * jnp.dtype(self.dtype).itemsize


def cache_layout(cfg) -> Tuple[Cached, ...]:
    """WHAT A CONFIG'S CACHE HOLDS, a row a tensor, decided here and nowhere
    else: :func:`init_cache` makes the rows, a prompt's writes
    (:func:`_write_prompt`), a chunk's (:func:`decode_chunk`'s tail) and the
    serve engine's byte counts walk them, and a new family's cache is rows of
    this table plus what is new about how it is READ.  Chosen by what the
    config says of its layers (:func:`layer_windows`, an owner of a shared
    slab counting once; the predicates above), never by the family's name.
    The order is the order a prompt's writes are emitted in: the slot's own,
    then the full layers' by position, their summaries, the window layers',
    the recurrent layers'.  (``pos [B]`` is the cache's own, ``routed`` a
    program's result: neither is a row.)

    - a full layer of K and V per KV head: ``k``, ``v``, a position ``2 x KV
      x dh`` values (``cfg.cache_head_dim`` where what is cached is not
      ``head_dim`` wide);
    - a full latent layer (:func:`latent_cache`): ONE tensor ``c``, a position
      one row (the normed latent, then the rotated key all heads share: 512 +
      64 values as published) and NO ``v``: the row is every head's key and
      its first values are the position's value vector;
    - a full layer that SELECTS what it reads (:func:`index_cache`): an index
      key ``idx_k`` beside each position (128 values as published), of a
      latent row or of K and V per head;
    - a layer that compacts (:func:`summary_cache`): ``k``, ``v`` are the
      exact WINDOW, and ``ks``, ``vs`` the summaries that pool them;
    - a window layer: rings ``k_ring``, ``v_ring``, or one of latent rows of
      their own width, ``c_ring`` (``cfg.window_latent_cache``: 1,088 values
      as published);
    - a recurrent layer (:func:`state_cache`): ``ssm`` in float32 and
      ``conv``, the layer's last inputs, which keeps its slots third (beside
      the width, so that the chip pads neither);
    - a family whose rotary positions are not cache positions
      (:func:`rope_offset`): ``rope_delta``, int32, what a slot's rotary
      position is ahead of its cached length (0 or less).

    A combination the bodies below do not serve is refused here, not left out
    of the cache in silence."""
    windows = layer_windows(cfg)
    n_full, n_state = windows.count(0), windows.count(RECURRENT)
    n_window = sum(w > 0 for w in windows)
    latent, index, compact = latent_cache(cfg), index_cache(cfg), summary_cache(cfg)
    assert not (latent and n_state), "no body runs latent layers between runs"
    assert not (compact and (latent or index or n_window or n_state)), (
        "a family that compacts has layers alike, stacked", windows)
    # K and V per KV head (a latent family has no such row)
    heads = None if latent else (
        kv_heads(cfg), getattr(cfg, "cache_head_dim", cfg.head_dim))
    full = partial(Cached, layers=FULL_LAYERS, count=n_full, dtype=cfg.dtype)
    ring = partial(Cached, layers=WINDOW_LAYERS, count=n_window,
                   arrangement=RING, dtype=cfg.dtype, window=max(windows))
    rows = []
    if rope_offset(cfg):
        rows.append(Cached("rope_delta", THE_SLOT, 0, SLOT, (), jnp.int32))
    if latent:
        rows.append(full("c", arrangement=SLAB, row=(1, latent[0])))
    elif compact:
        rows += [full(name, arrangement=WINDOW, row=heads, window=compact[0])
                 for name in ("k", "v")]
    else:
        rows += [full(name, arrangement=SLAB, row=heads) for name in ("k", "v")]
    if index:
        rows.append(full("idx_k", arrangement=SLAB, row=(1, index[0])))
    if compact:
        rows += [full(name, arrangement=SUMMARY, row=heads, window=compact[0],
                      chunk=compact[1], pools=pooled)
                 for name, pooled in (("ks", "k"), ("vs", "v"))]
    if n_window and latent:
        rows.append(ring("c_ring", row=(1, latent_cache(cfg, True)[0])))
    elif n_window:
        rows += [ring(name, row=heads) for name in ("k_ring", "v_ring")]
    if n_state:
        for name, axis in (("ssm", 1), ("conv", 2)):
            shape, dtype = state_cache(cfg)[name]
            rows.append(Cached(name, STATE_LAYERS, n_state, STATE, tuple(shape),
                               dtype, slot_axis=axis))
    return tuple(rows)


def cache_rows(cfg, *arrangements: str,
               layers: Optional[str] = None) -> Tuple[Cached, ...]:
    """The rows of :func:`cache_layout` arranged so (any, where none is
    named) and, ``layers``, owned so."""
    return tuple(r for r in cache_layout(cfg)
                 if (not arrangements or r.arrangement in arrangements)
                 and layers in (None, r.layers))


def cached_tensors(cfg, window: bool = False) -> Tuple[str, ...]:
    """The names of what :func:`init_cache` holds a position of a full layer
    (K and V per head, and the index key where the layer selects; the one
    latent row; the row and the index key of a layer that selects) or,
    ``window``, of a window layer's ring: a filter over :func:`cache_layout`."""
    return tuple(r.name for r in (
        cache_rows(cfg, RING) if window
        else cache_rows(cfg, SLAB, WINDOW, layers=FULL_LAYERS)))


def ring_read_by_tile(cache, cfg) -> bool:
    """Whether a decode step reads the window layers' rings of ``cache`` a
    tile of a live slot at a time (:func:`decode_chunk`): rings of latent
    rows, or rings of K and V where the config says so
    (``cfg.window_rings_by_tile``), in whole tiles, the row whole sublanes.
    Otherwise every row's whole ring, masked."""
    rings = cache_rows(cfg, RING)
    if not rings or not (latent_cache(cfg)
                         or getattr(cfg, "window_rings_by_tile", False)):
        return False
    ring = cache[rings[0].name]
    return ring.shape[-1] % DECODE_TILE == 0 and ring.shape[3] % 8 == 0


def init_cache(cfg, n_slots: int, max_len: int) -> Dict[str, jax.Array]:
    """The fixed-size cache of ``n_slots`` slots of ``max_len`` positions:
    every row of :func:`cache_layout`, zeros of its ``shape`` and ``dtype``
    under its name, plus per-slot ``pos``.  (Positions LAST, so the scores of
    a decode step come out with them on the lanes and the chip stores the
    cache unpadded.)"""
    cache = {row.name: jnp.zeros(row.shape(n_slots, max_len), row.dtype)
             for row in cache_layout(cfg)}
    cache["pos"] = jnp.zeros((n_slots,), jnp.int32)
    return cache


def _cache_scores_slab(q, k_all, v_all, l, mask, scale=None):
    """The cache half of :func:`_decode_attend` as masked einsums over layer
    ``l``'s whole padded slab ``[B, KV, dh, S]``, slot ``b`` attending the
    positions where ``mask [B, S]`` holds: what every platform can run, the
    plain reference the kernel is held to (``mask``: the positions below
    ``n[b]``; same result as
    :func:`ray_tpu.ops.attention.ragged_decode_attention`), and how a window
    layer's ring is read (:func:`_ring_mask`).  ``scale``: a family's own
    (None: ``dh ** -0.5``)."""
    k, v = (lax.dynamic_index_in_dim(a, l, 0, keepdims=False)
            for a in (k_all, v_all))
    dh = k.shape[2]
    mask = mask[:, None, None, :]
    # keep the cache reads in bf16 (f32 accumulation via
    # preferred_element_type) — upcasting the whole cache each step
    # would double the dominant HBM traffic of decode
    s = jnp.einsum("bkgd,bkds->bkgs", q, k.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    s = s / (dh ** 0.5) if scale is None else s * scale
    s = jnp.where(mask, s, -1e30)
    m = s.max(-1)
    e = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)  # n == 0: nothing
    acc = jnp.einsum("bkgs,bkds->bkgd", e.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return acc, m, e.sum(-1)


def _cache_scores(q, k_all, v_all, l, n, plan, scale=None, keep=None, **named):
    """``q [B, KV, G, dh]`` against positions ``j < n[b]`` of layer ``l`` of
    the whole caches ``[L, B, KV, dh, S]``: ``(acc, m, d)``, the softmax
    un-normalised.  Lowered for a TPU, with a cache of whole 128-position
    tiles, the Pallas kernel that copies in only the tiles below ``n[b]``;
    anywhere else the masked einsums over the slab.  Decided by what the
    program is lowered for and by the cache's shape, never by a flag.
    ``named``: the kernel call's own name.  ``keep [B, S]`` bool (None: all):
    of the positions ``j < n[b]`` the ones a layer that selects lets slot ``b``
    attend; both forms still read what is live and mask."""
    below = lambda n: jnp.arange(k_all.shape[-1])[None, :] < n[:, None]  # noqa: E731
    if keep is not None:
        if plan is None:
            return _cache_scores_slab(q, k_all, v_all, l, below(n) & keep, scale)
        return lax.platform_dependent(
            q, k_all, v_all, l, n, plan, keep,
            tpu=lambda q, k, v, l, n, plan, keep: ragged_decode_attention(
                q, k, v, l, plan, scale=scale, keep=keep, **named),
            default=lambda q, k, v, l, n, plan, keep: _cache_scores_slab(
                q, k, v, l, below(n) & keep, scale))
    if plan is None:
        return _cache_scores_slab(q, k_all, v_all, l, below(n), scale)
    return lax.platform_dependent(
        q, k_all, v_all, l, n, plan,
        tpu=lambda q, k, v, l, n, plan: ragged_decode_attention(
            q, k, v, l, plan, scale=scale, **named),
        default=lambda q, k, v, l, n, plan: _cache_scores_slab(
            q, k, v, l, below(n), scale))


def _latent_cache_scores(q, c_all, l, n, plan, *, scale: float, dv: int,
                         keep=None, **named):
    """:func:`_cache_scores` for a latent layer: ``q [B, 1, H, row]``, every
    head against the ONE row a position of layer ``l`` of ``c_all [L, B, 1,
    row, S]``, whose first ``dv`` values are the position's value vector.
    The kernel (:func:`ray_tpu.ops.attention.ragged_latent_decode_attention`)
    and the masked einsums over the slab are chosen as there.  ``keep [B, S]``
    bool (None: all): of the positions ``j < n[b]`` the ones a layer that
    selects lets slot ``b`` attend; both forms still read what is live and
    mask.  A window layer's ring is read the same way
    (:func:`_latent_ring_scores`; ``named``: the kernel call's own name)."""
    below = lambda n: jnp.arange(c_all.shape[-1])[None, :] < n[:, None]  # noqa: E731

    def slab(q, c, l, n, plan=None, keep=None):
        mask = below(n) if keep is None else below(n) & keep
        return latent_slab_attention(q, c, l, mask, scale=scale, dv=dv)

    if plan is None:
        out = slab(q[:, 0], c_all, l, n, keep=keep)
    else:
        out = lax.platform_dependent(
            q[:, 0], c_all, l, n, plan, keep,
            tpu=lambda q, c, l, n, plan, keep: ragged_latent_decode_attention(
                q, c, l, plan, scale=scale, dv=dv, keep=keep, **named),
            default=slab)
    return tuple(a[:, None] for a in out)


def _ring_holds(n, ring: int) -> jax.Array:
    """``[B, ring]``: the position entry ``r`` of slot ``b``'s ring holds once
    positions ``j < n[b]`` are written, the newest with ``j % ring == r``;
    negative where no position has filled the entry yet."""
    last = n.astype(jnp.int32)[:, None] - 1
    return last - (last - jnp.arange(ring)[None, :]) % ring


def _ring_mask(live, pos, window: int, ring: int) -> jax.Array:
    """``[B, ring]``: the ring entries a window layer's query at position
    ``pos[b]`` attends: those that hold a position (``live``: where the slot
    stood when the chunk began, all of it flushed; 0 for a slot that sits the
    chunk out, which attends nothing here) inside the window, ``j > pos -
    window``."""
    j = _ring_holds(live, ring)
    return (j >= 0) & (j > pos[:, None] - window)


@partial(jax.jit, static_argnames=("window", "scale", "dv"))
def _latent_ring_scores(q, c_ring, l, live, pos, plan, *, window: int,
                        scale: float, dv: int):
    """The cache half of a latent window layer's decode attention, read from
    its ring: :func:`_latent_cache_scores` over the entries a slot's ring
    holds, the step's window (:func:`_ring_mask`) its ``keep``, the kernel's
    call under a name of its own, so that a device trace's rows of the full
    layers' kernel stay the full layers'.  Jitted: the window layers of a step
    are alike, so ONE trace and one lowering of the kernel serve all of them,
    and the cut chunk's program finds the whole chunk's trace (what three
    further traces a program cost a replica's start: PERF.md section 6,
    PR 52)."""
    ring = c_ring.shape[-1]
    return _latent_cache_scores(
        q, c_ring, l, jnp.minimum(live, ring), plan, scale=scale, dv=dv,
        keep=_ring_mask(live, pos, window, ring),
        name="ragged_latent_ring_attention")


def _ring_scores(q, k_ring, v_ring, l, live, pos, plan, *, window: int,
                 scale: Optional[float]):
    """The cache half of a window layer's decode attention, read from its
    rings of K and V a live slot's tiles at a time: :func:`_cache_scores` over
    the entries a slot's ring holds (``plan``: of ``min(live, ring)``), the
    step's window (:func:`_ring_mask`) the kernel's ``keep``, the call under a
    name of its own; anywhere else the masked einsums over every row's ring."""
    keep = _ring_mask(live, pos, window, k_ring.shape[-1])
    if plan is None:
        return _cache_scores_slab(q, k_ring, v_ring, l, keep, scale)
    return lax.platform_dependent(
        q, k_ring, v_ring, l, keep, plan,
        tpu=lambda q, k, v, l, keep, plan: ragged_decode_attention(
            q, k, v, l, plan, scale=scale, keep=keep,
            name="ragged_ring_attention"),
        default=lambda q, k, v, l, keep, plan: _cache_scores_slab(
            q, k, v, l, keep, scale))


def _decode_attend(q, cached, k_new, v_new, i, window: int = 0,
                   scale: Optional[float] = None, keep_new=None) -> jax.Array:
    """q ``[B, H, 1, dh]`` of chunk step ``i`` against the keys a slot has:
    what its cache holds from before the chunk, and the chunk's own columns
    ``[steps, B, KV, dh]`` at ``t <= i`` (of a window layer: ``t > i -
    window`` too).  ``cached(q)`` gives the cache half for ``q [B, KV, G,
    dh]``, un-normalised (:func:`_cache_scores` over the positions ``j < n``
    of a full layer, ``n`` where the slot stood when the chunk began and 0
    for a slot that was inactive then; :func:`_cache_scores_slab`, or a latent
    family's :func:`_latent_cache_scores`, over a window layer's ring).  ONE
    softmax over both score sets: the halves are merged under the shared max
    and denominator.  GQA folds the query heads
    onto their KV head by reshape (no materialized repeat).  A latent layer
    is the same sums with one KV head, a ``scale`` of its own (None: ``dh **
    -0.5``) and values narrower than keys (``v_new``: the first values of
    ``k_new``).  ``keep_new [B, steps]`` bool (None: all): of the chunk's own
    columns the ones a layer that selects lets a slot attend (``cached`` then
    applies the same selection's other half)."""
    B, H, _, dh = q.shape
    KV, steps = k_new.shape[2], k_new.shape[0]
    q = q.reshape(B, KV, H // KV, dh)
    acc_old, m_old, d_old = cached(q)
    s_new = jnp.einsum("bkgd,tbkd->bkgt", q, k_new.astype(q.dtype),
                       preferred_element_type=jnp.float32)
    s_new = s_new / (dh ** 0.5) if scale is None else s_new * scale
    t = jnp.arange(steps)
    seen = (t <= i) & (t > i - window) if window else t <= i
    if keep_new is not None:
        seen = seen & keep_new[:, None, None, :]
    s_new = jnp.where(seen, s_new, -1e30)
    # column t = 0 is never masked, so the max is a real score (under a
    # selection some position is chosen, here or in the cache)
    m = jnp.maximum(m_old, s_new.max(-1))
    w_old, e_new = jnp.exp(m_old - m), jnp.exp(s_new - m[..., None])
    denom = d_old * w_old + e_new.sum(-1)
    out = acc_old * (w_old / denom)[..., None] + jnp.einsum(
        "bkgt,tbkd->bkgd", (e_new / denom[..., None]).astype(v_new.dtype),
        v_new, preferred_element_type=jnp.float32)
    return out.reshape(B, H, 1, v_new.shape[-1])


def _select(qi, w, idx_k, l, idx_new, live, i, top_k: int):
    """A decode step's selection on a layer that selects: slot ``b``'s index
    queries ``qi [B, Hi, d]`` and head weights ``w [B, Hi]`` against the index
    keys of layer ``l`` of the cache ``idx_k [L, B, 1, d, S]`` below ``live[b]``
    AND the chunk's own ``idx_new [steps, B, d]`` at ``t <= i``, ONE top-k over
    both (:func:`ray_tpu.ops.dsa.top_k_mask`).  Returns ``(keep [B, S], keep_new
    [B, steps])`` bool.  The scores are einsums over the layer's whole slab:
    the first form (module docstring of :mod:`ray_tpu.ops.dsa`)."""
    S, steps = idx_k.shape[-1], idx_new.shape[0]
    with jax.named_scope("attention.index_score"):
        slab = lax.dynamic_index_in_dim(idx_k, l, 0, keepdims=False)[:, 0]
        scores = jnp.concatenate([
            dsa.index_scores(qi, w, slab, "bhd,bds->bhs"),
            dsa.index_scores(qi, w, idx_new, "bhd,tbd->bht")], axis=-1)
    with jax.named_scope("attention.index_select"):
        valid = jnp.concatenate([
            jnp.arange(S)[None, :] < live[:, None],
            jnp.broadcast_to(jnp.arange(steps) <= i, (live.shape[0], steps))],
            axis=-1)
        keep = dsa.top_k_mask(scores, valid, top_k)
    return keep[:, :S], keep[:, S:]


def _flush_slices(slab, new, pos0):
    """The flush as one ``dynamic_update_slice`` a slot: ``new [L, steps, B,
    KV, dh]``, slot ``b``'s ``steps`` columns of every layer, into ``slab [L,
    B, KV, dh, S]`` at ``pos0[b] ..`` (held to ``S - steps``: the update
    clamps), EVERY slot's, in place.  What every platform can run, and the
    plain reference :func:`ray_tpu.ops.attention.cache_flush` is held to on
    the slots that decoded."""
    def one(b, big):
        # slot b's columns of the chunk, as [L, 1, KV, dh, steps]
        col = jnp.transpose(
            lax.dynamic_index_in_dim(new, b, 2, keepdims=False),
            (0, 2, 3, 1))[:, None]
        return lax.dynamic_update_slice(big, col, (0, b, 0, 0, pos0[b]))

    return lax.fori_loop(0, slab.shape[1], one, slab)


def _flush(slab, new, pos0, plan):
    """A chunk's columns ``new [L, steps, B, KV, dh]`` into ``slab``, in
    place.  Lowered for a TPU, with a cache of whole 128-position tiles
    (``plan``: :func:`ray_tpu.ops.attention.cache_flush_plan`), the Pallas
    kernel that visits only the slots that decoded and only the tiles their
    columns fall in; anywhere else the slice update of every slot.  Decided
    as :func:`_cache_scores` is, never by a flag."""
    if plan is None:
        return _flush_slices(slab, new, pos0)
    return lax.platform_dependent(
        slab, new, pos0, plan,
        tpu=lambda slab, new, pos0, plan: cache_flush(slab, new, plan),
        default=lambda slab, new, pos0, plan: _flush_slices(slab, new, pos0))


def _ring_of(t, lengths, ring: int):
    """A window layer's prompt keys or values ``[L, B, KV, Tp, dh]`` -> what
    its ring holds of them, ``[L, B, KV, dh, ring]`` (:func:`_ring_holds`;
    entries no position fills yet hold position 0's, and nothing reads
    them)."""
    j = jnp.maximum(_ring_holds(lengths, ring), 0)
    kept = jnp.take_along_axis(t, j[None, :, None, :, None], axis=3)
    return jnp.swapaxes(kept, 3, 4)


def can_continue(cfg) -> bool:
    """Whether a prompt of this config can be prefilled in PARTS
    (:func:`prefill_at`'s ``offsets``): every layer caches positions, which a
    later part can read back.  A family with recurrent layers
    (:func:`state_cache`) keeps whole prompts: its prefill starts from a zero
    state, unless its config says that the state a part leaves behind is the
    state the next part starts from (``cfg.state_carried_in``: the scan takes
    a state IN, the convolution the inputs before the part)."""
    return state_cache(cfg) is None or bool(
        getattr(cfg, "state_carried_in", False))


def _placed(held, new, offsets):
    """A full layer's keys BY POSITION for a prompt's part: ``held [B, KV, d,
    bound]``, what the cache holds of the rows' slots (positions last), and
    the part's own ``new [B, KV, P, d]`` -> ``[B, KV, bound, d]`` with row
    ``b``'s part at ``offsets[b] ..`` (``offsets[b] + P <= bound``)."""
    return jax.vmap(lambda h, n, at: lax.dynamic_update_slice(
        h, n.astype(h.dtype), (0, at, 0)))(
            jnp.swapaxes(held, 2, 3), new, offsets)


def _preceded(ring, new, offsets, window: int):
    """A window layer's keys for a prompt's part: ``[B, KV, before + P, d]``,
    the ``before`` positions ahead of ``offsets[b]`` read from the slot's ring
    ``[B, KV, d, R]`` by their places ``j % R`` (whole 128s that hold ``window
    - 1`` positions, at most the ring; a place that held no such position yet
    is masked by its position: :func:`ray_tpu.ops.attention.band_attention_after`),
    then the part's own ``new [B, KV, P, d]``."""
    R = ring.shape[-1]
    before = min(-(-(window - 1) // DECODE_TILE) * DECODE_TILE, R)
    j = (offsets[:, None] - before + jnp.arange(before)) % R
    earlier = jnp.take_along_axis(ring, j[:, None, None, :], axis=3)
    return jnp.concatenate(
        [jnp.swapaxes(earlier, 2, 3).astype(new.dtype), new], axis=2)


def prefill_at(params, cfg, tokens: jax.Array, lengths: jax.Array,
               cache: Dict[str, jax.Array], slots: jax.Array,
               offsets: Optional[jax.Array] = None,
               bound: Optional[int] = None,
               final: Optional[jax.Array] = None,
               visual: Optional[Dict] = None) -> Tuple[jax.Array, Dict]:
    """Run the prompts ``tokens [B, Tp]`` (right-padded; true lengths
    ``lengths [B]``) and write K/V into cache slots ``slots [B]`` (any
    subset — one compiled program admits a whole batch of requests).  Returns
    ``(last_logits [B, V], cache)``.  Positions are 0..Tp-1 and pos resets to
    ``lengths``: a WHOLE prompt, its slot prefilled from scratch.  A full layer
    keeps every position of the prompt (its k, v; a latent layer its rows),
    a window layer the last ``ring`` of each row (:func:`_ring_of`), a
    recurrent layer its state as it stands after each row's last REAL token
    and that row's last real inputs, written whole over the slot (a padded
    position changes neither): :func:`_write_prompt`, a row of
    :func:`cache_layout` at a time.  Where the family's layers count what they
    routed, the dispatch's counts come back as ``cache["routed"]`` (leaves
    stacked over the layers that route).

    ``offsets [B]`` int32 (None: the above): the rows are PARTS of prompts,
    row ``b``'s tokens at positions ``offsets[b] + 0..Tp-1`` of a slot whose
    earlier positions ``[0, offsets[b])`` a call before this one prefilled
    (:func:`can_continue`; the first part's offset is 0).  The part's queries
    attend what the cache holds of those positions AND the part's own keys
    under one softmax, its columns are written at ``offsets[b]``, and ``pos``
    becomes ``offsets + lengths``; the logits are the last real token's, as
    ever.  The offsets are runtime VALUES: one program whatever they are.  By
    kind of cache, as the config declares its layers:

    - a slab of k, v: the slot's first ``bound`` cached positions (static; None:
      the cache's length; ``offsets + Tp <= bound``) with the part's own placed
      among them (:func:`_placed`), attended by position
      (:func:`ray_tpu.ops.attention.continued_attention`: lowered for a TPU
      the flash kernel with the key length as a prefetched scalar, which
      neither folds nor fetches a block beyond ``offsets + Tp``).  What is
      PREPARED of those positions for the kernel (cached heads repeated to the
      query heads; a latent slab's up-projection, below) follows the live
      length too: a block of ``Tp`` positions a trip, the blocks below
      ``max(offsets) + Tp`` alone
      (:func:`ray_tpu.ops.attention.live_blocks`; ``bound`` is then whole
      blocks), so a part near its prompt's start pays for few;
    - a ring: the positions just ahead of the part by their places ``j % ring``
      (:func:`_preceded`), masked by absolute position; the ring then holds the
      last ``ring`` positions of prefix-and-part;
    - a latent slab: the cached rows are up-projected to per-head k and v by
      the block's own weights, as the part's own are (the block's ``context``,
      which hands over the layer's up-projection: the live blocks' rows go
      through it, a trip each);
    - a latent slab that selects: the part's index queries score the cached
      index keys below ``offsets`` and the part's own (the live blocks alone),
      ONE threshold over both (:func:`ray_tpu.ops.dsa.causal_top_k_mask`): the
      whole prompt's selection;
    - a state (``cfg.state_carried_in``): the scan starts from the state and
      the convolution from the last inputs the slot holds (zeros for a part at
      offset 0, whatever the slot's last tenant left), and both are written
      back as they stand after the part's last real token.

    A family whose upper layers read ONE lower layer's slab
    (:func:`shared_cache`) runs its lower layers and the slab's K and V at
    every position, and everything above for each row's LAST position alone,
    against the slab as this call leaves it.  ``final [B]`` bool (None: every
    row): the rows whose last position ENDS a prompt; where none does (a part
    that is not its prompt's last) the upper layers and the head do not run at
    all and the logits are zeros.

    ``visual`` (None: the rows are token ids alone, at rotary positions that
    are their cache positions): for a family with a tower in front
    (:func:`rope_offset`), ``{"rows": the tower's results for the frames this
    call's tokens stand for, "index" [B, Tp] int32: the row of those a token's
    input is (-1: its embedding), "positions" [B, 3, Tp] int32: every token's
    rotary position, "delta" [B] int32: what the rows' slots decode ahead of
    their cached lengths afterwards}``.  The slots' ``rope_delta`` is written
    either way (0 for a text request: a reused slot is clean)."""
    fam = family_of(cfg)
    B, Tp = tokens.shape
    windows = layer_windows(cfg)
    part = offsets is not None
    compact = summary_cache(cfg)
    shared = shared_cache(cfg)
    if part:
        assert can_continue(cfg), "a recurrent layer's prompt is prefilled whole"
        offsets = offsets.astype(jnp.int32)
        positions = offsets[:, None] + jnp.arange(Tp)         # [B, Tp]
        if compact:  # a part reads the summaries, a row a chunk of ``bound``
            bound = bound and bound // compact[1]
        # what the cache holds of the rows' slots, by kind of layer
        # (a family that shares one slab reads it for a last position alone)
        ahead = {False: () if shared is not None else tuple(
                     cache[row.name][:, slots, :, :, :bound]
                     for row in cache_rows(cfg, SLAB, SUMMARY, layers=FULL_LAYERS)),
                 True: tuple(cache[row.name][:, slots]
                             for row in cache_rows(cfg, RING))}
    else:
        positions = jnp.arange(Tp)
    if visual is not None:
        # rotary positions apart from cache positions: the blocks rotate by
        # these; offsets, masks and writes above and below stay the cache's
        positions = visual["positions"]
        x = fam.embed(params, tokens, cfg, positions, visual)
    else:
        x = fam.embed(params, tokens, cfg, positions)

    def among(window, held, own, prepare=None):
        # a PART's keys (values, ...) of one layer, a tensor each of ``held``
        # and ``own``: ``prepare`` (the layer's up-projection of cached rows,
        # a repeat to the query heads; None: as they are) of what the cache
        # holds ahead of the part and of the part's own.  A window layer's
        # are the ring's few positions; a full layer's go by position up to
        # the static bound, and only the blocks (a part wide) below the
        # longest row's end are prepared at all: the trips are a runtime
        # count, the kernel reads no further, and a part near the prompt's
        # start pays for few
        if window:
            rows = tuple(_preceded(h, t, offsets, window)
                         for h, t in zip(held, own))
            return prepare(*rows) if prepare else rows
        rows = tuple(_placed(h, t, offsets) for h, t in zip(held, own))
        return live_blocks(prepare, rows, offsets.max() + Tp, Tp
                           ) if prepare else rows

    def attend(q, k, v, row=None, index=None, window=0, held=None, pool=None):
        # the causal (or band) attention of training; kept: this layer's k,
        # v, or the cache row a latent family's block hands over; a layer
        # that selects attends the positions its index puts first, and its
        # index keys are kept beside the row.  ``held``: a PART's layer, what
        # the cache holds ahead of it, a tensor each of ``kept``.  A layer
        # that compacts (``pool``: its pooling vectors) attends a window at a
        # time, each with the summaries of the windows before it, and keeps
        # its k, v and every window's summaries
        scale = attention_scale(cfg, bool(window))
        if compact:
            W, c = compact
            out, pooled = eva.windowed_attention(
                q, k, v, *pool, window=W, chunk=c, scale=scale, held=held,
                rows0=offsets // W * (W // c) if part else None)
            return out, (k, v, *(pooled or ()))
        kept = (((k, v) if index is None else (k, v, index[2]))
                if row is None else (row,) if index is None
                else (row, index[2]))
        if held is not None:
            if row is None:  # (a latent layer's k and v cover it already)
                heads = q.shape[1] // k.shape[1]  # query heads a cached one
                k, v = among(window, held, (k, v), None if heads == 1 else (
                    lambda *kv: tuple(jnp.repeat(t, heads, axis=1) for t in kv)))
            if window:
                return band_attention_after(
                    q, k, v, offsets, window=window, scale=scale), kept
            keep = None if index is None else dsa.causal_top_k_mask(
                index[0], index[1], _placed(held[-1], index[2], offsets)[:, 0],
                index_cache(cfg)[1], first=offsets)
            return continued_attention(
                q, k, v, offsets, keep=keep, scale=scale), kept
        if index is not None:
            out = dsa.selected_attention(
                q, k, v, index, index_cache(cfg)[1], scale=scale)
            return out, kept
        out = _attend(q, k, v, causal=True, mesh=None, window=window,
                      scale=scale)[0]
        return out, kept

    routed = []
    if "blocks" in params:  # layers alike, stacked: one rolled loop
        def body(h, p):
            p, *held = p
            pool = {"pool": fam.pooling(p)} if compact else {}
            h, _, kv = fam.block(
                h, p, cfg, partial(attend, held=held or None, **pool), positions)
            return h, kv

        # ks [L, B, KV, Tp, dh]
        x, full = lax.scan(body, x, (params["blocks"],
                                     *(ahead[False] if part else ())))
        kept = _kept(cfg, full)
    elif shared is not None:  # the lower half, and the shared slab's K and V
        carried = None
        if part:  # what the slot holds of the part before; nothing at offset 0
            held = tuple(jnp.moveaxis(cache[row.name][_of_slots(row, slots)],
                                      row.slot_axis, 1)
                         for row in cache_rows(cfg, STATE))
            carried = tuple(
                jnp.where((offsets > 0).reshape(1, -1, *(1,) * (t.ndim - 2)), t, 0)
                for t in held)
        x, memory, kept = _prefill_lower(
            fam, params, cfg, x, attend, lengths, carried,
            ahead[True] if part else None, max(windows))
    elif state_cache(cfg):  # runs of recurrent layers rolled, the others listed
        x, routed, kept = _prefill_runs(
            fam, params, cfg, x, attend, positions, lengths)
    else:  # kinds of layer mixed, listed: unrolled, each kind's k, v apart
        valid = jnp.arange(Tp)[None, :] < lengths[:, None]
        done = {}  # what the layers so far kept, by kind of layer
        for p, w in zip(params["layers"], windows):
            held, rows_of = None, {}
            if part:  # this layer's among its kind's, and a latent block's rows
                held = tuple(t[len(done.get(bool(w), ()))] for t in ahead[bool(w)])
                if latent_cache(cfg, bool(w)):
                    rows_of = {"context": lambda row, up, w=w, held=held: among(
                        w, held[:1], (row,), up)}
            x, counts, kv = fam.block(
                x, p, cfg, partial(attend, window=w, held=held), positions,
                window=w, valid=valid, **rows_of)
            done.setdefault(bool(w), []).append(kv)
            routed += [] if counts is None else [counts]
        kept = _kept(cfg, *(
            tuple(jnp.stack(t) for t in zip(*done.get(kind, ())))
            for kind in (False, True)))
    rows = lengths.astype(jnp.int32)
    out = {**cache, "pos": cache["pos"].at[slots].set(
        rows + offsets if part else rows)}
    for row in cache_rows(cfg, SLOT):  # (0 for a text: a reused slot is clean)
        kept[row.name] = 0 if visual is None else visual["delta"].astype(jnp.int32)
    out.update(_write_prompt(cfg, cache, kept, slots, rows,
                             offsets if part else None))
    if routed:
        out["routed"] = routed if isinstance(routed, dict) else jax.tree.map(
            lambda *a: jnp.stack(a), *routed)
    if index_cache(cfg):
        # what the full layers' selection had to score, chose and read, of
        # the real rows (the masked kernel reads every causal position): row
        # t of a part at ``offsets`` has offsets + t + 1 positions to choose of
        start = offsets if part else jnp.zeros_like(rows)
        pairs = (rows * start + rows * (rows + 1) // 2).sum()
        top = jnp.clip(index_cache(cfg)[1] - start, 0, rows)
        chosen = (top * start + top * (top + 1) // 2
                  + (rows - top) * index_cache(cfg)[1]).sum()
        out["routed"] = {**out.get("routed", {}), **_selection_counts(
            cfg, scored=pairs, selected=chosen, read=pairs)}
    at_last = (lengths - 1)[:, None, None].astype(jnp.int32)
    x = jnp.take_along_axis(x, at_last, axis=1)
    if shared is None:
        return fam.unembed(params, x, cfg)[:, 0, :], out
    # the layers above the slab, for the rows' last positions: the slab as
    # this call leaves it, below where each row now stands
    k, v = (out[name][0][slots][None] for name in cached_tensors(cfg))
    seen = jnp.arange(k.shape[-1])[None, :] < out["pos"][slots][:, None]

    def read(q):  # [B, H, 1, dh]
        with jax.named_scope("attention.shared_kv"):
            b, h, _, dh = q.shape
            acc, _, d = _cache_scores_slab(
                q.reshape(b, k.shape[2], -1, dh), k, v, 0, seen,
                attention_scale(cfg))
            return (acc / d[..., None]).reshape(b, h, 1, dh).astype(cfg.dtype)

    def upper(x, memory):
        x, _ = fam.shared_layer(params, cfg, x, lambda q, k, v: (read(q), None))
        return fam.unembed(params, fam.upper_stack(
            params, cfg, x, memory, read), cfg)[:, 0, :]

    memory = jnp.take_along_axis(memory, at_last, axis=1)
    if final is None:
        return upper(x, memory), out
    return lax.cond(final.any(), upper, lambda x, memory: jnp.zeros(
        (B, cfg.vocab_size), jnp.float32), x, memory), out


def _kept(cfg, full=(), ringed=(), states=()) -> Dict[str, jax.Array]:
    """What a prefill call's layers kept, BY ROW of :func:`cache_layout`: the
    full layers' tensors (stacked over them, ``[L, B, KV, Tp, dh]``, a
    compacting family's summaries ``[L, B, KV, dh, rows]`` after them), the
    window layers' and the recurrent layers' (``[L_state, B, ...]``), each
    kind's in the table's order, under the names :func:`_write_prompt` finds
    them by.  Fewer tensors than rows: the call did not make the last ones (a
    compacting family's call that is no whole window pools nothing)."""
    return {row.name: t
            for layers, tensors in ((FULL_LAYERS, full), (WINDOW_LAYERS, ringed),
                                    (STATE_LAYERS, states))
            for row, t in zip(cache_rows(cfg, layers=layers), tensors)}


def _of_slots(row: Cached, slots) -> tuple:
    """The index of ``slots`` in a STATE row's tensor."""
    return (slice(None),) * row.slot_axis + (slots,)


def _write_prompt(cfg, cache, kept, slots, lengths, offsets=None):
    """What a prefill call leaves in its rows' slots, a row of
    :func:`cache_layout` at a time: ``kept[name]``, what the call's layers
    kept for the row of that name (:func:`_kept`; a row they kept nothing for
    stays as it was), of prompts of ``lengths [B]`` int32.  ``offsets`` (None:
    WHOLE prompts, the slots written from position 0): the call's rows are
    PARTS, each written at its own offset, a row at a time (a call is a row
    or a few).  By arrangement:

    - SLOT: the scalar, whole or part;
    - SLAB: the prompt's columns at ``[0, Tp)`` in one scatter over whole
      slots (a single advanced index keeps its axis position; decode never
      scatters), a part's at ``offsets[b]``;
    - RING: each row's last ``ring`` positions (:func:`_ring_of`); of a part,
      the entries whose newest position (:func:`_ring_holds` over
      prefix-and-part) is the part's own, the others kept;
    - WINDOW: the EXACT window row ``b``'s next position falls in (the call's
      last where the row ends it: its places are then all dead, ``pos %
      window == 0``), position ``j`` at place ``j % window``;
    - SUMMARY: every window's summaries at the row a window (those of a
      window the row did not fill lie beyond what ``pos`` lets a query read,
      and the roll-over that fills it rewrites them), a part's at its own
      summary row;
    - STATE: the state as it stands after each row's last REAL token, whole
      over the slot (which is what makes a reused slot clean)."""
    out, which = {}, None
    for row in cache_layout(cfg):
        if row.name not in kept:
            continue
        old, t = cache[row.name], kept[row.name]
        if row.arrangement == SLOT:
            out[row.name] = old.at[slots].set(t)
        elif row.arrangement == STATE:
            out[row.name] = old.at[_of_slots(row, slots)].set(
                jnp.moveaxis(t, 1, row.slot_axis).astype(old.dtype))
        elif row.arrangement == SLAB and offsets is None:
            out[row.name] = old.at[:, slots, :, :, :t.shape[3]].set(
                jnp.swapaxes(t, 3, 4).astype(old.dtype))
        elif row.arrangement == SLAB:
            cols = jnp.swapaxes(t, 3, 4).astype(old.dtype)
            for b in range(cols.shape[1]):
                old = lax.dynamic_update_slice(
                    old, cols[:, b:b + 1], (0, slots[b], 0, 0, offsets[b]))
            out[row.name] = old
        elif row.arrangement == RING and offsets is None:
            out[row.name] = old.at[:, slots].set(
                _ring_of(t, lengths, old.shape[-1]).astype(old.dtype))
        elif row.arrangement == RING:
            j = _ring_holds(offsets + lengths, old.shape[-1]) - offsets[:, None]
            new = jnp.take_along_axis(
                t, jnp.clip(j, 0, t.shape[3] - 1)[None, :, None, :, None], axis=3)
            out[row.name] = old.at[:, slots].set(jnp.where(
                (j >= 0)[None, :, None, None, :],
                jnp.swapaxes(new, 3, 4).astype(old.dtype), old[:, slots]))
        elif row.arrangement == WINDOW:
            T = t.shape[3]
            tw = min(T, row.window)
            if which is None:  # the window of the call's that each row keeps
                which = jnp.minimum(lengths // tw, T // tw - 1)
            held = jnp.take_along_axis(
                t.reshape(*t.shape[:3], T // tw, tw, t.shape[-1]),
                which[None, :, None, None, None, None], axis=3)[:, :, :, 0]
            out[row.name] = old.at[:, slots, :, :, :tw].set(
                jnp.swapaxes(held, 3, 4).astype(old.dtype))
        elif offsets is None:  # SUMMARY
            n = min(t.shape[-1], old.shape[-1])
            out[row.name] = old.at[:, slots, :, :, :n].set(
                t.astype(old.dtype)[..., :n])
        else:
            t = t.astype(old.dtype)
            for b in range(t.shape[1]):
                old = lax.dynamic_update_slice(
                    old, t[:, b:b + 1],
                    (0, slots[b], 0, 0, offsets[b] // row.chunk))
            out[row.name] = old
    return out


def _selection_counts(cfg, **counts) -> Dict[str, jax.Array]:
    """A dispatch's selection counters as they ride beside the routing counts
    (``dsa_*``, one value a full layer: a layer's own count, the same for
    each, so that a sum over the leaf is a sum over the layers)."""
    n_full = layer_windows(cfg).count(0)
    return {"dsa_" + name: jnp.full((n_full,), value, jnp.int32)
            for name, value in counts.items()}


def _prefill_lower(fam, params, cfg, x, attend, lengths, carried, rings,
                   window: int):
    """:func:`prefill_at`'s layers for a family that shares one slab
    (:func:`shared_cache`): the lower half rolled (``fam.lower_stack``), then
    the K and V of the layer that owns the slab, at every position.
    ``carried``: a PART's rows' states and last inputs as the slots hold them
    (``[L_state, B, ...]`` each; None: prompts from their start); ``rings``:
    what the window layers' rings hold of the rows' slots (``[L_window, B, KV,
    dh, R]`` each; None: whole prompts).  Returns ``(x, the memory layer's y,
    what was kept by row`` (:func:`_kept`): the slab's k, v ``[1, B, KV, T,
    dh]``, the window layers' stacked k, v, the recurrent layers' stacked
    state and last inputs``)``."""
    at_l = lambda t, at: lax.dynamic_index_in_dim(t, at, 0, keepdims=False)  # noqa: E731

    def mamba(at, xs, p, carry):
        before = state = None
        if carried is not None:
            state, before = (at_l(t, at) for t in carried)
        y, kept = fam.mamba_whole(xs, p, cfg, lengths, before, state)
        return y, kept, carry

    def ring(at, q, k, v, carry):
        held = None if rings is None else tuple(at_l(t, at) for t in rings)
        with jax.named_scope("attention.diff_window"):
            out, kept = attend(q, k, v, window=window, held=held)
        return out, kept, carry

    x, memory, _, states, ringed = fam.lower_stack(
        params, cfg, x, None, mamba, ring)
    full = tuple(t[None] for t in fam.shared_kv(params, cfg, x))
    return x, memory, _kept(cfg, full, ringed, states)


def _prefill_runs(fam, params, cfg, x, attend, positions, lengths):
    """:func:`prefill_at`'s layers for a family with recurrent layers: every
    run of them (``cfg.layer_runs``) one rolled loop over the kind's stacked
    parameters, the attention layers between them listed.  Returns ``(x, the
    layers' routing counts with their leaves stacked over the layers, what
    was kept by row`` (:func:`_kept`): the attention layers' stacked k and v,
    the recurrent layers' stacked state and last inputs``)``."""
    valid = positions[None, :] < lengths[:, None]
    recur = lambda xbc, dt, p: fam.mamba_whole(  # noqa: E731
        xbc, dt, p, cfg, lengths)
    routed, kept = [], {False: [], True: []}
    for kind, first, count, at in cfg.layer_runs:
        recurrent = layer_windows(cfg)[first] == RECURRENT
        if recurrent:
            def body(h, i, kind=kind):
                h, counts, carried = fam.block(
                    h, fam.layer_of(params[kind], i), cfg, recur, positions,
                    kind=kind, valid=valid)
                return h, (counts, carried)

            x, (counts, carried) = lax.scan(body, x, at + jnp.arange(count))
        else:
            outs = []
            for p in params[kind][at:at + count]:
                x, *out = fam.block(x, p, cfg, attend, positions, kind=kind,
                                    valid=valid)
                outs.append(out)
            counts, carried = jax.tree.map(lambda *a: jnp.stack(a), *outs)
        routed.append(counts)
        kept[recurrent].append(carried)
    full, states = (tuple(jnp.concatenate(t) for t in zip(*kept[kind]))
                    for kind in (False, True))
    # leaves stacked over the layers, in layer order
    return (x, jax.tree.map(lambda *a: jnp.concatenate(a), *routed),
            _kept(cfg, full, states=states))


def prefill(params, cfg, tokens: jax.Array, lengths: jax.Array,
            cache: Dict[str, jax.Array], slot: jax.Array) -> Tuple[jax.Array, Dict]:
    """:func:`prefill_at` with contiguous slots ``slot + [0..B)``."""
    B = tokens.shape[0]
    return prefill_at(params, cfg, tokens, lengths, cache,
                      slot + jnp.arange(B, dtype=jnp.int32))


def sample_logits(logits: jax.Array, key: jax.Array, *, temperature: float = 0.0,
                  top_k: int = 0) -> jax.Array:
    """Greedy (temperature 0) or temperature/top-k categorical sampling."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def decode_chunk(params, cfg, cache, tokens, active, key, *, steps: int,
                 n: Optional[jax.Array] = None, temperature: float = 0.0,
                 top_k: int = 0, eos_id: Optional[int] = None):
    """Run ``steps`` decode+sample iterations in one device computation.
    ``tokens [B]`` are each slot's last emitted token, ``active [B]`` bool
    gates the position advance.  Returns ``(emitted [B, steps], cache,
    active, key)``.  A slot that emits ``eos_id`` flips inactive mid-chunk
    (its pos freezes).

    ``n`` (a traced scalar, ``1 <= n <= steps``) CUTS the chunk: the same
    step runs ``n`` times, in a loop whose bound is a runtime value, so one
    compiled program serves every ``n``.  ``emitted[:, n:]`` then repeat each
    slot's last token (``emitted[:, -1]`` is it either way), ``pos`` advances
    ``n``, and the flush still writes ``steps`` columns: those from ``n`` on
    land at or beyond the new ``pos``, as an EOS-frozen slot's do (module
    docstring).  None: the whole chunk, a ``lax.scan`` over ``steps``.

    No step writes into the cache (module docstring): the new K/V columns
    go to a chunk-local buffer ``[L, steps, B, KV, dh]``, attention is over
    the cache below ``pos0`` plus that buffer (:func:`_decode_attend`), and
    after the last step the columns are flushed to ``pos0[b]``, in place, a
    tensor at a time (:func:`_flush`): lowered for a TPU with a cache of
    whole tiles by the kernel that merges them into the one or two tiles
    they fall in, of the slots that were active when the chunk began only;
    elsewhere by a slice update a slot.  What a slot attends of the cache is
    fixed when the chunk begins (``live``), and so are both kernels' work
    lists, built once here.  A recurrent layer's state is no column: it rides
    the steps' carry, each step overwriting it in place for the rows that
    take the step (frozen for a row that is inactive or stopped at EOS), so a
    cut chunk leaves it ``n`` steps on and there is nothing to flush.  The
    step is ``serve-gpt2-xl-chat``'s
    ``model.decode_step_ms``, the flush the ``cache_flush`` row of its
    ``breakdown.device_ops``."""
    fam = family_of(cfg)
    B = tokens.shape[0]
    # what a full layer caches a position: k and v, or one latent row whose
    # first values are the position's value vector (absorbed attention)
    windows = layer_windows(cfg)
    window = max(windows)
    # the table's rows by what a chunk does with each: columns flushed at the
    # slots' places, columns flushed modulo the ring, summaries a full window
    # rolls over into, a state the steps carry
    flushed = cache_rows(cfg, SLAB, WINDOW, layers=FULL_LAYERS)
    wrapped, pooled, carried = (
        cache_rows(cfg, kind) for kind in (RING, SUMMARY, STATE))
    names = tuple(row.name for row in flushed)
    latent, index, compact = latent_cache(cfg), index_cache(cfg), summary_cache(cfg)
    shared = shared_cache(cfg)
    # (a slab that layers other than its owner read: its kernel call has a
    # name of its own)
    slab_name = {} if shared is None else {"name": "ragged_shared_kv_attention"}
    old = tuple(cache[name] for name in names)
    rings = tuple(cache[row.name] for row in wrapped)
    S = old[0].shape[-1]
    ring = rings[0].shape[-1] if rings else S
    # the flush holds a start to S - steps silently (a dynamic_update_slice
    # clamps, and the kernel's plan does as it does): a slot at pos0 needs
    # pos0 + steps <= S (the engine's bucket + max_new + chunk); a ring's
    # flush, steps <= ring - window + 1 (ring_positions)
    assert steps <= S and (not window or steps <= ring - window + 1), (
        steps, S, window, ring)
    assert not compact or steps <= S - compact[0], (steps, S, compact)
    if steps == 0:
        return jnp.zeros((B, 0), jnp.int32), cache, active, key
    pos0 = cache["pos"]
    # where the chunk's columns go: a slot's position, or its place in the
    # exact window of a family that compacts (whose caller ends a chunk where
    # the nearest slot's window ends: no chunk straddles one)
    place0 = pos0 % compact[0] if compact else pos0
    # what a slot attends of the cache, fixed for the chunk: the positions
    # below where it stood, nothing for a slot that sits the chunk out
    live = jnp.where(active, place0, 0)
    # the kernels' work lists, functions of who is active and where alone:
    # built once here, for every layer, step and tensor
    plan = to_flush = None
    if S % DECODE_TILE == 0 and all(t.shape[3] % 8 == 0 for t in old):
        plan = ragged_decode_plan(live, S // DECODE_TILE)
        if steps <= DECODE_TILE:
            to_flush = cache_flush_plan(active, place0, steps, S, written=n)
    # a latent family's rings, read as its slabs are: a live slot's ring
    # holds min(live, ring) entries, whole tiles of them listed, and the step's
    # window is the kernel's mask (a ring of K and V per head stays a slab
    # read: that kernel takes no mask)
    ring_plan = ragged_decode_plan(
        jnp.minimum(live, ring),
        ring // DECODE_TILE) if ring_read_by_tile(cache, cfg) else None
    # a family that compacts: the summaries of the windows before a slot's
    # own, ``rows a window`` for each it has filled, read as the window is
    sums = tuple(cache[row.name] for row in pooled)
    far = far_plan = None
    if compact:
        far = jnp.where(active, pos0 // compact[0] * (compact[0] // compact[1]), 0)
        if plan is not None and sums[0].shape[-1] % DECODE_TILE == 0:
            far_plan = ragged_decode_plan(far, sums[0].shape[-1] // DECODE_TILE)
    # the chunk-local buffers, one a cached tensor: [layers of its kind,
    # steps, B, KV, dh], a layer's own at its place among its kind
    local = tuple(jnp.zeros((t.shape[0], steps, B, *t.shape[2:4]), t.dtype)
                  for t in old + rings)
    # the latent rows a selecting layer's attention READS a slot a step, of
    # the cache: the kernel a slot's live tiles, the slab all of it
    read_of = ((lambda n: -(-n // DECODE_TILE) * DECODE_TILE)
               if plan is not None else (lambda n: jnp.full_like(n, S)))
    # the recurrent layers' state, and the slots whose state a step moves
    held = tuple(cache[row.name] for row in carried)
    moved = ssm.state_update_plan(active) if held and (
        ssm.kernel_shapes if shared is None else ssm.selective_kernel_shapes)(
            held[0]) else None
    # a latent family's block in its decode form (the family's docstring)
    form = {"absorbed": True} if latent else {}

    def step(carry, i):
        locs, held, pos, toks, act, rng = carry
        rng, sub = jax.random.split(rng)
        positions = pos[:, None]  # [B, 1] per-slot offsets (wpe / rope)
        for row in cache_rows(cfg, SLOT):  # rotary positions ahead of the cache's
            positions = positions + cache[row.name][:, None]
        x = fam.embed(params, toks[:, None], cfg, positions)  # [B, 1, D]

        def layer(l, w, at, block, carry):
            """Layer ``l``, the ``at``-th of its kind (``w``: its window, 0
            a full layer), run as ``block(x, attend)``."""
            x, *locs = carry
            scale = attention_scale(cfg, bool(w))  # None: dh ** -0.5
            at_l = lambda a: lax.dynamic_index_in_dim(a, at, 0, keepdims=False)
            # this kind's chunk-local buffers among the carry's
            mine = slice(len(names), None) if w else slice(0, len(names))

            def cached(q, keep=None):
                if compact:
                    with jax.named_scope("attention.eva_window"):
                        near = _cache_scores(q, *old, at, live, plan, scale)
                    with jax.named_scope("attention.eva_summary"):
                        rest = _cache_scores(q, *sums, at, far, far_plan, scale)
                    with jax.named_scope("attention.eva_merge"):
                        return eva.merge(near, rest)
                if latent and w:
                    return _latent_ring_scores(
                        q, rings[0], at, live, pos, ring_plan, window=w,
                        scale=scale, dv=latent_cache(cfg, True)[1])
                if latent:
                    return _latent_cache_scores(
                        q, old[0], at, live, plan, scale=scale, dv=latent[1],
                        keep=keep)
                if not w and index:  # a slab of k and v under a selection
                    return _cache_scores(
                        q, old[0], old[1], at, live, plan, scale, keep=keep,
                        name="ragged_sparse_gqa_attention")
                if not w:
                    return _cache_scores(q, *old, at, live, plan, scale,
                                         **slab_name)
                if ring_plan is not None:
                    return _ring_scores(q, *rings, at, live, pos, ring_plan,
                                        window=w, scale=scale)
                return _cache_scores_slab(
                    q, *rings, at, _ring_mask(live, pos, w, ring), scale)

            def attend(q, k, v, row=None, picked=None):  # [B, heads, 1, dh]
                put = lambda buf, t: lax.dynamic_update_slice(
                    buf, t[None, None, :, :, 0, :].astype(buf.dtype),
                    (at, i, 0, 0, 0))
                cols = (((k, v) if picked is None else (k, v, picked[2]))
                        if row is None else (row,) if picked is None
                        else (row, picked[2]))
                new = tuple(put(buf, t) for buf, t in zip(locs[mine], cols))
                k_new = at_l(new[0])
                v_new = at_l(new[1]) if row is None else k_new[..., :v.shape[-1]]
                keep = keep_new = None
                if picked is not None:
                    # the layer's selection for this step: over the slab's
                    # index keys below ``live`` AND the chunk's own up to i
                    keep, keep_new = _select(
                        picked[0][:, :, 0], picked[1][:, 0], old[-1], at,
                        at_l(new[-1])[:, :, 0], live, i, index[1])
                out = _decode_attend(q, partial(cached, keep=keep), k_new,
                                     v_new, i, w, scale, keep_new)
                locs[mine] = new
                return out.astype(cfg.dtype), locs

            x, counts, locs = block(x, attend)
            return (x, *locs), counts

        counted = []  # what the layers that route counted, this step
        if "blocks" in params:  # layers alike, stacked: one rolled loop
            def rolled(l, carry):
                p = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                    a, l, 0, keepdims=False), params["blocks"])
                return layer(l, 0, l, lambda x, attend: fam.block(
                    x, p, cfg, attend, positions), carry)[0]

            x, *locs = lax.fori_loop(0, cfg.n_layers, rolled, (x, *locs))
        elif shared is not None:  # two rolled halves around the shared slab
            def attended(w, at, q, k, v, locs):
                # layer()'s write into the chunk-local buffer and its read of
                # cache and buffer, for a middle that is handed q, k, v
                (a, *locs), _ = layer(
                    None, w, at, lambda x, attend: (lambda got: (
                        got[0], None, got[1]))(attend(q, k, v)), (None, *locs))
                return a, tuple(locs)

            def mamba(at, xs, p, carry):
                locs, (state, tails) = carry
                tail = lax.dynamic_index_in_dim(tails, at, 0, keepdims=False)
                moved_to = []

                def update(*inputs):
                    with jax.named_scope("ssm.selective_state_update"):
                        new, y = ssm.selective_state_update(
                            state, at, *inputs, act, moved)
                    moved_to.append(new)
                    return y

                y, last = fam.mamba_step(xs, p, cfg, tail, update)
                last = jnp.where(act[None, :, None], last, tail)
                return y, None, (locs, (
                    moved_to[0],
                    lax.dynamic_update_index_in_dim(tails, last, at, 0)))

            def ringed(at, q, k, v, carry):
                with jax.named_scope("attention.diff_window"):
                    a, locs = attended(window, at, q, k, v, carry[0])
                return a, None, (locs, carry[1])

            x, memory, (locs, held), _, _ = fam.lower_stack(
                params, cfg, x, (tuple(locs), tuple(held)), mamba, ringed)
            now = []  # the buffers with this step's column of the slab in

            def own(q, k, v):
                with jax.named_scope("attention.shared_kv"):
                    a, new = attended(0, 0, q, k, v, locs)
                now.append(new)
                return a, None

            x, _ = fam.shared_layer(params, cfg, x, own)
            locs = now[0]

            def read(q):  # a layer that reads the slab and writes nothing
                with jax.named_scope("attention.shared_kv"):
                    return _decode_attend(
                        q, lambda q: _cache_scores(
                            q, *old, 0, live, plan, attention_scale(cfg),
                            **slab_name),
                        locs[0][0], locs[1][0], i, 0,
                        attention_scale(cfg)).astype(cfg.dtype)

            x = fam.upper_stack(params, cfg, x, memory, read)
        elif held:  # runs of recurrent layers rolled, the others listed
            for kind, first, count, at in cfg.layer_runs:
                if windows[first] == RECURRENT:
                    def recurrent(carry, l, kind=kind):
                        x, state, tails = carry
                        tail = lax.dynamic_index_in_dim(tails, l, 0, keepdims=False)
                        moved_to = []

                        def update(*inputs):
                            with jax.named_scope("ssm.state_update"):
                                new, y = ssm.state_update(
                                    state, l, *inputs, act, moved)
                            moved_to.append(new)
                            return y

                        def recur(xbc, dt, p):
                            y, last = fam.mamba_step(xbc, dt, p, cfg, tail, update)
                            return y, jnp.where(act[None, :, None], last, tail)

                        x, counts, last = fam.block(
                            x, fam.layer_of(params[kind], l), cfg, recur,
                            positions, kind=kind, valid=act[:, None])
                        return (x, moved_to[0], lax.dynamic_update_index_in_dim(
                            tails, last, l, 0)), counts

                    (x, *held), counts = lax.scan(
                        recurrent, (x, *held), at + jnp.arange(count))
                    counted.append(counts)
                    continue
                for j, p in enumerate(params[kind][at:at + count]):
                    (x, *locs), counts = layer(
                        at + j, 0, at + j, lambda x, attend, p=p, kind=kind: fam.block(
                            x, p, cfg, attend, positions, kind=kind,
                            valid=act[:, None]), (x, *locs))
                    counted.append(jax.tree.map(lambda a: a[None], counts))
            counted = jax.tree.map(lambda *a: jnp.concatenate(a), *counted)
        else:  # kinds of layer mixed, listed: unrolled, each kind's cache
            state, seen = (x, *locs), {}
            for l, (p, w) in enumerate(zip(params["layers"], windows)):
                at = seen[bool(w)] = seen.get(bool(w), -1) + 1
                state, counts = layer(l, w, at, lambda x, attend, p=p, w=w: fam.block(
                    x, p, cfg, attend, positions, window=w, valid=act[:, None],
                    **form), state)
                counted += [] if counts is None else [counts]
            x, *locs = state
        logits = fam.unembed(params, x, cfg)[:, 0, :]
        nxt = sample_logits(logits, sub, temperature=temperature, top_k=top_k)
        nxt = jnp.where(act, nxt, toks)
        # what the step's selection had to score, chose and read, of the rows
        # that took it: a context is the cache below where the row stood and
        # the step's own position
        context = jnp.where(act, pos + 1, 0)
        selection = _selection_counts(
            cfg, scored=context.sum(),
            selected=jnp.minimum(context, index[1]).sum(),
            read=jnp.where(act, read_of(live) + i + 1, 0).sum()) if index else {}
        pos = pos + act.astype(jnp.int32)
        if eos_id is not None:
            act = act & (nxt != eos_id)
        # leaves stacked over the layers that route (None: nothing counted;
        # the rolled runs' come stacked)
        if isinstance(counted, list):
            counted = jax.tree.map(
                lambda *a: jnp.stack(a), *counted) if counted else None
        if selection:
            counted = {**(counted or {}), **selection}
        return (tuple(locs), tuple(held), pos, nxt, act, rng), (nxt, counted)

    state = (local, held, pos0, tokens, active, key)
    if n is None:
        (locs, held, pos, _, active, key), (emitted, routed) = lax.scan(
            step, state, jnp.arange(steps))
    else:
        # the cut chunk: what the scan stacks a step is carried instead, the
        # tokens written at their step, the routing counts added up
        def cut_step(i, carry):
            state, emitted, routed = carry
            state, (nxt, counted) = step(state, i)
            return (state, emitted.at[i].set(nxt),
                    jax.tree.map(jnp.add, routed, counted))

        counted = jax.eval_shape(step, state, 0)[1][1]
        (locs, held, pos, last, active, key), emitted, routed = lax.fori_loop(
            0, n, cut_step,
            (state, jnp.zeros((steps, B), jnp.int32),
             jax.tree.map(jnp.zeros_like, counted)))
        emitted = jnp.where(jnp.arange(steps)[:, None] < n, emitted, last)

    # what the chunk leaves of every row it writes: the state as the last step
    # carried it, the columns flushed, a filled window rolled over into its
    # summaries, the rings' columns modulo the ring
    out = {**cache, "pos": pos,
           **{row.name: t for row, t in zip(carried, held)}}
    for name, big, loc in zip(names, old, locs):
        out[name] = _flush(big, loc, place0, to_flush)
    if pooled:
        out.update(zip((row.name for row in pooled), _roll_over(
            cfg, fam.pooling(params["blocks"]),
            *(out[row.pools] for row in pooled), sums, pos, pos != pos0)))
    if wrapped:
        # once a chunk and whole: column t of slot b goes to entry
        # (pos0[b] + t) % ring, chosen by a 0/1 matrix (exact), every other
        # entry stays; no scatter, and a wrap is nothing special
        hit = ((pos0[:, None, None] + jnp.arange(steps)[None, :, None]) % ring
               == jnp.arange(ring)[None, None, :])          # [B, steps, ring]
        for row, loc in zip(wrapped, locs[len(names):]):
            new = jnp.einsum("ltbkd,btr->lbkdr", loc, hit.astype(loc.dtype))
            out[row.name] = jnp.where(hit.any(1)[None, :, None, None, :],
                                      new, cache[row.name])
    if routed is not None:  # the chunk's routing counts: summed over its steps
        out["routed"] = routed if n is not None else jax.tree.map(
            lambda a: a.sum(0), routed)
    return emitted.T, out, active, key


def _roll_over(cfg, pool, k, v, sums, pos, moved):
    """The compaction of the slots whose window the chunk FILLED (``moved``
    and now at a multiple of the window): the window's exact ``k, v [L, B, KV,
    dh, places]`` pooled a chunk at a time (:func:`ray_tpu.ops.eva.pool_chunks`,
    every layer at once: ``pool``, the stacked layers' vectors) into the rows
    ``[(w - 1) R, w R)`` of the slot's summaries, in place; the window's
    places are dead from here on (``pos % window == 0``) and the next chunk's
    flush starts again at place 0.  A loop over the slots that rolled alone:
    none, most chunks, and then it costs nothing."""
    window, chunk = summary_cache(cfg)
    rolled = moved & (pos % window == 0)
    order = jnp.argsort(~rolled)  # the slots that rolled first

    def roll(j, sums):
        b = order[j]
        with jax.named_scope("attention.eva_pool"):
            held = (lax.dynamic_slice_in_dim(t, b, 1, 1)[:, 0, :, :, :window]
                    for t in (k, v))
            pooled = eva.pool_chunks(
                *held, *pool, chunk=chunk,
                scale=attention_scale(cfg) or cfg.head_dim ** -0.5)
            return tuple(lax.dynamic_update_slice(
                s, p[:, None], (0, b, 0, 0, (pos[b] - window) // chunk))
                for s, p in zip(sums, pooled))

    return lax.fori_loop(0, rolled.sum(), roll, tuple(sums))


def generate(params, cfg, prompts: jax.Array, lengths: jax.Array, *,
             max_new_tokens: int, key: Optional[jax.Array] = None,
             temperature: float = 0.0, top_k: int = 0,
             eos_id: Optional[int] = None) -> jax.Array:
    """One-shot batched generation (prefill + ONE decode chunk of
    ``max_new_tokens - 1`` steps, so the chunk-local K/V buffer is as wide
    as the answer).  Returns ``[B, max_new_tokens]`` generated tokens
    (post-EOS positions repeat the EOS token).  For the serving path use
    :mod:`ray_tpu.serve.llm`, which runs the same kernels in chunks (cut
    where an answer ends) under iteration-level continuous batching."""
    B, Tp = prompts.shape
    if key is None:
        key = jax.random.PRNGKey(0)
    cache = init_cache(cfg, B, Tp + max_new_tokens)
    last_logits, cache = prefill(
        params, cfg, prompts, lengths, cache, jnp.int32(0))
    key, sub = jax.random.split(key)
    first = sample_logits(last_logits, sub, temperature=temperature, top_k=top_k)
    active = jnp.ones((B,), bool)
    if eos_id is not None:
        active = active & (first != eos_id)
    sampling = dict(temperature=temperature, top_k=top_k, eos_id=eos_id)
    if summary_cache(cfg) and max_new_tokens > 1:
        rest = _decode_to_window_ends(
            params, cfg, cache, first, active, key, max_new_tokens - 1, sampling)
    else:
        rest, _, _, _ = decode_chunk(
            params, cfg, cache, first, active, key,
            steps=max_new_tokens - 1, **sampling)
    return jnp.concatenate([first[:, None], rest], axis=1)


def _decode_to_window_ends(params, cfg, cache, first, active, key, total: int,
                           sampling: dict) -> jax.Array:
    """:func:`generate`'s answer for a family that compacts: no chunk may
    straddle a slot's window end (:func:`decode_chunk`), so the ``total``
    steps run as cut chunks, each ending where the nearest active slot's
    window does (the serve engine's rule, here on the device: one program)."""
    window = summary_cache(cfg)[0]
    steps = min(total, window_positions(window) - window)

    def chunk(carry):
        done, cache, tok, active, key, emitted = carry
        left = jnp.where(active, window - cache["pos"] % window, steps)
        n = jnp.minimum(jnp.minimum(steps, total - done), left.min())
        out, cache, active, key = decode_chunk(
            params, cfg, cache, tok, active, key, steps=steps, n=n, **sampling)
        # the columns past ``n`` repeat the last token; the next chunk's
        # overwrite them
        return (done + n, cache, out[:, -1], active, key,
                lax.dynamic_update_slice(emitted, out, (0, done)))

    emitted = jnp.zeros((first.shape[0], total + steps), jnp.int32)
    *_, emitted = lax.while_loop(
        lambda carry: carry[0] < total, chunk,
        (jnp.int32(0), cache, first, active, key, emitted))
    return emitted[:, :total]
