"""Mixture-of-Experts FFNs.  Three layers, by age:

- :func:`moe_ffn` (below): the Switch layer, top-1 with a capacity that DROPS
  (kept for the LayerNorm block's ``n_experts`` option and the pipeline's
  ``pp x ep x dp`` dry run: GELU experts with biases, which the other two do
  not have);
- :func:`held_experts_ffn`: top-k gated experts as they are SERVED, the layer
  told which experts this chip holds, no token dropped, a runtime number of
  trips over the held pairs;
- :func:`experts_ffn_train`: the same layer TRAINED: differentiable (no loop:
  a runtime trip count has no transpose; every pair of the tokens given, so
  every shape is static), ReGLU beside SwiGLU, and the experts spread over a
  mesh axis with their exchange (the experts' matrices gathered to each
  chip's own tokens, their gradients sent home a chip's block at a time under
  the backward pass's own matmuls: a chip's work does not follow the routing).

The two dropless layers share the sort of the pairs (:func:`_sorted_pairs`).
The served one's two grouped matmuls are :func:`_experts_block`; the trained
one has a block of its own (:func:`_experts_block_train`: a pair's gate goes
in before the down matmul, and the backward pass is written by hand).

The Switch layer: Mixture-of-Experts FFN with expert parallelism over the ``ep`` axis.

The reference has no MoE/expert-parallel code (SURVEY §2.5 row EP:
"Absent"); this is the TPU-native build target — "expert-axis sharding +
``all_to_all`` over ICI".  Switch-Transformer-style top-1 routing with a
fixed per-expert capacity, expressed as dense dispatch/combine einsums
(the GShard formulation): expert weights carry an ``expert`` logical axis
mapped to the mesh's ``ep`` axis, the token batch is sharded over
dp/fsdp, and XLA lowers the ``[tokens] x [experts]`` dispatch einsum into
the ep-axis all_to_all/all_gather pair — collectives ride ICI, nothing is
hand-scheduled.

Shapes are static (capacity = ceil(cf * tokens / E)), so the whole thing
jits once; dropped tokens (over capacity) fall through the residual
connection, as in Switch.  The load-balance auxiliary loss is the Switch
eq. (4): ``E * sum_e f_e * P_e``, minimized at uniform routing.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.layers import dense


def _constrain(x: jax.Array, mesh: Optional[Mesh], spec: P) -> jax.Array:
    if mesh is None:
        return x
    try:
        if jax.typeof(x).vma:
            # inside a manual region (e.g. the pp pipeline's shard_map):
            # constraints on varying arrays are rejected; sharding still
            # propagates from the ep-sharded expert weights.
            return x
    except AttributeError:
        pass
    # drop axes the mesh doesn't have
    parts = tuple(a if (a in mesh.axis_names) else None for a in spec)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))


def moe_ffn(
    x: jax.Array,
    router_w: jax.Array,
    w1: jax.Array,
    b1: jax.Array,
    w2: jax.Array,
    b2: jax.Array,
    *,
    capacity_factor: float = 2.0,
    mesh: Optional[Mesh] = None,
    f32_param_grads: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Top-1 (Switch) MoE feed-forward.

    Args:
        x: ``[B, T, D]`` activations (compute dtype).
        router_w: ``[D, E]`` router weights (kept f32 for stable softmax).
        w1, b1: ``[E, D, F]``, ``[E, F]`` expert up-projections.
        w2, b2: ``[E, F, D]``, ``[E, D]`` expert down-projections.
        capacity_factor: per-expert buffer = ``cf * tokens / E``.
        mesh: optional mesh; expert dims get an ``ep`` sharding constraint.
        f32_param_grads: accumulate the expert weights' gradients in float32
            (:func:`ray_tpu.ops.layers.dense`).

    Returns:
        ``(y, aux)`` — ``[B, T, D]`` output and the scalar load-balance
        loss (add ``aux_weight * aux`` to the training loss).
    """
    B, T, D = x.shape
    E = w1.shape[0]
    S = B * T
    C = max(1, math.ceil(capacity_factor * S / E))
    xf = x.reshape(S, D)

    logits = xf.astype(jnp.float32) @ router_w.astype(jnp.float32)  # [S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate = probs.max(axis=-1)          # [S] top-1 gate value
    expert = probs.argmax(axis=-1)     # [S] chosen expert

    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)       # [S, E]
    # arrival order within each expert's queue; tokens past C are dropped
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot            # [S, E]
    pos_tok = pos.sum(axis=-1)                                   # [S]
    keep = (pos_tok < C).astype(jnp.float32)
    dispatch = onehot * keep[:, None]                            # [S, E]
    pos_onehot = jax.nn.one_hot(pos_tok.astype(jnp.int32), C, dtype=jnp.float32)
    disp = dispatch[..., None] * pos_onehot[:, None, :]          # [S, E, C]

    # dispatch: tokens -> per-expert buffers.  With x sharded over
    # dp/fsdp and the E dim constrained to ep this einsum IS the ep
    # all_to_all (XLA inserts it under GSPMD).
    expert_in = jnp.einsum("sec,sd->ecd", disp.astype(x.dtype), xf)
    expert_in = _constrain(expert_in, mesh, P("ep", None, None))

    # per expert: [C, D] @ [D, F], then [C, F] @ [F, D]
    h = dense(expert_in, w1, b1[:, None, :], f32_param_grads=f32_param_grads)
    h = jax.nn.gelu(h, approximate=True)
    out = dense(h, w2, b2[:, None, :], f32_param_grads=f32_param_grads)
    out = _constrain(out, mesh, P("ep", None, None))

    # combine: per-expert buffers -> tokens, weighted by the gate (the
    # gate factor keeps the router differentiable — Switch eq. 2)
    combine = disp * (gate * keep)[:, None, None]                # [S, E, C]
    y = jnp.einsum("sec,ecd->sd", combine.astype(out.dtype), out)

    # Switch load-balance loss: E * sum_e (token fraction)_e * (prob mass)_e
    f = onehot.mean(axis=0)
    Pm = probs.mean(axis=0)
    aux = E * jnp.sum(f * Pm)
    return y.reshape(B, T, D).astype(x.dtype), aux


def init_moe_params(
    key: jax.Array, n_layers: int, d_model: int, d_ff: int, n_experts: int,
    *, std: float = 0.02, res_std: Optional[float] = None,
) -> Dict[str, jax.Array]:
    """Layer-stacked expert params ``[L, E, ...]`` (router kept f32)."""
    L, D, F, E = n_layers, d_model, d_ff, n_experts
    res_std = res_std if res_std is not None else std / (2 * L) ** 0.5
    kr, k1, k2 = jax.random.split(key, 3)
    return {
        "router": jax.random.normal(kr, (L, D, E)) * std,
        "ew1": jax.random.normal(k1, (L, E, D, F)) * std,
        "eb1": jnp.zeros((L, E, F)),
        "ew2": jax.random.normal(k2, (L, E, F, D)) * res_std,
        "eb2": jnp.zeros((L, E, D)),
    }


def moe_logical_axes() -> Dict[str, Tuple]:
    """Logical axes for :func:`init_moe_params` (expert -> ep)."""
    return {
        "router": ("layers", "embed", None),
        "ew1": ("layers", "expert", "embed", "mlp"),
        "eb1": ("layers", "expert", "mlp"),
        "ew2": ("layers", "expert", "mlp", "embed"),
        "eb2": ("layers", "expert", "embed"),
    }


# -- top-k routing without drops, over the experts THIS chip holds ------------
#
# The second expert layer of this file (the Switch layer above keeps the
# LayerNorm block's training path).  What a wide expert-parallel deployment
# asks of one chip: the router scores ALL experts, a token keeps its
# ``top_k``, and the chip adds up what the experts it holds (``first_expert
# .. first_expert + n_held``) give for the tokens routed to them.  What the
# absent experts would add is left out: on one chip the layer runs without
# its exchange, and nothing stands in for the other chips.

# up to this many (token, expert) pairs go through the grouped matmuls as one
# block and a token's rows are gathered back: a decode step (its rows x top-k:
# 264 - 490 pairs in the serve cells).  A prefill call is 256 tokens or wider
# (``serve.llm.CALL_TOKENS``: 2,048 pairs and up) and goes in trips.  Lowered
# from 4,096 on the chip's measurement (PERF.md section 6, PR 46; a whole call
# of the 256 / 512 bucket, ms): K-EXAONE's 22.9 -> 17.7 / 28.1 -> 23.5,
# Granite's 31.5 -> 25.3 / 59.6 -> 41.3 (its 512 bucket was in trips already)
_ONE_BLOCK_PAIRS = 1024
# of more pairs, the HELD ones go this many rows a trip, as many trips as they
# need: a skewed router costs trips, never tokens.  The chip's sweep (PERF.md
# section 6, PR 46; TPU v5e): what a trip costs is its scatter-add, and that
# neither by its rows nor by its live rows: XLA sorts an update of 512 rows or
# more and walks the RESULT's rows (3.7 ms into [2,048, 5,120] float32 whether
# 512 or 4,096 rows are added, live or not), and adds one of up to 256 rows in
# place (~0.15 ms).  dots3-note's whole 2,048-token call / a part at offset
# 4,096, ms, by rows a trip: 4,096 (a quarter of all pairs, as it stood) 70.1 /
# 110.3, 2,048: 68.7 / 107.3, 1,024: 66.5 / 105.4, 512: 77.0 / 112.2,
# **256: 50.4 / 90.7**; 1,024 and 512 added back in slices of 256 rows: 51.9 /
# 92.1 and 51.8 / 91.9.  Granite's (20 layers over stacked weights, an eighth
# held) whole calls of 256 / 512 / 1,024 / 2,048 tokens: 31.5 / 59.6 / 81.7 /
# 135.9 as it stood, 25.3 / 41.3 / 72.6 / 146.4 at 256 (ten trips a layer at
# 2,048 tokens, each three grouped-matmul calls over 180 groups: the one shape
# that lost; two calls a trip since PR 54, prompts 0.8 of the bucket: 24.0 ->
# 22.9 / 38.9 -> 37.2 / - / 135.1 -> 132.8), 30.6 / 43.7 / 73.1 / 135.9 at
# 1,024 in slices; K-EXAONE's 128 x 2 / 256 / 512 / 1,024 / 2,048: 21.9 /
# 22.9 / 28.1 / 42.9 / 62.3 as it stood, 17.1 / 17.7 / 23.5 / 36.7 / 55.2 at
# 256.  All of that is a chip that holds a SHARE.  A stage that holds EVERY
# expert of its layers (Keye-VL's: 128 of 128, a 2,048-token part 16,384 pairs,
# all held) would walk 64 trips a layer, ~0.12 - 0.13 ms each (two grouped
# matmuls over 128 groups of which two or three have rows 57 us, the loop's
# copies 37, the gather, the gates and the scatter-add the rest), and needs no
# trip: every pair of a token is here, so nothing is ADDED into the result
# (``all_held``: one block whatever the pairs).  The chip, one part, ms a layer
# in ``moe.expert_ffn`` (PERF.md section 6, PR 60): a first part 8.6 -> 5.4, a
# part at offset 4,096 7.5 -> 3.9 (the programs 63.3 -> 43.0 and 98.9 -> 75.4);
# the layer alone 9.2 -> 6.5.  What is left is the grouped matmuls themselves
# (2.7 - 4.3 ms a layer where the experts' bytes need 1.5: the compiler tiles
# 512 rows for groups of ~128)
_TRIP_ROWS = 256


def dispatch_trips(pairs: int, held, all_held: bool = False):
    """``(block, trips)`` of one dispatch of ``pairs`` (token, expert) pairs of
    which ``held`` are this chip's: the rows one trip hands the grouped
    matmuls, and the trips it takes (``block * trips`` rows computed).
    ``held`` may be a count, an array of counts (a layer each) or a traced
    value: the device's loop and the host's counter
    (``perf_stats()["moe"]["prefill"]["rows_computed"]`` and ``["trips"]``)
    both ask here.  ``all_held`` (static): the chip holds EVERY expert of the
    layer, so the pairs go as one block whatever their number
    (:func:`held_experts_ffn`); a block that is all the pairs runs no loop."""
    if all_held or pairs <= _ONE_BLOCK_PAIRS:
        return pairs + -pairs % 8, 1
    return _TRIP_ROWS, -(-held // _TRIP_ROWS)


def route_sigmoid_top_k(x: jax.Array, router_w: jax.Array, bias: jax.Array,
                        top_k: int, scale: float):
    """``x [N, D]`` -> ``(experts [N, k] int32, gates [N, k] float32)``.
    DeepSeek-V3-style routing: ``s = sigmoid(x W_r)`` over every expert in
    float32 (at matmul precision "highest": a bf16 pass over the scores moves
    the k-th place), the ``k`` largest of ``s + bias`` chosen, and the CHOSEN
    experts' own scores (without the bias) renormalised over all ``k`` and
    scaled."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    return (experts.astype(jnp.int32),
            scale * chosen / chosen.sum(-1, keepdims=True))


def route_softmax_top_k(x: jax.Array, router_w: jax.Array, top_k: int):
    """``x [N, D]`` -> ``(experts [N, k] int32, gates [N, k] float32)``.
    Mixtral/Granite-style routing: the ``k`` largest router LOGITS are chosen
    (no sigmoid, no selection bias, no scale) and the gates are the softmax
    over those ``k`` alone.  Float32 at matmul precision "highest", for the
    reason :func:`route_sigmoid_top_k` gives."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    chosen, experts = jax.lax.top_k(logits, top_k)
    return experts.astype(jnp.int32), jax.nn.softmax(chosen, axis=-1)


def gate_up_side_by_side(p: Dict[str, Any]) -> Dict[str, Any]:
    """One layer's (or one stack of layers') parameters as they are SERVED:
    ``ew_gate`` and ``ew_up`` ``[.., n_held, D, F]``, the leaves a family's
    ``init`` makes (and a reference reads), become ONE leaf ``ew_gate_up``
    ``[.., n_held, D, 2F]``, gate's columns then up's, which is what
    :func:`held_experts_ffn` takes.  IN PLACE on the dict ``p`` (a layer
    without experts, or one laid out already, is left as it is): the two
    sources are let go here, before the caller makes the next layer's copy,
    so a model's expert weights are never held twice (a family's
    ``serving_layout`` walks its layers with this; the caller owns the dicts:
    :func:`ray_tpu.models.generate.serving_layout`)."""
    if "ew_gate" in p:
        # (ready before the next layer's copy is asked for: the sources are
        # held until the concatenation that reads them has run)
        p["ew_gate_up"] = jax.block_until_ready(
            jnp.concatenate([p.pop("ew_gate"), p.pop("ew_up")], axis=-1))
    return p


def _sorted_pairs(experts: jax.Array, first_expert, n_held: int,
                  valid: Optional[jax.Array] = None):
    """What both expert layers below do before any matmul: of the ``M = N *
    k`` (token, expert) pairs of ``experts [N, k]``, which are HELD here
    (experts ``first_expert .. first_expert + n_held``, of valid tokens),
    ``order [M]``: the pairs sorted by held expert, pairs of absent experts
    last, and ``bounds [n_held + 1]``: where each held expert's rows begin in
    that list (``bounds[n_held]``: the held pairs)."""
    M = experts.size
    local = experts.reshape(M) - first_expert
    held = (local >= 0) & (local < n_held)
    if valid is not None:
        held = held & jnp.repeat(valid, experts.shape[1])
    key = jnp.where(held, local, n_held)
    order = jnp.argsort(key)                      # held pairs first, by expert
    bounds = jnp.searchsorted(
        key[order], jnp.arange(n_held + 1)).astype(jnp.int32)
    return held, order, bounds


def _experts_block(rows: jax.Array, w_gate_up: jax.Array, w_down: jax.Array,
                   sizes: jax.Array, activation):
    """``rows [R, D]`` sorted by expert, ``sizes`` of them each (rows past
    their sum belong to no group, and what comes out for them is not meant to
    be read) -> ``[R, D]`` float32: TWO grouped matmuls (``lax.ragged_dot``,
    as the trained block's are: the Pallas grouped matmul that ships with JAX
    gave the training layer the same time to 3 % on the chip, PERF.md section
    6, PR 57, the permutations around the matmuls being what costs), gate and
    up as one call whose result is split into its halves for ``activation(g)
    * u`` (``silu``: SwiGLU; ``relu``: ReGLU), then down.  The weights are
    used in the rows' dtype."""
    F = w_down.shape[-2]
    gu = jax.lax.ragged_dot(rows, w_gate_up.astype(rows.dtype), sizes)
    h = activation(gu[:, :F]) * gu[:, F:]
    return jax.lax.ragged_dot(h, w_down.astype(rows.dtype), sizes,
                              preferred_element_type=jnp.float32)


def held_experts_ffn(
    x: jax.Array, experts: jax.Array, gates: jax.Array, w_gate_up: jax.Array,
    w_down: jax.Array, *, first_expert: int = 0,
    valid: Optional[jax.Array] = None, layer: Optional[jax.Array] = None,
    all_held: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of a top-k SwiGLU expert layer, no token dropped.

    Args:
        x: ``[N, D]`` tokens (compute dtype).
        experts, gates: ``[N, k]``, each token's chosen experts (numbered over
            ALL experts) and their weights (:func:`route_sigmoid_top_k`).
        w_gate_up: ``[n_held, D, 2F]``, an expert's gate and up matrices side
            by side on the last axis (:func:`gate_up_side_by_side`: laid out
            once when an engine takes its parameters, never in a step; a
            family's ``init`` makes ``ew_gate`` and ``ew_up``, the engine
            serves from ``ew_gate_up``); w_down: ``[n_held, F, D]``: experts
            ``first_expert .. first_expert + n_held``, without biases.
        valid: ``[N]`` bool, the real tokens.  Padding and the rows of slots
            that sit a step out are routed nowhere: they cost no expert
            matmul and no expert weight read, and their part of ``y`` is 0.
        layer: for weights that hold the experts of SEVERAL layers stacked
            (``[n_layers, n_held, D, 2F]``, a family whose layer loop is
            rolled), the index (it may be traced) of the layer to use.  The
            grouped matmuls are then given every layer's experts and sizes of
            0 for all but this layer's: a group without rows costs nothing,
            where a layer's weights sliced out to feed the kernel would be a
            copy of them (170 MB a layer a decode step at Granite's widths).
        all_held: a static fact of the caller's deployment: ``first_expert``
            is 0 and the ``n_held`` experts are ALL the router chooses from
            (a pipeline stage that holds whole layers).  It picks the way
            back to the tokens (below), never the result.

    The ``N * k`` (token, expert) pairs are sorted by held expert (pairs of
    absent experts last) and the held ones go through TWO grouped matmuls
    (``lax.ragged_dot``: on a TPU one kernel over the rows of each group, no
    capacity, and a group without rows costs nothing, not even the read of
    its weights): gate and up as one call whose result is split into its
    halves for ``silu(g) * u``, then down.  Two ways back to the tokens.
    ONE BLOCK: all ``M`` rows go through the two grouped matmuls once and a
    token's ``k`` rows are gathered back by the inverse permutation and summed
    under its gates in float32 (no loop, nothing added into anything): taken
    up to one block of pairs (``_ONE_BLOCK_PAIRS``: a decode step), and by a
    dispatch of ANY size when ``all_held``: every pair is then this chip's,
    so the block computes no row a loop would have skipped.  TRIPS: of more
    pairs (a prefill call) on a chip that holds a SHARE of the experts, the
    HELD ones take ``_TRIP_ROWS`` rows a trip, as many trips as they need
    (:func:`dispatch_trips`: a runtime count), each added into the result
    where its rows' tokens are, in float32: what a trip gathers, multiplies
    and adds back follows what this chip holds (a 32nd of the pairs where 32
    chips share a layer), not all pairs.

    Returns ``(y [N, D] float32, tokens [n_held] int32)``: the weighted sum,
    and the valid tokens routed to each held expert.
    """
    N, D = x.shape
    widen = lambda sizes: sizes  # noqa: E731 — the groups' sizes as the kernel takes them
    if layer is not None:
        n_layers, n_held = w_down.shape[:2]
        w_gate_up, w_down = (
            w.reshape(n_layers * n_held, *w.shape[2:]) for w in (w_gate_up, w_down))
        widen = lambda sizes: jax.lax.dynamic_update_slice(  # noqa: E731
            jnp.zeros((n_layers * n_held,), sizes.dtype), sizes, (layer * n_held,))
    n_held, top_k = w_down.shape[0] if layer is None else n_held, experts.shape[1]
    M = N * top_k
    held, order, bounds = _sorted_pairs(experts, first_expert, n_held, valid)
    tokens = bounds[1:] - bounds[:-1]
    gate_of = jnp.where(held, gates.reshape(M), 0.0)

    def experts_of(pairs, sizes):
        return _experts_block(x[pairs // top_k], w_gate_up, w_down,
                              widen(sizes), jax.nn.silu)

    if all_held or M <= _ONE_BLOCK_PAIRS:
        block, _ = dispatch_trips(M, 0, all_held)
        # the TPU's grouped-matmul kernel takes whole sublane tiles of rows
        # (a list of another length, 49 rows x 10, is computed densely:
        # every row against every held expert); rows past the groups' sizes
        # belong to no group
        rows = order if block == M else jnp.concatenate(
            [order, jnp.zeros((block - M,), order.dtype)])
        y = experts_of(rows, tokens)[:M]          # [M, D], sorted by expert
        # pair (n, j) sits at row rank[n * k + j]; a row past the held pairs
        # holds nothing meant to be read, and its gate is 0
        rank = jnp.argsort(order).reshape(N, top_k)
        y = jnp.where(held.reshape(N, top_k, 1), y[rank], 0.0)
        return (y * gate_of.reshape(N, top_k, 1)).sum(1), tokens

    block, trips = dispatch_trips(M, bounds[n_held])
    if M % block:  # whole trips
        order = jnp.concatenate([order, jnp.zeros((-M % block,), order.dtype)])

    def trip(i, out):
        lo = i * block
        pairs = jax.lax.dynamic_slice(order, (lo,), (block,))
        live = lo + jnp.arange(block) < bounds[n_held]
        sizes = (jnp.clip(bounds[1:], lo, lo + block)
                 - jnp.clip(bounds[:-1], lo, lo + block))
        y = jnp.where(live[:, None],
                      experts_of(pairs, sizes) * gate_of[pairs][:, None], 0.0)
        # rows past the held pairs go nowhere
        return out.at[jnp.where(live, pairs // top_k, N)].add(y, mode="drop")

    return jax.lax.fori_loop(
        0, trips, trip, jnp.zeros((N, D), jnp.float32)), tokens


# -- the same layer TRAINED: differentiable, its experts spread over the chips --
#
# ``held_experts_ffn`` walks its held pairs with ``lax.fori_loop(0, trips,
# ...)`` under a TRACED trip count (what a skewed router costs is trips, never
# tokens).  Reverse-mode differentiation cannot take that loop: it lowers to a
# ``while`` whose transpose would have to keep one set of residuals a trip, for
# a number of trips no shape says.  The training form has no loop and nothing
# that follows the routing: it computes ALL the ``N * k`` pairs of the tokens
# it is given against ALL the experts, so every shape is static (``N * k``
# rows through the grouped matmuls however a router skews them: no capacity, no
# dropped token at any imbalance) and plain reverse mode takes it.  Under a
# mesh the chips divide the TOKENS and each chip is brought the experts it
# does not hold (below): a chip's work is its own tokens' pairs, the same
# rows whatever the routing.  The form that sent the tokens to the chip that
# holds their experts (an all-gather of the tokens, each chip its own experts'
# part in 65,536-row trips under a runtime count, a reduce-scatter of the
# partial sums) was built first and measured on the four chips (PERF.md
# section 6, PR 57): every chip waited for the one the routers loaded most
# (1.41 - 1.78 x the mean over seeds), and the step's time followed the seed
# by 2 - 5 %.
#
# What costs on the v5e is less the matmuls than the two permutations around
# them (one chip, 98 k pairs of 2,560 values: gathering the rows 4.8 ms,
# ADDING them back into a float32 result 15.2 ms where the grouped matmuls are
# ~8), so both permutations and both their transposes are GATHERS here: a
# token has exactly ``k`` pairs, all on this chip, so "add a token's pairs" is
# a gather by the inverse permutation and a sum over ``k``.
#
# And the block has ONE backward pass of its own (:func:`_experts_block_train`,
# PR 62), because plain reverse mode of "down matmul, gather back, times the
# gates, sum" needs every pair's down result for the gates' gradient: under
# the model's remat the backward pass ran the down matmul a second time and
# gathered its float32 ``[pairs, D]`` result (1 GB at the cell's sizes) again,
# and the cotangent was a float32 ``ct x gate`` product written whole and then
# gathered.  The gate goes in BEFORE the down matmul instead.  What the
# backward pass keeps: the sorted rows, the gate-and-up result, the sorted
# gates and the sort itself, all upstream of the down matmul; what a replay
# recomputes: one row gather and the gate-and-up matmul (the sort too, unless
# a policy keeps ``EXPERTS_SORT``); what it computes: the cotangent's rows
# gathered once in the dtype they arrive in, four grouped matmuls, the gates'
# gradient from ``dh`` over ``[pairs, F]``.  Seven grouped matmuls a layer
# where there were eight, one float32 ``[pairs, D]`` gather where there were
# three; the layer alone on one chip, forward + replay + backward, 108.6 ->
# 74.4 ms (PERF.md section 6, PR 62).

ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
# what a remat policy may keep of a layer's experts (``save_only_these_names``):
# the pairs' sort (``order``, ``rank``, the groups' sizes: integers, 0.8 MB a
# layer at 98 k pairs), so that a replay sorts nothing again.  (The layer's
# RESULT had a name here until PR 62, and keeping it kept nothing: only the
# residual sum reads it, and a sum's backward pass needs no operand.)
EXPERTS_SORT = "experts_sort"

# ``lhs [R, A]``, ``rhs [R, B]``, ``sizes`` -> ``[E, A, B]``: the rows of each
# group contracted (a grouped matmul's transpose in its weights)
_ROWS_CONTRACTED = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _halves(gu):
    """``gu [R, 2F]`` -> gate's columns and up's, float32."""
    F = gu.shape[-1] // 2
    return gu[:, :F].astype(jnp.float32), gu[:, F:].astype(jnp.float32)


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def _experts_block_train(x, gates, w_gate_up, w_down, order, rank, sizes,
                         activation: str):
    """The TRAINED block, tokens in to tokens out, with ONE backward pass of
    its own.  ``x [N, D]``, ``gates [N, k]`` float32, the weights in ``x``'s
    dtype; ``order [R]``: the pairs sorted by expert (pair ``p`` is token ``p
    // k``'s; ``R``: ``N * k`` and up to 7 rows more that belong to no group
    and are never read back), ``rank [k, N]`` its inverse (pair ``(n, j)`` sits
    at row ``rank[j, n]``: a token's ``k`` rows gathered as ``k`` blocks of
    ``[N, D]`` and the blocks added, where ``[N, k, D]`` would be laid out
    again before its sum, ``k`` not being a whole tile of rows), ``sizes [E]``
    the rows of each expert.  Returns ``[N, D]`` in ``x``'s dtype.

    A pair's gate goes in BEFORE the down matmul (``sum_j g_j (h_j W[e_j]) =
    sum_j (g_j h_j) W[e_j]``): it scales ``act(g) * u`` where that is formed,
    and the way back to the tokens is a plain sum of a token's ``k`` rows.
    Nothing in the backward pass then needs the down matmul's result: the
    gates' gradient is ``sum_F dh * (act(g) * u)`` over ``[R, F]`` with ``dh =
    ct W_down^T``, which the pass computes anyway.  Its residuals all lie
    UPSTREAM of the down matmul (the sorted rows, ``gu``, the sorted gates),
    so a replay under ``jax.checkpoint`` runs the sort (unless kept:
    ``EXPERTS_SORT``), one row gather and the gate-and-up matmul, and the down
    matmul, its float32 ``[R, D]`` result and that result's gather are dead
    code there.  Every grouped matmul has operands in ``x``'s dtype and
    float32 accumulation; sums over ``k`` are float32."""
    return _experts_block_train_fwd(
        x, gates, w_gate_up, w_down, order, rank, sizes, activation)[0]


def _experts_block_train_fwd(x, gates, w_gate_up, w_down, order, rank, sizes,
                             activation):
    rows = x[order // gates.shape[1]]
    gate_of_row = gates.reshape(-1)[order]
    gu = jax.lax.ragged_dot(rows, w_gate_up, sizes)
    # formed in float32 and rounded once, gate and all
    g, u = _halves(gu)
    h = (ACTIVATIONS[activation](g) * u * gate_of_row[:, None]).astype(gu.dtype)
    y = jax.lax.ragged_dot(h, w_down, sizes, preferred_element_type=jnp.float32)
    out = y[rank].sum(0).astype(x.dtype)
    return out, (rows, gu, gate_of_row, w_gate_up, w_down, order, rank, sizes)


def _experts_block_train_bwd(activation, kept, ct):
    rows, gu, gate_of_row, w_gate_up, w_down, order, rank, sizes = kept
    grouped = partial(jax.lax.ragged_dot_general, group_sizes=sizes,
                      ragged_dot_dimension_numbers=_ROWS_CONTRACTED)
    with jax.named_scope("moe.expert_ffn"):  # (a rule of its own has no scope)
        # the cotangent's rows once, in the dtype it arrives in; the weights'
        # gradients before what follows from them, so that their way home
        # (``experts_ffn_train``'s shifts) has the rest to fly under
        hu, pull = jax.vjp(
            lambda g, u: ACTIVATIONS[activation](g) * u, *_halves(gu))
        h = (hu * gate_of_row[:, None]).astype(gu.dtype)
        ct_rows = ct[order // rank.shape[0]]
        d_w_down = grouped(h, ct_rows, preferred_element_type=jnp.float32)
        dh = jax.lax.ragged_dot(ct_rows, jnp.swapaxes(w_down, 1, 2), sizes,
                                preferred_element_type=jnp.float32)
        d_gate_of_row = (dh * hu).sum(-1)
        dgu = jnp.concatenate(
            pull(dh * gate_of_row[:, None]), -1).astype(gu.dtype)
        d_w_gate_up = grouped(rows, dgu)
        drows = jax.lax.ragged_dot(dgu, jnp.swapaxes(w_gate_up, 1, 2), sizes)
        dx = drows[rank].astype(jnp.float32).sum(0)
        return (dx.astype(ct.dtype), d_gate_of_row[rank].T,
                d_w_gate_up, d_w_down.astype(w_down.dtype), None, None, None)


_experts_block_train.defvjp(_experts_block_train_fwd, _experts_block_train_bwd)


def _experts_train(x, gates, w_gate_up, w_down, experts, activation: str):
    """``x [N, D]``, ``experts``, ``gates [N, k]`` over ALL the experts of
    ``w_gate_up [E, D, 2F]`` / ``w_down [E, F, D]`` -> ``[N, D]`` in ``x``'s
    dtype: the ``N * k`` pairs sorted by expert, and all of them through
    :func:`_experts_block_train`."""
    _, order, bounds = _sorted_pairs(experts, 0, w_down.shape[0])
    # the TPU's grouped-matmul kernel takes whole sublane tiles of rows (a
    # list of another length is computed densely); rows past the groups
    # belong to no group and are not read
    rank = jnp.argsort(order).reshape(experts.shape).T
    order = jnp.pad(order, (0, -experts.size % 8))
    order, rank, sizes = checkpoint_name(
        (order, rank, bounds[1:] - bounds[:-1]), EXPERTS_SORT)
    return _experts_block_train(
        x, gates.astype(jnp.float32), w_gate_up.astype(x.dtype),
        w_down.astype(x.dtype), order, rank, sizes, activation)


def experts_ffn_train(
    x: jax.Array, experts: jax.Array, gates: jax.Array, w_gate_up: jax.Array,
    w_down: jax.Array, *, activation: str = "silu",
    mesh: Optional[Mesh] = None, axis: Optional[str] = None,
) -> jax.Array:
    """A top-k gated expert layer that a train step differentiates, no token
    dropped, its experts spread over the chips of mesh axis ``axis``.

    ``x [N, D]`` tokens, ``experts``, ``gates [N, k]`` (numbered over ALL
    experts, a router's: :func:`route_softmax_top_k`), ``w_gate_up [E, D, 2F]``
    (gate's columns then up's), ``w_down [E, F, D]``, ``activation``: ``silu``
    (SwiGLU) or ``relu`` (ReGLU).  Returns ``y [N, D]`` in ``x``'s dtype: the
    gate-weighted sum of each token's ``k`` experts.  Gradients reach ``x``,
    ``gates`` and both weights through the block's own backward pass
    (:func:`_experts_block_train`: it keeps what lies upstream of the down
    matmul and the sort, a replay under ``jax.checkpoint`` recomputes one row
    gather and the gate-and-up matmul, never the down matmul).

    With a mesh, tokens AND experts are divided over ``axis`` (``N`` and ``E``
    both in ``mesh.shape[axis]`` contiguous blocks: a chip HOLDS its block of
    the experts, their masters and their optimizer state; an axis of one chip
    is the plain path).  The exchange brings the EXPERTS to the tokens: an
    all-gather of the experts' matrices in ``x``'s dtype before a chip
    computes its own tokens' pairs and, in the backward pass, the same gather
    again and the matrices' gradients home in float32: each chip sends every
    other chip that chip's block of its partial gradient, ``n - 1``
    independent shifts (``ppermute``) that the receiver adds to its own in a
    fixed order (never a sum of bf16 partials:
    :func:`ray_tpu.parallel.sharding.gather_for_compute`'s rule; the same
    bytes as a reduce-scatter moves).  Both are static shapes that no routing
    changes.  A shift is a start and a done with work between, where the
    reduce-scatter it replaced was ONE instruction that held the chip (12 ms
    a layer for the gate-and-up gradients on the v5e's 2x2): the gradients
    fly under the backward pass's grouped matmuls.  The exchange is tied to
    the layer (``optimization_barrier`` with the layer's tokens, and so with
    their cotangent): a transfer still in flight makes any other collective
    issued meanwhile wait for it, so one left to fly under the attention
    costs the FSDP parameters' gathers what it saves here.  What still
    stands on the line is the gather, 5 ms a layer forward and again in the
    backward pass (PERF.md section 6, PR 58).  Scopes: ``moe.exchange`` the
    collectives, the casts and the sum; ``moe.expert_ffn`` the rest."""
    if mesh is None or mesh.shape[axis] == 1:
        with jax.named_scope("moe.expert_ffn"):
            return _experts_train(
                x, gates, w_gate_up, w_down, experts, activation)

    n = mesh.shape[axis]

    @jax.custom_vjp
    def brought(w):
        return jax.lax.all_gather(w.astype(x.dtype), axis, tiled=True)

    def sent_home(w, ct):
        """A chip's partial gradient of ALL the experts -> the sum over the
        chips of the block it holds, float32."""
        with jax.named_scope("moe.exchange"):
            me = jax.lax.axis_index(axis)
            blocks = ct.reshape((n, ct.shape[0] // n) + ct.shape[1:])
            block_of = lambda chip: jax.lax.dynamic_index_in_dim(  # noqa: E731
                blocks, chip % n, keepdims=False).astype(jnp.float32)
            home = block_of(me)
            for by in range(1, n):  # a fixed order of summation
                home = home + jax.lax.ppermute(
                    block_of(me + by), axis, [(c, (c + by) % n) for c in range(n)])
            return (home.astype(w.dtype),)

    # (the residual is the held block itself: only its dtype is read)
    brought.defvjp(lambda w: (brought(w), w), sent_home)

    def on_chip(x, experts, gates, w_gate_up, w_down):
        with jax.named_scope("moe.exchange"):
            # the exchange stays INSIDE the layer: no gather before the
            # tokens are here; transposed, no cotangent out before the
            # gradients are home (docstring)
            x, w_gate_up, w_down = jax.lax.optimization_barrier(
                (x, w_gate_up, w_down))
            w_gate_up, w_down = brought(w_gate_up), brought(w_down)
        with jax.named_scope("moe.expert_ffn"):
            return _experts_train(
                x, gates, w_gate_up, w_down, experts, activation)

    return jax.shard_map(
        on_chip, mesh=mesh, in_specs=(P(axis),) * 5, out_specs=P(axis),
        check_vma=False)(x, experts, gates, w_gate_up, w_down)
