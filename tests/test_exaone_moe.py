"""The EXAONE-MoE family (window and full attention layers in one cache,
sigmoid-routed experts of which this chip holds a block, beside a shared one)
against its plain reference (``benchmark/reference/k_exaone_ref.py``), at a
small size on the CPU.

Tolerances.  With ``dtype=float32`` the program and the reference do the
same arithmetic in another order (grouped matmuls against a masked loop, a
blocked band against a masked square, one softmax merged from a ring and a
chunk's columns), so logits of size ~1 agree to a few 1e-6; the limit is
``F32_TOL = 2e-4``, far under what any departure makes: a ring entry read
one position off (>1e-2), a dropped token (>1e-1), a bf16 router pass (1e-1
where the k-th expert changes).
"""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import family_harness
import pytest
from family_harness import (
    engine,
    one_shot,
    padded,
    run_engine,
    serve,
    served_layer,
    shares_add_up,
    sigmoid_top_k_by_hand,
    tiny_model,
    worst_gap,
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import k_exaone_ref as ref  # noqa: E402
from ray_tpu.models import exaone_moe as em  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops.attention import attention  # noqa: E402
from ray_tpu.serve.llm import GenerationEngine, make_config  # noqa: E402

pytestmark = pytest.mark.usefixtures("kept_engine_programs")
F32_TOL = 2e-4


def sizes_of(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "sliding_windows": cfg.sliding_windows,
            "top_k": cfg.experts_per_token, "routed_scale": cfg.routed_scale,
            "first_expert": cfg.experts_held[0], "rope_theta": cfg.rope_base,
            "rms_eps": cfg.rms_eps}


@pytest.fixture(scope="module")
def model():
    # window 8 (ring 16), 16 experts of which 4..11 are held, top-4
    return tiny_model("exaone_moe")


def ref_logits(model, seq):
    cfg, params = model
    return np.asarray(ref.logits(
        params, jnp.asarray([padded(seq)]), sizes_of(cfg))[0])[:len(seq)]


def test_config_reads_the_published_lists_up_to_its_depth():
    cfg = make_config(
        "exaone_moe", "tiny", n_layers=5,
        layer_types=[em.WINDOW] * 3 + [em.FULL] + [em.WINDOW] * 4,
        mlp_layer_types=[em.DENSE] + [em.SPARSE] * 7, experts_held=[0, 16])
    assert cfg.sliding_windows == (8, 8, 8, 0, 8)
    assert cfg.mlp_layer_types == (em.DENSE,) + (em.SPARSE,) * 4
    hash(cfg)  # jit closes over it
    # the default pattern is the published one: LLLG, the first layer dense
    assert em.ExaoneMoeConfig.tiny(n_layers=8).sliding_windows == (
        8, 8, 8, 0, 8, 8, 8, 0)
    with pytest.raises(AssertionError):
        em.ExaoneMoeConfig.tiny(experts_held=(12, 8))  # past the router


@pytest.mark.parametrize("layer", [0, 1, 3], ids=[
    "window_dense", "window_sparse", "full_sparse"])
def test_one_block_of_each_kind_against_the_reference(model, layer):
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(layer), (2, 21, cfg.d_model))
    p, window = params["layers"][layer], cfg.sliding_windows[layer]
    # the block reads the served layout, the reference ``init``'s leaves
    got, routed, _ = em.block(x, served_layer(p), cfg, window=window)
    sizes = {k: v for k, v in sizes_of(cfg).items() if k != "sliding_windows"}
    with jax.default_matmul_precision("highest"):
        want = ref._layer(x, p, window=window, lower=None, **sizes)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < F32_TOL
    assert (routed is None) == (layer == 0)
    if routed is not None:  # 42 tokens x 4 choices, half of the experts held
        assert 0 < int(routed["tokens"].sum()) <= 42 * 4
        assert int(routed["touched"]) == int((routed["tokens"] > 0).sum())


def test_forward_against_the_reference(model):
    cfg, params = model
    seq = list(np.random.RandomState(1).randint(0, cfg.vocab_size, 40))
    got = np.asarray(em.apply(params, jnp.asarray([seq]), cfg)[0])
    assert np.abs(got - ref_logits(model, seq)).max() < F32_TOL


@pytest.mark.parametrize("n_prompt", [3, 8, 37], ids=[
    "shorter_than_window", "equal_to_window", "several_windows"])
def test_prefill_then_decode_through_the_cache_against_the_reference(
        model, n_prompt):
    """Prefill, then three decode chunks of 5 steps through the cache (ring
    16: the 37-token prompt has wrapped it twice, and every chunk's flush
    wraps again somewhere): each served token is the reference's best at its
    position in one full forward over prompt + served tokens."""
    cfg, params = model
    rng = np.random.RandomState(n_prompt)
    prompts = [list(rng.randint(0, cfg.vocab_size, n_prompt)),
               list(rng.randint(0, cfg.vocab_size, max(1, n_prompt - 2)))]
    served, cache, _ = serve(  # a third slot sits idle
        cfg, params, prompts, (None,) * 3, steps=5, bucket=40, cache_len=64)
    assert cache["k"].shape[0] == 1 and cache["k_ring"].shape == (
        4, 3, cfg.n_kv_heads, cfg.head_dim, 16)
    assert int(cache["pos"][2]) == len(prompts[0]) + 15
    assert worst_gap(partial(ref_logits, model), prompts, served) < F32_TOL


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """16 experts over 8 chips, 2 a chip: the parts the shares give, the
    shared expert counted once, are the uncut layer (the reference, given
    every expert)."""
    whole = em.ExaoneMoeConfig.tiny(dtype=jnp.float32)
    p = em.init_layer(whole, jax.random.PRNGKey(3), 1)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 9, whole.d_model))
    top_k, scale = whole.experts_per_token, whole.routed_scale
    worst, counted = shares_add_up(
        p, h, 8, moe.route_sigmoid_top_k(
            h.reshape(18, -1), p["router"], p["router_bias"], top_k, scale),
        sigmoid_top_k_by_hand(h, p, top_k, scale), ref._swiglu, whole.n_experts)
    assert counted == 18 * top_k  # every choice, once
    assert worst < F32_TOL


def _to_one_expert(n_tokens, valid=None):
    """Every token's first choice is held expert 0, its other three absent
    (experts 6..8 of 9, of which 0..5 are held)."""
    experts = np.tile(np.array([0, 6, 7, 8], np.int32), (n_tokens, 1))
    return dict(experts=experts, valid=np.arange(n_tokens) < (
        n_tokens if valid is None else valid))


def _a_32nd_held():
    """128 tokens x top-4 over 192 experts of which 6 are held: a 32nd of the
    pairs, one trip of 64 rows with a quarter of it live."""
    rng = np.random.RandomState(46)
    experts = np.stack([rng.choice(192, 4, replace=False) for _ in range(128)])
    return dict(experts=experts.astype(np.int32))


def _all_six(even: bool):
    """90 tokens x top-3 (270 pairs) over the six experts the dispatch test
    holds: each token three of the six, or expert 2 and two of 0, 4 and 5."""
    rng = np.random.RandomState(60)
    pick = (lambda: rng.choice(6, 3, replace=False)) if even else (
        lambda: np.append(2, rng.choice([0, 4, 5], 2, replace=False)))
    return dict(experts=np.stack([pick() for _ in range(90)]).astype(np.int32))


# (token, expert) pairs through ``held_experts_ffn``: the routing, the rows of
# a trip and the pairs of one block (None: the module's), and the trips the
# dispatch has to take
DISPATCHES = {
    # 64 tokens, each choosing experts 0..3, all four held, some rows no token
    "one_trip": dict(
        experts=np.tile(np.arange(4, dtype=np.int32), (64, 1)),
        valid=np.arange(64) % 7 != 0, trips=1),
    "trips": dict(
        experts=np.tile(np.arange(4, dtype=np.int32), (64, 1)),
        valid=np.arange(64) % 7 != 0, one_block=100, trip_rows=64, trips=4),
    # one trip, an eighth full when a trip was a quarter of ALL pairs
    "a_32nd_of_the_pairs_held": dict(
        **_a_32nd_held(), one_block=100, trip_rows=64, trips=1),
    # several trips, the last one ragged: 150 pairs of one expert, 64 a trip
    "all_to_one_held_expert": dict(
        **_to_one_expert(150), one_block=100, trip_rows=64, trips=3),
    "pairs_exactly_a_block": dict(
        **_to_one_expert(80, valid=64), one_block=100, trip_rows=64, trips=1),
    "pairs_one_under_a_block": dict(
        **_to_one_expert(80, valid=63), one_block=100, trip_rows=64, trips=1),
    "pairs_one_over_a_block": dict(
        **_to_one_expert(80, valid=65), one_block=100, trip_rows=64, trips=2),
    # the experts of four layers stacked, the third's used
    "stacked_weights_one_layer_used": dict(
        **_a_32nd_held(), one_block=100, trip_rows=64, trips=1, layer=2),
    "stacked_weights_several_trips": dict(
        **_to_one_expert(150), one_block=100, trip_rows=64, trips=3, layer=1),
    # 100 tokens x held experts (0, 1): token n's rows are n and 100 + n of
    # the sorted list, in trips 0 - 1 and 1 - 3: its sum crosses trips
    "a_tokens_two_experts_in_different_trips": dict(
        experts=np.tile(np.array([0, 1, 7, 8], np.int32), (100, 1)),
        one_block=100, trip_rows=64, trips=4),
    # a pair list that is no multiple of 8 rows (a decode step's 49 x 10)
    "one_block_filled_to_whole_tiles": dict(
        experts=np.tile(np.array([1, 3, 8], np.int32), (13, 1)), trips=1),
    # a stage that holds EVERY expert (``all_held``): more pairs than a block
    # go as ONE block, under an even router ...
    "every_expert_held_even": dict(
        **_all_six(even=True), one_block=100, trip_rows=64, all_held=True, trips=1),
    # ... one that leaves experts 1 and 3 without a row and gives 2 every token
    "every_expert_held_some_without_a_row_one_with_most": dict(
        **_all_six(even=False), one_block=100, trip_rows=64, all_held=True,
        trips=1),
    # ... and with padding rows among the tokens
    "every_expert_held_padding_rows": dict(
        **_all_six(even=True), valid=(np.arange(90) % 5 != 0) & (np.arange(90) < 79),
        one_block=100, trip_rows=64, all_held=True, trips=1),
}


@pytest.mark.parametrize("case", list(DISPATCHES))
def test_no_token_is_dropped_when_every_token_takes_the_same_experts(
        monkeypatch, case):
    """Every held (token, expert) pair is computed, whatever the routing puts
    into a trip: against the dense sum, an expert at a time.  Through one
    block of pairs, or (the block and the trip made small) through as many
    trips as the HELD pairs need, which is what ``dispatch_trips`` says and
    what the device's loop is handed."""
    want = dict(DISPATCHES[case])
    if "one_block" in want:
        monkeypatch.setattr(moe, "_ONE_BLOCK_PAIRS", want["one_block"])
        monkeypatch.setattr(moe, "_TRIP_ROWS", want["trip_rows"])
    experts = jnp.asarray(want["experts"])
    N, top_k = experts.shape
    D, F, E, layer = 16, 8, 6, want.get("layer")
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(keys[0], (N, D))
    stack = () if layer is None else (4,)
    w_gate, w_up = (jax.random.normal(k, (*stack, E, D, F)) / 4 for k in keys[1:3])
    w_down = jax.random.normal(keys[3], (*stack, E, F, D)) / 3
    gates = jax.random.uniform(keys[4], (N, top_k), minval=0.1)
    valid = jnp.asarray(want.get("valid", np.ones(N, bool)))
    ran = []
    loop = jax.lax.fori_loop
    monkeypatch.setattr(jax.lax, "fori_loop", lambda lo, hi, *a: (
        ran.append(int(hi)), loop(lo, hi, *a))[1])
    all_held = want.get("all_held", False)
    layer_ffn = partial(
        moe.held_experts_ffn, x, experts, gates, moe.gate_up_side_by_side(
            {"ew_gate": w_gate, "ew_up": w_up})["ew_gate_up"], w_down,
        valid=valid, layer=None if layer is None else jnp.int32(layer))
    y, tokens = layer_ffn(all_held=all_held)
    if layer is not None:
        w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
    took = np.asarray((experts[:, :, None] == jnp.arange(E)) & valid[:, None, None])
    assert tokens.tolist() == took.sum((0, 1)).tolist()
    dense = sum(
        (took[:, :, e] * gates).sum(1)[:, None]
        * ((jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e])
        for e in range(E))
    assert np.abs(np.asarray(y) - np.asarray(dense)).max() < 1e-5
    block, trips = moe.dispatch_trips(N * top_k, int(took.sum()), all_held)
    assert trips == want["trips"]
    if N * top_k > moe._ONE_BLOCK_PAIRS and not all_held:
        assert ran == [trips] and block == moe._TRIP_ROWS
    else:
        assert ran == [] and block == N * top_k + -(N * top_k) % 8
    if all_held:
        # the argument left at its default walks the same pairs in trips:
        # the same result and counts; a padding row's part of ``y`` is 0
        assert N * top_k > moe._ONE_BLOCK_PAIRS
        y_loop, tokens_loop = layer_ffn()
        assert ran == [moe.dispatch_trips(N * top_k, int(took.sum()))[1]]
        assert tokens_loop.tolist() == tokens.tolist()
        assert np.abs(np.asarray(y) - np.asarray(y_loop)).max() < 1e-5
        assert not np.asarray(y)[~np.asarray(valid)].any()


def _eqns(jaxpr, name):
    """Every equation called ``name`` in ``jaxpr`` and the jaxprs inside it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _eqns(sub, name)
    return found


@pytest.mark.parametrize("n,top_k,rows,loops", [
    # a 2,048-token prefill call with top-8: a trip's rows, not M / 4 = 4,096
    (2048, 8, 256, 1),
    # the narrowest prefill calls (256 tokens: top-8, and Granite's top-10)
    (256, 8, 256, 1), (256, 10, 256, 1),
    # decode steps (33 rows x 8, 49 x 10): one block of M rows filled to
    # whole sublane tiles, no loop, no scatter
    (33, 8, 264, 0), (49, 10, 496, 0), (128, 8, 1024, 0),
])
def test_the_grouped_matmuls_are_handed_a_trips_rows(n, top_k, rows, loops):
    """What lowers: above one block of pairs (every prefill call) the TWO
    ``ragged_dot`` calls (gate and up as one over the side-by-side weights,
    then down) sit in the loop over trips and take ``_TRIP_ROWS`` rows each,
    whatever ``M`` is; up to one block (a decode step) they take the ``M``
    pairs at once and a token's rows are gathered back.  No concatenation of
    an expert-weight shape is part of the dispatch."""
    assert (moe._ONE_BLOCK_PAIRS, moe._TRIP_ROWS) == (1024, 256)
    f32, i32 = jnp.float32, jnp.int32
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((n, 16), f32), ((n, top_k), i32), ((n, top_k), f32),
        ((4, 16, 16), f32), ((4, 8, 16), f32))]
    jaxpr = jax.make_jaxpr(moe.held_experts_ffn)(*shapes).jaxpr
    dots = _eqns(jaxpr, "ragged_dot") or _eqns(jaxpr, "ragged_dot_general")
    assert [eqn.invars[0].aval.shape[0] for eqn in dots] == [rows] * 2
    assert [eqn.invars[1].aval.shape for eqn in dots] == [(4, 16, 16), (4, 8, 16)]
    assert not any(len(eqn.outvars[0].aval.shape) == 3
                   for eqn in _eqns(jaxpr, "concatenate"))
    assert len(_eqns(jaxpr, "while")) == loops
    assert len(_eqns(jaxpr, "scatter-add")) == loops
    assert moe.dispatch_trips(n * top_k, 0)[0] == rows


@pytest.mark.parametrize("pairs,held,want", [
    (264, 7, (264, 1)), (490, 490, (496, 1)), (1024, 0, (1024, 1)),
    (1025, 0, (256, 0)), (16384, 512, (256, 2)), (16384, 256, (256, 1)),
    (16384, 257, (256, 2)), (20480, 2560, (256, 10)),
])
def test_dispatch_trips(pairs, held, want):
    assert moe.dispatch_trips(pairs, held) == want
    # a layer each, as the engine's counter asks; and a traced count
    block, trips = moe.dispatch_trips(pairs, np.array([held, held]))
    assert np.broadcast_to(block * trips, (2,)).tolist() == [want[0] * want[1]] * 2
    assert int(jax.jit(lambda h: jnp.asarray(
        moe.dispatch_trips(pairs, h)[1]))(held)) == want[1]


@pytest.mark.parametrize("pairs,held,want", [
    # a 2,048-token part with top-8 on a stage that holds every expert: all
    # its pairs at once, whatever came back as held (padding rows are not)
    (16384, 16384, (16384, 1)), (16384, 16000, (16384, 1)), (16384, 0, (16384, 1)),
    # filled up to whole sublane tiles, as a narrow dispatch is
    (2050, 2050, (2056, 1)), (1025, 7, (1032, 1)),
    # a decode step: one block with or without the fact
    (264, 264, (264, 1)), (490, 490, (496, 1)),
])
def test_dispatch_trips_when_every_expert_is_held(pairs, held, want):
    assert moe.dispatch_trips(pairs, held, all_held=True) == want
    # a layer each, as the engine's counter asks
    block, trips = moe.dispatch_trips(pairs, np.array([held, held]), True)
    assert np.broadcast_to(block * trips, (2,)).tolist() == [want[0]] * 2


@pytest.mark.parametrize("n,top_k,rows", [(2048, 8, 16384), (257, 8, 2056)])
def test_every_expert_held_lowers_without_a_loop(n, top_k, rows):
    """What lowers for a prefill call on a stage that holds every expert: NO
    ``while`` and no scatter-add, exactly two ``ragged_dot`` over all the
    call's pairs (filled to whole sublane tiles); the same shapes without the
    fact keep their loop (the case above this one's neighbour)."""
    f32, i32 = jnp.float32, jnp.int32
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((n, 16), f32), ((n, top_k), i32), ((n, top_k), f32),
        ((4, 16, 16), f32), ((4, 8, 16), f32))]
    jaxpr = jax.make_jaxpr(partial(moe.held_experts_ffn, all_held=True))(
        *shapes).jaxpr
    dots = _eqns(jaxpr, "ragged_dot") or _eqns(jaxpr, "ragged_dot_general")
    assert [eqn.invars[0].aval.shape[0] for eqn in dots] == [rows] * 2
    assert not _eqns(jaxpr, "while") and not _eqns(jaxpr, "scatter-add")
    assert len(_eqns(jax.make_jaxpr(moe.held_experts_ffn)(*shapes).jaxpr,
                     "while")) == 1


@pytest.mark.parametrize("t,window", [(256, 128), (384, 100), (48, 8), (128, 128)])
def test_band_attention_is_the_masked_square(t, window):
    q, k, v = (jax.random.normal(key, (2, 3, t, 16))
               for key in jax.random.split(jax.random.PRNGKey(t), 3))
    got = attention(q, k, v, causal=True, window=window)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / 4.0
    s = np.where((j <= i) & (j > i - window), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkd->bhqd", w / w.sum(-1, keepdims=True), v)
    assert np.abs(np.asarray(got) - want).max() < 1e-5


# buckets (8, 16, 32) at CALL_TOKENS 16: rows 2 / 1 / 1; 4 slots; an answer
# of 6 tokens is a prefill and two chunks of 3 steps, so a slot admitted in
# tick t is free again for tick t + 2
ADMISSIONS = {
    # a burst of one bucket is several calls in ONE tick, each rows(b) wide
    "burst_of_one_bucket": dict(
        lens=(5, 6, 3, 7), budget=None,
        ticks=[[[5, 6], [3, 7]]],
        prefill={8: dict(calls=2, rows=4, padded_tokens=32, prompts=4,
                         live_tokens=21)}),
    # FIFO: no request overtakes another, a prompt is padded to ITS bucket
    # only (6 and 4 do not join 5's call past the 20 between them, and 20
    # does not widen anyone), and admission stops at the free slots
    "mixed_buckets_keep_fifo": dict(
        lens=(5, 20, 6, 4, 12, 3), budget=None,
        ticks=[[[5], [20], [6, 4]], [[12], [3]]],
        prefill={8: dict(calls=3, rows=6, padded_tokens=48, prompts=4,
                         live_tokens=18),
                 16: dict(calls=1, rows=1, padded_tokens=16, prompts=1,
                          live_tokens=12),
                 32: dict(calls=1, rows=1, padded_tokens=32, prompts=1,
                          live_tokens=20)}),
    # the tick's budget of PADDED tokens stops admission with slots still
    # free; the rest goes next tick, in order
    "tick_budget_stops_admission": dict(
        lens=(5, 6, 12, 4, 20), budget=32,
        ticks=[[[5, 6], [12]], [[4]], [[20]]],
        prefill={8: dict(calls=2, rows=4, padded_tokens=32, prompts=3,
                         live_tokens=15),
                 16: dict(calls=1, rows=1, padded_tokens=16, prompts=1,
                          live_tokens=12),
                 32: dict(calls=1, rows=1, padded_tokens=32, prompts=1,
                          live_tokens=20)}),
    # a tick's first call goes whatever it is wide: a budget under a call's
    # width admits one call a tick and starves nobody
    "a_ticks_first_call_always_goes": dict(
        lens=(20, 3, 30), budget=8,
        ticks=[[[20]], [[3]], [[30]]],
        prefill={8: dict(calls=1, rows=2, padded_tokens=16, prompts=1,
                         live_tokens=3),
                 32: dict(calls=2, rows=2, padded_tokens=64, prompts=2,
                          live_tokens=50)}),
}


@pytest.mark.parametrize("case", list(ADMISSIONS))
def test_engine_admits_under_a_token_budget_in_order(model, monkeypatch, case):
    """rows(bucket) = max(1, CALL_TOKENS // bucket), at most the slots; the
    queue's head is formed into calls greedily and in order, as many a tick
    as the free slots and the tick's budget of padded tokens allow.  The
    engine's answers are the one-shot path's whatever call a prompt rode
    in, and its counters say exactly what was dispatched.  The calls sit on
    both sides of one block of (token, expert) pairs (made 64 here: buckets 8
    x 2 rows and 16 are 64 pairs, one block of 64 rows a layer whatever is
    held; bucket 32 is 128 pairs, trips of 16 rows as its held pairs need)."""
    from ray_tpu.serve import llm

    cfg, params = model
    want = ADMISSIONS[case]
    monkeypatch.setattr(llm, "CALL_TOKENS", 16)
    monkeypatch.setattr(moe, "_ONE_BLOCK_PAIRS", 64)
    monkeypatch.setattr(moe, "_TRIP_ROWS", 16)
    # the block's size is read at trace time: programs of a path of their own
    monkeypatch.setattr(family_harness, "PATH", "one_block_is_64_pairs")
    eng, _, _ = engine(  # never started: the test is the engine thread
        "exaone_moe", n_slots=4, max_new_tokens=6, decode_chunk_steps=3,
        prefill_buckets=(8, 16, 32), prefill_token_budget=want["budget"])
    assert eng._rows == {8: 2, 16: 1, 32: 1}
    assert eng._tick_tokens == (want["budget"] or 4 * 32)
    landed, count = [], eng._count_routed
    monkeypatch.setattr(eng, "_count_routed", lambda phase, counts, *a, **kw: (
        landed.append((phase, counts, kw.get("padded"))),
        count(phase, counts, *a, **kw))[1])
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(0, cfg.vocab_size, n)) for n in want["lens"]]
    futs = [eng.submit(p, 6) for p in prompts]
    seen = run_engine(eng, futs)
    steps = [s["steps"] for s in seen if s["steps"] is not None]
    assert [s["admitted"] for s in seen if s["admitted"]] == want["ticks"]
    stats = eng.perf_stats()
    empty = dict(calls=0, rows=0, padded_tokens=0, prompts=0, live_tokens=0)
    assert stats["prefill"] == {
        str(b): want["prefill"].get(b, empty) for b in (8, 16, 32)}
    assert [f.result() for f in futs] == one_shot(params, cfg, prompts, 6)
    # what the decode chunks read: a full layer a slot's live tiles, the four
    # window layers every row's ring (one tile here), whatever the context
    tiles = stats["cache_tiles"]
    assert tiles["layers"] == {"full": 1, "window": 4}
    dispatches = tiles["padded"] // 5  # 5 rows x 1 tile of 128
    assert tiles["read_window"] == dispatches * 5 and tiles["read_full"] > 0
    assert tiles["held_window"] == tiles["read_window"]  # K and V per head
    # the routing counts of every drained dispatch, prefill calls (several a
    # tick) and chunks apart
    routed = stats["moe"]
    held = cfg.experts_held[1]
    for phase in ("prefill", "decode"):
        tokens = np.asarray(routed[phase]["tokens"])
        assert tokens.shape == (4, held) and tokens.sum() > 0
        assert (np.asarray(routed[phase]["touched"]) <= tokens.sum(1)).all()
    # ... by the steps each chunk really ran: an answer of 6 tokens is its
    # prefill's, a chunk of 3 and a chunk CUT to the 2 it has left
    assert len(steps) == dispatches and set(steps) <= {1, 2, 3}
    assert routed["decode_steps"] == sum(steps) < 3 * dispatches
    # only real prompt tokens were routed: 4 choices each, over 16 experts
    assert np.asarray(routed["prefill"]["tokens"]).sum(1).max() <= 4 * sum(
        len(p) for p in prompts)
    # ``rows_computed``: the rows the landed prefill calls' grouped matmuls
    # were handed, a sparse layer each (``tokens`` over it is the fill): what
    # ``dispatch_trips``, which the device's loop asks too, makes of the held
    # pairs a call returned and the padded tokens it was wide
    calls = [(np.asarray(c["tokens"]).sum(-1), padded)
             for phase, c, padded in landed if phase == "prefill"]
    assert sorted(padded for _, padded in calls) == sorted(
        max(b, 16) for b, tally in want["prefill"].items()
        for _ in range(tally["calls"]))
    assert routed["prefill"]["rows_computed"] == sum(
        4 * 64 if padded == 16 else int((-(-held // 16) * 16).sum())
        for held, padded in calls)
    assert np.sum(routed["prefill"]["tokens"]) <= routed["prefill"]["rows_computed"]
    # ``trips``: the loop trips of those calls, a layer each (a chip that
    # holds a share: the calls of more than one block, as their held pairs
    # need; one block runs no loop)
    assert routed["prefill"]["trips"] == sum(
        0 if padded == 16 else int((-(-held // 16)).sum())
        for held, padded in calls)
    assert "rows_computed" not in routed["decode"]
    assert "trips" not in routed["decode"]


@pytest.mark.parametrize("n_slots,buckets,rows", [
    # serve-gpt2-xl-chat and serve-k-exaone-236b-ep8-mixed, as their files
    # size the engine
    (16, (64, 128, 256, 512), (4, 2, 1, 1)),
    (32, (128, 256, 512, 1024, 2048, 4096), (2, 1, 1, 1, 1, 1)),
    # a bucket under CALL_TOKENS / n_slots is as wide as the slots
    (3, (8, 32), (3, 3)),
])
def test_a_bucket_is_call_tokens_wide(n_slots, buckets, rows):
    from ray_tpu.serve import llm

    assert llm.CALL_TOKENS == 256
    eng = GenerationEngine(
        em.ExaoneMoeConfig.tiny(dtype=jnp.float32, experts_held=(4, 8)),
        {}, n_slots=n_slots, max_new_tokens=4, decode_chunk_steps=2,
        prefill_buckets=buckets)
    assert eng._rows == dict(zip(buckets, rows))
    assert eng._tick_tokens == n_slots * buckets[-1]
