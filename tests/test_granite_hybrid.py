"""The Granite-4.0-H family (Mamba-2 layers whose per-request state is a
position-free kind of cache, a full-attention layer without position encoding
among them, softmax-over-top-k experts of which this chip holds a block beside
a shared MLP, a tied head) against its plain reference
(``benchmark/reference/granite_hybrid_ref.py``), at a small size on the CPU.

Tolerances.  With ``dtype=float32`` the program and the reference do the same
arithmetic in another order (the chunked quadratic form of a prompt and the
in-place step of a decode chunk against the reference's one recurrence a
position, grouped matmuls against a masked loop, one softmax merged from the
cache and a chunk's columns).  The tied head over rows of std 2 ** -10 makes
the tiny preset's logits small (std ``LOGIT_STD`` ~ 0.003), so every limit is
stated in units of it: ``F32_TOL = 2e-4`` of a logit's std, as the other
families' tests have it, far under what any departure makes: a state rounded
through bfloat16 (> 3e-3 of it), a conv tail one input off, a padded position
that moved the state, a scale of ``head_dim ** -0.5`` (each > 1e-2 of it).
"""

import dataclasses
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_harness import (
    decode_chunk,
    engine,
    one_shot,
    padded,
    prefill,
    run_engine,
    serve,
    served_layer,
    shares_add_up,
    tiny_model,
    worst_gap,
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import granite_hybrid_ref as ref  # noqa: E402
from ray_tpu.models import generate as gen  # noqa: E402
from ray_tpu.models import granite_hybrid as gh  # noqa: E402
from ray_tpu.ops import moe, ssm  # noqa: E402
from ray_tpu.serve.llm import make_config  # noqa: E402

pytestmark = pytest.mark.usefixtures("kept_engine_programs")
LOGIT_STD = 2.8e-3
F32_TOL = 2e-4 * LOGIT_STD


def sizes_of(cfg):
    return {"layer_types": list(cfg.layer_types), "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "mamba_heads": cfg.mamba_heads,
            "mamba_state": cfg.mamba_state, "top_k": cfg.experts_per_token,
            "first_expert": cfg.experts_held[0],
            "embedding_multiplier": cfg.embedding_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "rms_eps": cfg.rms_eps}


@pytest.fixture(scope="module")
def model():
    # 5 layers, attention at index 2 (one period's pattern), 8 Mamba heads of
    # 8 with a state of 16 in chunks of 8, 16 experts of which 4..11 are
    # held, top-4; every multiplier is something other than 1
    return tiny_model("granite_hybrid")


def ref_logits(model, seq):
    """The reference's logits for ``seq``, computed over the sequence padded
    to one width (every layer is causal: what follows a position does not
    reach it), so that the reference compiles once."""
    cfg, params = model
    return ref.logits(
        params, jnp.asarray([padded(seq)]), sizes_of(cfg))[0][:len(seq)]


def test_config_is_the_published_one_and_says_what_it_caches():
    cfg = make_config("granite_hybrid", "4.0-h-small", experts_held=[0, 9])
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        4096, 32, 8, 128)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state,
            cfg.mamba_conv, cfg.mamba_chunk) == (128, 64, 128, 4, 256)
    assert cfg.d_inner == 8192 == 2 * cfg.d_model and cfg.conv_width == 8448
    assert (cfg.n_experts, cfg.experts_per_token, cfg.d_expert, cfg.d_shared) == (
        72, 10, 768, 1536)
    assert (cfg.embedding_multiplier, cfg.logits_scaling,
            cfg.residual_multiplier, cfg.attention_scale) == (
        12.0, 16.0, 0.22, 1 / 128)
    assert [l for l, t in enumerate(cfg.layer_types) if t == "attention"] == [
        5, 15, 25, 35]
    hash(cfg)  # jit closes over it
    assert gen.family_of(cfg) is gh and gen.cached_tensors(cfg) == ("k", "v")
    # the cell's share: two periods, 18 Mamba layers and 2 attention layers
    cut = dataclasses.replace(cfg, n_layers=20, vocab_size=12544,
                              layer_types=cfg.layer_types[:20])
    assert cut.layer_runs == (("mamba", 0, 5, 0), ("attention", 5, 1, 0),
                              ("mamba", 6, 9, 5), ("attention", 15, 1, 1),
                              ("mamba", 16, 4, 14))
    assert gen.layer_windows(cut).count(gen.RECURRENT) == 18
    cache = jax.eval_shape(lambda: gen.init_cache(cut, 49, 2688))
    assert set(cache) == {"k", "v", "pos", "ssm", "conv"}
    # two heads of 64 side by side on a tile's 128 lanes, the state size on
    # its sublanes: 64 tiles of [128, 128] a row a layer
    assert cache["ssm"].shape == (18, 49, 64, 128, 128)
    assert cache["ssm"].dtype == jnp.float32  # the state is NOT bfloat16
    assert cache["conv"].shape == (18, 3, 49, 8448)
    assert cache["k"].shape == (2, 49, 8, 128, 2688)
    # a slot: 18 x (4,194,304 + 50,688) of state, 2 x 4,096 B x 2,688 of K/V
    per_slot = lambda a, axis: a.size * a.dtype.itemsize // a.shape[axis]  # noqa: E731
    assert per_slot(cache["ssm"], 1) + per_slot(cache["conv"], 2) == 18 * 4_244_992
    assert per_slot(cache["k"], 1) + per_slot(cache["v"], 1) == 2 * 4096 * 2688
    shapes = jax.eval_shape(lambda: gh.init(cut, jax.random.PRNGKey(0)))
    assert gh.num_params(shapes) == 4_058_678_528  # the issue's 4,058.7 M
    with pytest.raises(AssertionError):
        gh.GraniteHybridConfig.tiny(experts_held=(12, 8))  # past the router


@pytest.mark.parametrize("layer", [0, 2], ids=["mamba", "attention"])
def test_one_block_of_each_kind_against_the_reference(model, layer):
    cfg, params = model
    # 64 positions: the width every reference pass of this file runs at, so
    # the reference's layer compiles once a kind for the whole file
    x = jax.random.normal(jax.random.PRNGKey(layer), (1, 64, cfg.d_model))
    p = gh.layer_params(params, cfg, layer)
    got, routed, _ = jax.jit(
        lambda x, p: gh.block(x, p, cfg, kind=cfg.layer_types[layer]))(
            x, served_layer(p))
    with jax.default_matmul_precision("highest"):
        want = ref._layer(x, p, kind=cfg.layer_types[layer],
                          **ref.layer_statics(sizes_of(cfg)))
    # a layer's output is of size ~1, not a logit's
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    assert 0 < int(routed["tokens"].sum()) <= 64 * 4 and int(routed["rows"]) == 64


def test_forward_against_the_reference(model):
    """(a) ``apply`` (the chunked scan over 43 positions in chunks of 8: five
    whole chunks and three positions) is the reference's forward."""
    cfg, params = model
    seq = list(np.random.RandomState(1).randint(0, cfg.vocab_size, 43))
    want = ref_logits(model, seq)
    assert abs(want.std() / LOGIT_STD - 1) < 0.1
    forward = jax.jit(gh.apply, static_argnums=2)
    got = np.asarray(forward(params, jnp.asarray([seq]), cfg)[0])
    assert np.abs(got - want).max() < F32_TOL
    # the tolerance tells the attention scale apart from head_dim ** -0.5
    other = dataclasses.replace(cfg, attention_multiplier=cfg.head_dim ** -0.5)
    wrong = np.asarray(forward(params, jnp.asarray([seq]), other)[0])
    assert np.abs(wrong - want).max() > 50 * F32_TOL


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_scan_against_the_recurrence(chunk):
    """(g) ``ssd_scan`` at a length that is no multiple of the chunk (37:
    chunks of 8 and 16 leave a remainder, one of 64 holds it all) against the
    recurrence a position, rows right-padded by ``dt = 0``: ``y`` at the real
    positions and the state after each row's LAST REAL position; and the
    convolution's tail is the last real inputs."""
    B, T, H, P, N = 2, 37, 4, 8, 16
    keys = jax.random.split(jax.random.PRNGKey(chunk), 6)
    x = jax.random.normal(keys[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (B, T, H)))
    a = -jnp.exp(jax.random.normal(keys[2], (H,)))
    b, c = (jax.random.normal(k, (B, T, N)) for k in keys[3:5])
    lengths = jnp.asarray([37, 20])
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    y, state = ssm.ssd_scan(x, jnp.where(valid[..., None], dt, 0.0), a, b, c,
                            chunk=chunk)

    for row, n in enumerate([37, 20]):
        held = np.zeros((H, P, N))
        for t in range(n):  # the recurrence itself, in float64
            step = np.asarray(dt[row, t], np.float64)
            held = (np.exp(step * np.asarray(a))[:, None, None] * held
                    + (step[:, None] * np.asarray(x[row, t]))[:, :, None]
                    * np.asarray(b[row, t])[None, None, :])
            want = (held * np.asarray(c[row, t])[None, None, :]).sum(-1)
            assert np.abs(np.asarray(y[row, t]) - want).max() < 1e-4
        assert np.abs(np.asarray(state[row]) - held).max() < 1e-4
    raw = jax.random.normal(keys[5], (B, T, 5))
    tail = np.asarray(ssm.conv_tail(raw, jnp.asarray([37, 2]), 3))
    assert (tail[0] == np.asarray(raw[0, 34:37])).all()
    assert (tail[1, 0] == 0).all() and (tail[1, 1:] == np.asarray(raw[1, :2])).all()


# every cache test's shapes: 4 slots of 64 positions, prefill calls of 3 rows
# x 48, chunks of 5 steps (so the harness's programs are built once for them)
SLOTS, POSITIONS, STEPS = 4, 64, 5
CALL = {"bucket": 48, "rows": 3}


def _cut_walk(model, prompts, *, spoil=None):
    """Three prompts of different lengths right-padded into ONE prefill call,
    then a whole chunk of 5 steps, a chunk CUT after 3, and a whole chunk,
    one slot idle throughout -> the served tokens of each prompt."""
    cfg, params = model
    slots = [2, 0, 1]
    served, cache, _ = serve(
        cfg, params, prompts, (None, 3, None), steps=STEPS, slots=slots,
        n_slots=SLOTS, cache_len=POSITIONS, spoil=spoil, **CALL)
    # a cut of 3 advanced the positions (and the state) 3 steps, not 5
    assert [int(cache["pos"][s]) for s in slots] == [len(p) + 13 for p in prompts]
    # what the steps left in the cache against ONE prefill of everything a
    # row has consumed (its prompt and all but the last served token)
    _, whole, _, _ = prefill(
        cfg, params, [p + out[:-1] for p, out in zip(prompts, served)], slots,
        gen.init_cache(cfg, SLOTS, POSITIONS), **CALL)
    drift = max(
        float(jnp.abs(cache[name] - whole[name]).max() / jnp.abs(whole[name]).max())
        for name in ("ssm", "conv"))
    return served, drift


@pytest.mark.parametrize("broken", [None, "bf16_state", "stale_tail"])
def test_prefill_then_decode_through_the_cache_against_the_reference(
        model, broken):
    """(b) Prefill of three right-padded rows in one call, then whole chunks
    and a cut chunk through the state: each served token's LOGIT is the
    reference's best at its position in one full forward over prompt + served
    tokens, within float32 rounding, and the state and tail the 13 steps left
    are those of one prefill (the chunked scan) over the same tokens, to 1e-5
    of the largest value (the same sums in another order).  The comparison
    FAILS, as it must, with the state rounded through bfloat16 between chunks
    (2 ** -9 of a value) and with the convolution's tail one input stale."""
    cfg, params = model
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, cfg.vocab_size, n)) for n in (29, 9, 2)]
    spoil = None
    if broken == "bf16_state":
        spoil = lambda c: {**c, "ssm": c["ssm"].astype(jnp.bfloat16).astype(  # noqa: E731
            jnp.float32)}
    elif broken == "stale_tail":
        spoil = lambda c: {**c, "conv": jnp.roll(c["conv"], 1, axis=1)}  # noqa: E731
    served, drift = _cut_walk(model, prompts, spoil=spoil)
    if broken is None:
        gap = worst_gap(partial(ref_logits, model), prompts, served)
        assert gap < F32_TOL and drift < 1e-5
    else:
        assert drift > 1e-4


def test_a_row_stopped_at_eos_is_frozen_and_an_idle_row_untouched(model):
    """(b, d) A row that emits ``eos_id`` at step 2 of a chunk of 5 keeps the
    state of a chunk cut after that step (its later steps moved nothing) and
    repeats the token; a slot that sits the chunk out has its state and tail
    bit for bit as they were, whatever they were."""
    cfg, params = model
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(0, cfg.vocab_size, n)) for n in (11, 6)]
    _, cache, tokens, active = prefill(
        cfg, params, prompts, [0, 2], gen.init_cache(cfg, SLOTS, POSITIONS),
        **CALL)
    # the idle slot holds something, so that "unchanged" is not "zero"
    cache["ssm"] = cache["ssm"].at[:, 1].set(0.5)
    cache["conv"] = cache["conv"].at[:, :, 1].set(0.25)
    run = lambda **kw: decode_chunk(  # noqa: E731
        params, cfg, cache, tokens, active, steps=STEPS, **kw)[:3]
    free, after, _ = run()
    eos = int(free[2, 2])  # what slot 2 emits at step 2
    assert eos not in [int(t) for t in free[2, :2]]
    stopped, froze, still = run(eos_id=eos)
    cut, at_cut, _ = run(n=3)
    assert [int(t) for t in stopped[2]] == [int(t) for t in free[2, :3]] + [eos] * 2
    assert int(froze["pos"][2]) == len(prompts[1]) + 3 == int(at_cut["pos"][2])
    assert not bool(still[2]) and bool(still[0])
    for name, axis in (("ssm", 1), ("conv", 2)):
        take = lambda c, slot: np.asarray(jnp.take(c[name], slot, axis=axis))  # noqa: E731
        assert (take(froze, 2) == take(at_cut, 2)).all(), name
        assert (take(froze, 2) != take(after, 2)).any(), name
        assert (take(froze, 0) == take(after, 0)).all(), name  # ran all five
        for c in (after, froze, at_cut):
            assert (take(c, 1) == take(cache, 1)).all(), name


def test_a_reused_slot_gives_what_a_fresh_cache_gives(model):
    """(c) A long prompt is served in slot 0, then a shorter one is prefilled
    into the same slot: its state, tail and tokens are those of a cache that
    never held the first (prefill writes a slot's state whole)."""
    cfg, params = model
    rng = np.random.RandomState(9)
    long, short = (list(rng.randint(0, cfg.vocab_size, n)) for n in (30, 4))

    def serve(prompt, cache):
        _, cache, tokens, active = prefill(
            cfg, params, [prompt], [0], cache, **CALL)
        emitted, cache, *_ = decode_chunk(
            params, cfg, cache, tokens, active, steps=STEPS)
        return [int(t) for t in emitted[0]], cache

    _, used = serve(long, gen.init_cache(cfg, SLOTS, POSITIONS))
    again, reused = serve(short, used)
    fresh_tokens, fresh = serve(short, gen.init_cache(cfg, SLOTS, POSITIONS))
    assert again == fresh_tokens
    assert (np.asarray(reused["ssm"][:, 0]) == np.asarray(fresh["ssm"][:, 0])).all()
    assert (np.asarray(reused["conv"][:, :, 0]) == np.asarray(fresh["conv"][:, :, 0])).all()
    assert int(reused["pos"][0]) == 4 + STEPS


ACTIVE = {
    "some": [True, False, True, True, False],
    "none": [False, False, False],
    "all": [True, True],
    "last": [False, False, False, True],
}


@pytest.mark.parametrize("active", list(ACTIVE))
def test_state_update_kernel_matches_the_masked_form(active):
    """(e) ``state_update_kernel`` in the TPU interpreter, walking the plan's
    slots, against the masked ``jax.numpy`` form over every row, and both
    against the recurrence written out a head in the layout ``[H, P, N]``:
    the listed rows' new state and ``y`` (the same products summed in another
    order: 1e-5 of values of size ~10), every other row, and every other
    LAYER, bit for bit as it was."""
    act = jnp.asarray(ACTIVE[active])
    L, B, H, P, N, layer = 3, len(ACTIVE[active]), 32, 64, 128, 1
    g = ssm.heads_per_tile(H, P)
    assert g == 2 and ssm.heads_per_tile(8, 8) == 8  # all of a tiny model's
    keys = jax.random.split(jax.random.PRNGKey(B), 5)
    heads = jax.random.normal(keys[0], (L, B, H, P, N))
    state = jnp.stack([ssm.pack_state(h, g) for h in heads])
    assert state.shape == (L, B, H // 2, N, 2 * P)
    assert (np.asarray(ssm.unpack_state(state[0], g)) == np.asarray(heads[0])).all()
    decay = jax.random.uniform(keys[1], (B, H), minval=0.5, maxval=1.0)
    dtx = jax.random.normal(keys[2], (B, H, P))
    b, c = (jax.random.normal(k, (B, N)) for k in keys[3:])
    plan = ssm.state_update_plan(act)
    assert int(plan[0]) == sum(ACTIVE[active])
    assert sorted(int(s) for s in plan[1:1 + int(plan[0])]) == [
        i for i, a in enumerate(ACTIVE[active]) if a]
    assert ssm.kernel_shapes(state) and not ssm.kernel_shapes(state[..., :64])
    want, y_want = ssm.state_update_masked(state, layer, decay, dtx, b, c, act)
    got, y_got = ssm.state_update_kernel(
        state, jnp.int32(layer), decay, dtx, b, c, plan, interpret=True)
    rows = np.asarray(act)
    written = (heads[layer] * decay[:, :, None, None]
               + dtx[..., None] * b[:, None, None, :])
    np.testing.assert_allclose(ssm.unpack_state(want[layer], g)[rows],
                               written[rows], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        y_want[rows], (written * c[:, None, None, :]).sum(-1)[rows],
        rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got[layer][rows], want[layer][rows],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y_got[rows], y_want[rows], rtol=1e-5, atol=1e-4)
    assert (np.asarray(y_got)[~rows] == 0).all()
    assert (np.asarray(got[layer])[~rows] == np.asarray(state[layer])[~rows]).all()
    for other in (0, 2):
        assert (np.asarray(got[other]) == np.asarray(state[other])).all()


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """(f) 16 experts over 8 chips, 2 a chip: the routed parts the shares
    give, the shared MLP counted once, are the uncut layer (the reference's
    sums, given every expert)."""
    whole = gh.GraniteHybridConfig.tiny(dtype=jnp.float32)
    p = gh.init_layer(whole, jax.random.PRNGKey(3), 0)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 9, whole.d_model))
    experts, gates = moe.route_softmax_top_k(
        h.reshape(18, -1), p["router"], whole.experts_per_token)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    chosen, sel = jax.lax.top_k(h @ p["router"], whole.experts_per_token)
    worst, counted = shares_add_up(
        p, h, 8, (experts, gates), (sel, jax.nn.softmax(chosen, -1)),
        ref._swiglu, whole.n_experts)
    assert counted == 18 * whole.experts_per_token  # every choice, once
    assert worst < 2e-5


def test_engine_serves_a_mixed_batch_as_generate_does(model):
    """Prompts of three buckets through ``GenerationEngine`` (a never-started
    engine: the test is the engine thread), answers of different lengths so
    that chunks are cut: every answer is the one-shot path's, and the state
    counters count the rows the steps had to move against those the CPU's
    masked update touched (every row)."""
    cfg, params = model
    eng, _, _ = engine(
        "granite_hybrid", n_slots=3, max_new_tokens=7, decode_chunk_steps=3,
        prefill_buckets=(8, 32))
    assert set(eng.cache) == {"k", "v", "pos", "ssm", "conv"}
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(0, cfg.vocab_size, n)) for n in (5, 20, 12, 3)]
    asked = [6, 7, 3, 5]
    futs = [eng.submit(p, n) for p, n in zip(prompts, asked)]
    run_engine(eng, futs)
    assert [f.result(timeout=1) for f in futs] == one_shot(
        params, cfg, prompts, asked)
    stats = eng.perf_stats()
    assert stats["cache_tiles"]["layers"] == {"full": 1, "window": 0, "state": 4}
    state = stats["state"]
    # a row of a layer: 8 x 8 x 16 float32 of state (one tile [16, 64]), 3 x
    # (64 + 32) of inputs
    assert state["layers"] == 4 and state["row_bytes"] == 4096 + 3 * 96 * 4
    assert state["dispatches"] > 0 and state["steps"] < 3 * state["dispatches"]
    # every generated token but a request's first took one live row-step
    assert state["rows_live"] == sum(asked) - len(asked)
    assert state["rows_updated"] == 4 * state["steps"]  # masked: all 3 + 1 rows
    routed = stats["moe"]
    assert np.asarray(routed["decode"]["tokens"]).shape == (5, 8)
    assert routed["decode"]["rows"] == [state["rows_live"]] * 5
