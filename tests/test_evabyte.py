"""EvaByte (``ray_tpu.models.evabyte``, ``ray_tpu.ops.eva``): an exact,
block-aligned window beside one pooled key and value a chunk of every earlier
window, and the cache that goes with it: the SIXTH kind of
``generate.init_cache``, the only one that rewrites what it holds while a
request is live.  Everything is held to ``benchmark/reference/evabyte_ref.py``
(independent of the program: its own rotary, pooling and ONE materialised
softmax a row) on seeded float32 weights; the tiny model has a window of 32
positions and chunks of 4 (8 summary rows a window), 2 layers, 4 heads of 16.

Tolerances: float32 on both sides, sums in another order (a merge of partial
softmaxes against one softmax, scans against loops): logits agree to ``2e-4``
at a spread of 1, and a served token's logit lies at most ``1e-3`` under the
reference's best (``block_scale=8``: spread ~10).  A wrong mask, a stale
window, a summary at the wrong row or a missed roll-over moves logits by
whole units.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_harness import (
    Slots,
    engine,
    prefill_at,
    run_engine,
    tiny_model,
    worst_gap,
)

from benchmark.reference import evabyte_ref
from ray_tpu.models import evabyte
from ray_tpu.models import generate as gen
from ray_tpu.ops import eva
from ray_tpu.serve import llm

pytestmark = pytest.mark.usefixtures("kept_engine_programs")

W, C = 32, 4      # the tiny preset's window and chunk
ROWS = W // C     # summary rows a window


def _sizes(cfg):
    return {"window_size": cfg.window_size, "chunk_size": cfg.chunk_size,
            "rope_theta": cfg.rope_base, "rms_eps": cfg.rms_eps,
            "vocab_size": cfg.vocab_size}


def _ref(params, cfg, seq, **kw):
    """The reference's logits over ``seq`` (one row)."""
    return evabyte_ref.logits(params, np.asarray([seq]), _sizes(cfg), **kw)[0]


def _tokens(n, seed=0, vocab=320):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def test_parameters_are_the_published_counts():
    """202,391,552 a layer, 1,310,720 in the embedding, 10,485,760 in the
    eight-way head (the configuration file's arithmetic), counted on shapes."""
    cfg = evabyte.Config.evabyte_6_5b(n_layers=8)
    shapes = jax.eval_shape(lambda: evabyte.init(cfg, jax.random.PRNGKey(0)))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert count(shapes["blocks"]) == 8 * 202_391_552
    assert count(shapes["tok_emb"]) == 1_310_720
    assert count(shapes["head"]) == 10_485_760
    assert count(shapes) == 1_630_932_992
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(shapes))


def test_whole_prefill_over_three_and_a_half_windows_all_eight_heads():
    """110 positions: three full windows and 14 of the fourth (a ragged last
    chunk of 2).  The family's forward against the reference for ALL EIGHT
    prediction heads at every position, and the served prefill's last logits
    against head 0 (the byte the served path samples)."""
    cfg, params = tiny_model("evabyte")
    seq = _tokens(110)
    want = _ref(params, cfg, seq, all_heads=True)           # [110, 8, 320]
    padded = np.zeros((1, 128), np.int32)
    padded[0, :110] = seq
    got = np.asarray(evabyte.apply(params, jnp.asarray(padded), cfg,
                                   all_heads=True))[0, :110]
    assert want.shape == got.shape == (110, 8, 320)
    assert np.abs(got - want).max() < 2e-4
    # head p at position i is a different function of the stream for each p
    assert np.abs(want[:, 0] - want[:, 1]).max() > 0.5
    last, cache, _ = prefill_at(
        params, cfg, jnp.asarray(padded), jnp.asarray([110]),
        gen.init_cache(cfg, 2, 160), jnp.asarray([0]))
    assert np.abs(np.asarray(last)[0] - want[-1, 0]).max() < 2e-4
    assert int(cache["pos"][0]) == 110
    # the cache: 14 exact places of window 3, and 3 x 8 summary rows
    assert cache["k"].shape == (2, 2, 4, 16, gen.window_positions(W))
    assert cache["ks"].shape == (2, 2, 4, 16, 5 * ROWS)


def test_pooling_is_the_reference_formula():
    """``pool_chunks`` against the formula written out for one chunk."""
    rng = np.random.default_rng(0)
    k, v = (jnp.asarray(rng.normal(size=(3, 16, 8)), jnp.float32) for _ in "kv")
    phi, mu = (jnp.asarray(rng.normal(size=(3, 16)), jnp.float32) for _ in "pm")
    ks, vs = eva.pool_chunks(k, v, phi, mu, chunk=4, scale=0.25)
    for head in range(3):
        for n in range(2):
            kc, vc = (np.asarray(t[head, :, 4 * n:4 * n + 4]).T for t in (k, v))
            p = jax.nn.softmax(0.25 * kc @ np.asarray(phi[head]))
            assert np.allclose(ks[head, :, n], p @ kc + np.asarray(mu[head]), atol=1e-5)
            assert np.allclose(vs[head, :, n], p @ vc, atol=1e-5)


def _whole(params, cfg, prompt, width, cache_len=224):
    row = np.zeros((1, width), np.int32)
    row[0, :len(prompt)] = prompt
    return prefill_at(params, cfg, jnp.asarray(row), jnp.asarray([len(prompt)]),
                      gen.init_cache(cfg, 3, cache_len), jnp.asarray([1]))


@pytest.mark.parametrize("part", [32, 64])
@pytest.mark.parametrize("n", [70, 96, 117])
def test_parts_leave_what_the_whole_call_leaves(part, n):
    """A prompt in parts of one and of two windows: the last logits, ``pos``,
    the exact window's live places and every summary row a query may read are
    the whole call's; a prompt that ends ON a window's end (96) leaves the
    window dead and its last window pooled."""
    cfg, params = tiny_model("evabyte")
    prompt = _tokens(n, seed=n)
    want_logits, want, _ = _whole(params, cfg, prompt, 128)
    cache = gen.init_cache(cfg, 3, 224)
    for at in range(0, n, part):
        own = prompt[at:at + part]
        row = np.zeros((1, part), np.int32)
        row[0, :len(own)] = own
        logits, cache, _ = prefill_at(
            params, cfg, jnp.asarray(row), jnp.asarray([len(own)]), cache,
            jnp.asarray([1]), offsets=jnp.asarray([at]), bound=128)
    assert np.abs(np.asarray(logits) - np.asarray(want_logits)).max() < 2e-4
    assert int(cache["pos"][1]) == int(want["pos"][1]) == n
    live, rows = n % W, n // W * ROWS
    for name, upto in (("k", live), ("v", live), ("ks", rows), ("vs", rows)):
        a, b = (np.asarray(c[name][:, 1, :, :, :upto]) for c in (cache, want))
        assert a.shape == b.shape and np.allclose(a, b, atol=2e-5), name
    assert np.abs(np.asarray(logits)[0] - _ref(params, cfg, prompt)[-1]).max() < 2e-4


def _decode_as_the_engine_does(slots, steps, total, cut=True):
    """Chunks of ``steps`` until every slot has ``total`` tokens, each ending
    where the nearest active slot's window does: the cut program with that
    bound, or (``cut=False``) only ever WHOLE chunks, of whatever length
    reaches the window's end exactly (``steps`` then has to divide the way)."""
    while min(len(slots.out[s]) for s in slots.out) < total:
        pos = np.asarray(slots.cache["pos"])
        ends = min(W - int(pos[s]) % W for s in np.flatnonzero(slots.active))
        if cut:
            slots.decode(steps, n=min(steps, ends) if ends < steps else None)
        else:
            assert ends >= steps or ends == 0, (ends, steps)
            slots.decode(steps)


@pytest.mark.parametrize("cut", [True, False], ids=["cut", "whole"])
def test_decode_through_window_ends_by_prefill_by_decode_and_by_both(cut):
    """Three slots in one batch, at different places in their windows: slot 0
    whose first window the PREFILL filled and pooled (prompt 45), slot 1 whose
    window prefill and decode fill together (prompt 20: crosses 32, then
    fills 32..64 by DECODE alone), slot 3 from a prompt that ended on a
    window's end (64).  Every served token around each end (positions ``W w -
    1``, ``W w``, ``W w + 1`` and all between) is the reference's greedy one,
    its logit within 1e-3 of the reference's best.  ``whole``: the prompts are
    placed so that whole chunks of 4 end on every window's end."""
    slots = Slots("evabyte", 160)
    prompts = {0: _tokens(45 if cut else 44, 1), 1: _tokens(20, 2),
               3: _tokens(64, 3)}
    for slot, prompt in prompts.items():
        slots.admit(slot, prompt, 64)
    _decode_as_the_engine_does(slots, 5 if cut else 4, 49, cut=cut)
    ref = lambda seq: _ref(slots.params, slots.cfg, seq)  # noqa: E731
    served = [slots.out[s][:49] for s in prompts]
    assert worst_gap(ref, list(prompts.values()), served) < 1e-3
    for slot, prompt in prompts.items():
        seq = prompt + slots.out[slot][:49]
        logits = ref(seq)
        ends = [e for e in range(W, len(seq), W) if e > len(prompt)]
        assert ends, slot
        for e in ends:
            for at in (e - 1, e, e + 1):  # the token chosen AT position ``at``
                assert seq[at + 1] == int(logits[at].argmax()), (slot, at)
    # slot 1 crossed 32 and 64; slot 0 crossed 64; slot 3 crossed 96
    pos = [int(p) for p in slots.cache["pos"]]
    assert pos[1] >= 20 + 48 and pos[3] >= 64 + 48


def test_a_window_pooled_at_roll_over_is_the_prefills():
    """The summaries a decode's roll-over writes are the rows a prefill of the
    same tokens pools: slot 0 decodes from a prompt of 20 through positions 32
    and 64; a fresh cache prefilled with those 64 tokens holds the same 16
    rows."""
    slots = Slots("evabyte", 160)
    prompt = _tokens(20, 5)
    slots.admit(0, prompt, 32)
    _decode_as_the_engine_does(slots, 6, 46)
    assert int(slots.cache["pos"][0]) >= 64
    seq = (prompt + slots.out[0])[:64]
    _, want, _ = _whole(slots.params, slots.cfg, seq, 64)
    for name in (row.name for row in gen.cache_layout(slots.cfg)
                 if row.arrangement == gen.SUMMARY):
        got = np.asarray(slots.cache[name][:, 0, :, :, :2 * ROWS])
        # (values of size ~20 under ``block_scale=8``: float32's 1e-5 of them)
        assert np.allclose(got, np.asarray(want[name][:, 1, :, :, :2 * ROWS]),
                           rtol=1e-4, atol=1e-3), name


def test_a_chunk_may_not_straddle_and_the_slack_bounds_it():
    """The window's slab holds the window and its slack; a chunk longer than
    the slack is refused where the sizes are static."""
    slots = Slots("evabyte", 96)
    slots.admit(0, [1, 2, 3], 8)
    with pytest.raises(AssertionError):
        gen.decode_chunk(slots.params, slots.cfg, slots.cache, slots.tok,
                         jnp.asarray(slots.active), slots.key, steps=W + 1)


# -- as a chip runs it: the ragged kernel over the window and the summaries ----

def test_lowered_for_tpu_two_ragged_reads_and_the_flush(lowered_for_tpu):
    """Window 128, chunks of 16 (8 rows a window), cache of whole tiles: the
    decode program a chip runs (the ragged kernel TWICE a layer, over the
    window's two tiles and the summaries' one, and the flush kernel at the
    slot's place in its window), 8 steps a chunk, two slots that cross
    position 128 at different steps.  Tokens equal the reference's greedy."""
    slots = Slots("evabyte", 384, n=3, window_size=128, chunk_size=16)
    assert slots.cache["k"].shape[-1] == 256 and slots.cache["ks"].shape[-1] == 128
    prompts = {0: _tokens(122, 7), 1: _tokens(117, 8)}
    for slot, prompt in prompts.items():
        slots.admit(slot, prompt, 128)
    for n in (6, 5, 8):  # 128 for slot 0; 128 for slot 1; one whole chunk
        slots.decode(8, n=None if n == 8 else n)
    ref = lambda seq: _ref(slots.params, slots.cfg, seq)  # noqa: E731
    served = [slots.out[s] for s in prompts]
    assert [len(s) for s in served] == [20, 20]
    assert worst_gap(ref, list(prompts.values()), served) < 1e-3
    assert [int(p) for p in slots.cache["pos"]] == [141, 136, 128]


# -- the engine ----------------------------------------------------------------

def _served_as_reference(cfg, params, prompts, answers):
    ref = lambda seq: _ref(params, cfg, seq)  # noqa: E731
    assert worst_gap(ref, prompts, answers) < 1e-3


def test_engine_requests_that_roll_over_mid_answer_and_the_counters():
    """Four requests through the engine (3 slots, chunks of 8, whole prompts
    of up to four windows): every answer is the reference's greedy one, the
    chunks were cut at window ends, and ``perf_stats()["eva"]`` adds up: a
    roll-over a window end a request decoded through, 8 chunks pooled each,
    the prefills' windows, and reads that cover what the queries may
    attend."""
    eng, cfg, params = engine(
        "evabyte", seed=4, n_slots=3, max_new_tokens=40,
        decode_chunk_steps=8, prefill_buckets=(32, 64, 128))
    sizes = ((27, 40), (70, 30), (100, 33), (5, 20))
    prompts = [_tokens(n, 10 + i) for i, (n, _) in enumerate(sizes)]
    futs = [eng.submit(p, m) for p, (_, m) in zip(prompts, sizes)]
    seen = run_engine(eng, futs)
    eng.stop()
    answers = [f.result(1) for f in futs]
    assert [len(a) for a in answers] == [m for _, m in sizes]
    _served_as_reference(cfg, params, prompts, answers)
    stats = eng.perf_stats()
    counted = stats["eva"]
    # window ends decoded THROUGH (the last token is never fed back): a
    # request stands at prompt + answer - 1 when it ends
    crossed = sum((n + m - 1) // W - n // W for n, m in sizes)
    assert crossed == 4
    assert counted["rollovers"] == crossed
    assert counted["chunks_pooled"] == crossed * ROWS
    assert counted["prefill_windows_pooled"] == sum(n // W for n, _ in sizes)
    assert counted["window_cuts"] >= 2
    assert any(t["steps"] not in (None, 8) for t in seen)
    assert counted["read_positions"] >= counted["attendable_positions"] > 0
    assert counted["steps"] == sum(t["steps"] or 0 for t in seen)
    tiles = stats["cache_tiles"]
    assert tiles["read_full"] == counted["window_tiles"] + counted["summary_tiles"]
    assert tiles["eva_rollovers"] == counted["rollovers"]
    # no chunk straddled a window's end: every dispatch ended at or before it
    assert counted["window"] == W and counted["chunk"] == C


def test_engine_parts_are_whole_windows(monkeypatch):
    """Prompts longer than one PART (two windows here) go in parts at offsets
    that are multiples of the window; the answers are the reference's, and
    those of an engine that never splits.  A part that is no whole number of
    windows is refused where the engine is built."""
    sizes = ((150, 12), (9, 30), (200, 40))
    prompts = [_tokens(n, 20 + i) for i, (n, _) in enumerate(sizes)]
    answers = {}
    for part in (64, 10 ** 6):
        monkeypatch.setattr(llm, "PREFILL_PART_TOKENS", part)
        eng, cfg, params = engine(
            "evabyte", seed=4, n_slots=3, max_new_tokens=40,
            decode_chunk_steps=8, prefill_buckets=(32, 64, 256))
        futs = [eng.submit(p, m) for p, (_, m) in zip(prompts, sizes)]
        run_engine(eng, futs)
        eng.stop()
        answers[part] = [f.result(1) for f in futs]
        if part == 64:
            tally = eng.perf_stats()["prefill"]["parts"]
            assert tally["prompts"] == 2 and tally["calls"] == 3 + 4
            assert eng.perf_stats()["eva"]["prefill_windows_pooled"] == 4 + 6
    assert answers[64] == answers[10 ** 6]
    _served_as_reference(cfg, params, prompts, answers[64])
    monkeypatch.setattr(llm, "PREFILL_PART_TOKENS", 48)
    with pytest.raises(AssertionError, match="whole windows"):
        engine("evabyte", seed=4, n_slots=2, prefill_buckets=(32, 64))


def test_one_shot_generate_cuts_itself_at_window_ends():
    """``generate.generate`` for a family that compacts runs its answer as cut
    chunks on the device: 40 tokens from prompts of 40 and 30 cross positions
    64, and 32 and 64."""
    from family_harness import one_shot

    cfg, params = tiny_model("evabyte", seed=4, block_scale=8)
    prompts = [_tokens(40, 30), _tokens(30, 31)]
    answers = one_shot(params, cfg, prompts, 40, pad_to=64)
    _served_as_reference(cfg, params, prompts, answers)
