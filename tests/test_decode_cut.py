"""The cut decode chunk: ``decode_chunk(n=...)`` runs the chunk's step under a
runtime bound, and the serve engine asks for it whenever a live request has
fewer tokens left than the chunk is long, so that no answer waits for steps
nobody needs (``GenerationEngine.step``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_harness import (
    LONG,
    LONG_CHUNK,
    TINY,
    Slots,
    engine,
    one_shot,
    run_engine,
    served,
    tiny_model,
)

from ray_tpu.models import generate as gen

pytestmark = pytest.mark.usefixtures("kept_engine_programs")


STEPS = 8


@pytest.mark.parametrize("family", ["gpt2", "exaone_moe", "kimi_k2"])
@pytest.mark.parametrize("n", [1, 3, 7])
def test_cut_chunk_then_the_rest_equals_one_whole_chunk(family, n, positions):
    """A chunk of 8 cut to ``n`` steps, then the ``8 - n`` that complete it,
    against one whole chunk: the same tokens, the same ``pos``, the same
    cache below ``pos`` (the columns a cut chunk flushes beyond its ``n`` lie
    past ``pos`` and the next flush overwrites them), and the same next
    chunk, which attends all of it.  One slot's columns cross position 128
    (cut at 3 its real columns end short of the boundary, so the flush lists
    one tile; the rest of the chunk then crosses), one stands where its
    ring of 16 wraps, one is short, one idle.  (8 steps and not the engine's
    16: a step in the TPU interpreter is a third of a second, and the cut is
    the same loop whatever the chunk's length.)"""
    prompts = {0: [3, 17, 5], 1: [1 + i % 50 for i in range(121)],
               3: [2 + i % 40 for i in range(60)]}

    def run(cuts):
        eng = Slots(family, positions(LONG))
        for slot, prompt in prompts.items():
            eng.admit(slot, prompt, 128)
        for cut in cuts:
            eng.decode(STEPS, n=cut)
        return eng

    whole, cut = run([None]), run([n, STEPS - n])
    assert cut.out == whole.out
    pos = [int(p) for p in whole.cache["pos"]]
    assert [int(p) for p in cut.cache["pos"]] == pos == [
        3 + STEPS, 121 + STEPS, 0, 60 + STEPS, 128]
    for name in gen.cached_tensors(whole.cfg):
        for slot in prompts:
            np.testing.assert_allclose(
                cut.cache[name][:, slot, ..., :pos[slot]],
                whole.cache[name][:, slot, ..., :pos[slot]], rtol=1e-4, atol=1e-5)
    for eng in (whole, cut):
        eng.decode(STEPS)
    assert cut.out == whole.out
    cut.assert_greedy(dict.fromkeys(prompts, 1 + 2 * STEPS))


@pytest.mark.parametrize("family", ["gpt2", "exaone_moe", "kimi_k2"])
def test_whole_chunk_stays_a_scan_and_the_cut_is_one_loop(family):
    """The whole chunk is what it was before there was a cut: ONE ``scan`` of
    ``steps`` at the top of its program and no loop with a runtime bound
    (the benchmark reads its device time as that of ``steps`` steps).  The
    cut form is one ``while`` over the same step: its body holds every
    operation of the scan's."""
    cfg, params = tiny_model(family)
    cache = gen.init_cache(cfg, 3, 64)
    args = (served(params, cfg), cache, jnp.zeros((3,), jnp.int32), jnp.ones((3,), bool),
            jax.random.PRNGKey(0))
    chunk = lambda params, cache, *rest, **kw: gen.decode_chunk(  # noqa: E731
        params, cfg, cache, *rest, steps=8, **kw)
    whole = jax.make_jaxpr(chunk)(*args).jaxpr.eqns
    cut = jax.make_jaxpr(lambda *a: chunk(*a[:-1], n=a[-1]))(
        *args, jnp.int32(3)).jaxpr.eqns
    # (the flush's loop over the 3 slots, a tensor, follows the steps')
    loops = lambda eqns: [e for e in eqns  # noqa: E731
                          if e.primitive.name in ("scan", "while")]
    scan, loop = loops(whole)[0], loops(cut)[0]
    assert [e.primitive.name for e in loops(whole)] == (
        ["scan"] * len(loops(whole)))  # no loop with a runtime bound
    assert scan.params["length"] == 8
    assert [e.primitive.name for e in loops(cut)] == (
        ["while"] + ["scan"] * (len(loops(whole)) - 1))
    ops = lambda jaxpr: {e.primitive.name for e in jaxpr.eqns}  # noqa: E731
    assert ops(scan.params["jaxpr"].jaxpr) <= ops(
        loop.params["body_jaxpr"].jaxpr)


# ---------------------------------------------------------------------------
# the engine: never started, the test is its thread

def _engine(family, **kw):
    """Two slots, buckets of 8 and 128, answers of up to 40 tokens; window 16
    where a family has one: the one-shot reference decodes an answer in ONE
    chunk."""
    return engine(family, seed=3, changed=LONG_CHUNK.get(family, {}), **{
        "n_slots": 2, "max_new_tokens": 40, "prefill_buckets": (8, 128), **kw})


def _chunks(eng, futs):
    """The ticks of ``run_engine`` that dispatched a chunk."""
    return [s for s in run_engine(eng, futs) if s["steps"] is not None]


def test_an_answer_of_18_tokens_pays_17_steps_and_lands_with_its_cut():
    """Alone, with chunks of 16: one whole chunk and a cut of ONE step, where
    two whole chunks ran before; the future resolves when that chunk lands,
    and the meter counts the steps the request's chunks really ran."""
    eng, cfg, params = _engine("gpt2", decode_chunk_steps=16)
    fut = eng.submit([3, 5, 7], 18)
    seen = _chunks(eng, [fut])
    assert [s["steps"] for s in seen] == [16, 1]
    # the cut chunk was dispatched in the second tick and drained in the
    # third: the answer was whole the moment it landed
    assert [fut.result(timeout=1)] == one_shot(params, cfg, [[3, 5, 7]], 18)
    decode = eng.perf_stats()["decode"]
    assert (decode["requests"], decode["gaps"], decode["chunk_steps_paid"]) == (
        1, 17, 17)


@pytest.mark.parametrize("family", ["gpt2", "exaone_moe", "kimi_k2"])
def test_two_requests_ending_apart_cut_twice_and_the_counters_follow(family):
    """Answers of 6 and 11 tokens in chunks of 4, side by side: 4, a cut of 1
    (the first ends), 4, a cut of 1 (the second ends).  ``scheduled``, the
    routing counts' ``decode_steps`` and the flush's crossing test follow the
    steps a chunk really ran; the answers are the one-shot path's."""
    eng, cfg, params = _engine(  # one bucket: both ride the first tick's call
        family, decode_chunk_steps=4, prefill_buckets=(128,))
    # 122 positions: the second chunk stands at 127 and its ONE real column
    # stays inside the tile, where a whole chunk's four would cross 128
    prompts = [[1 + i % 50 for i in range(122)], [9, 4, 7]]
    futs = [eng.submit(p, n) for p, n in zip(prompts, (6, 11))]
    seen = _chunks(eng, futs)
    assert [s["steps"] for s in seen] == [4, 1, 4, 1]
    assert [s["scheduled"] for s in seen] == [[], [1, 1], [4], [1]]
    # 122 + 1 - 1 and 3: one tile each; 126 % 128 > 128 - 1 is false
    assert [s["flushed"] for s in seen] == [2, 2, 1, 1]
    assert [s["done"] for s in seen] == [
        [False, False], [False, False], [True, False], [True, False]]
    assert [f.result(timeout=1) for f in futs] == one_shot(
        params, cfg, prompts, (6, 11))
    stats = eng.perf_stats()
    assert stats["decode"]["chunk_steps_paid"] == stats["decode"]["gaps"] == 15
    if "experts_held" in TINY[family]:  # its layers count what they routed
        assert stats["moe"]["decode_steps"] == 4 + 1 + 4 + 1
        tokens = np.asarray(stats["moe"]["decode"]["tokens"])
        # only live rows' steps were routed: 5 + 10 tokens, top-4 of 16
        assert 0 < tokens.sum(1).max() <= 4 * 15


def test_one_cut_program_serves_every_bound_and_one_token_needs_none():
    """Answers of every length from 1 to 12 on three slots, in chunks of 8:
    whatever bounds the cuts take, no program is built beyond the whole chunk
    and the one cut chunk; an answer of ONE token is its prefill's and asks
    for no cut."""
    from ray_tpu.util import compile_cache

    eng, cfg, params = _engine("gpt2", decode_chunk_steps=8, n_slots=3)
    first = eng.submit([5, 9, 2], 1)
    assert [s["steps"] for s in _chunks(eng, [first])] == [8]  # today's vehicle
    assert [first.result(timeout=1)] == one_shot(params, cfg, [[5, 9, 2]], 1)
    _chunks(eng, [eng.submit([5, 9, 2], 10)])  # a whole chunk and a cut of 1
    built = compile_cache.counts()["count"]
    lengths = [12, 3, 9, 1, 7, 11, 2, 5, 10, 4, 8, 6]
    prompts = [[3 + i, 17, 5][:1 + i % 3] for i in range(12)]
    futs = [eng.submit(p, n) for p, n in zip(prompts, lengths)]
    steps = [s["steps"] for s in _chunks(eng, futs)]
    assert len(set(steps)) >= 5, steps
    assert compile_cache.counts()["count"] == built
    assert [f.result(timeout=1) for f in futs] == one_shot(
        params, cfg, prompts, lengths)
    decode = eng.perf_stats()["decode"]
    assert decode["chunk_steps_paid"] == decode["gaps"]
