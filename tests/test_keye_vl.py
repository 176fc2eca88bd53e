"""The Keye-VL family (a vision tower in front of the text path, three-axis
rotary positions that are not cache positions, grouped-query attention under a
learned selection over a slab of K and V, softmax-routed experts all held)
against its plain reference (``benchmark/reference/keye_vl_ref.py``), at a
small size on the CPU: ``index_topk`` 8 against contexts of 30-50 so that the
selection drops positions, frames of 4 x 4 patches (4 rows a frame), a tower
of 2 blocks, 8 experts top 2.

Tolerances.  With ``dtype=float32`` the program and the reference do the same
arithmetic in another order, so logits of size ~1 and tower rows of size ~2
agree to a few 1e-6; the limit is ``F32_TOL = 2e-4``, far under what any
departure makes (the selection left out or one-axis positions for the video's
tokens move a logit by > 1: ``test_the_controls_move_the_logits``; a stale
index key, a wrong ``rope_delta``, a frame encoded twice: > 1e-2).  The
text-only request is compared BIT for bit.  Logits are compared, not tokens.
"""

import base64
import json
import os
import sys
import urllib.error
import urllib.request
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import family_harness
import pytest
from family_harness import (
    TINY,
    decode_chunk,
    engine,
    padded,
    prefill_at,
    tiny_model,
    worst_gap,
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import keye_vl_ref as ref  # noqa: E402
from ray_tpu.models import generate as gen  # noqa: E402
from ray_tpu.models import keye_vl as kv  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.serve import llm  # noqa: E402
from ray_tpu.serve.llm import RequestRefused, make_config  # noqa: E402

pytestmark = pytest.mark.usefixtures("kept_engine_programs")
F32_TOL = 2e-4
GRID = (4, 4)          # patches a frame; 2 x 2 = 4 merged rows
PER_FRAME = 4

# (op by op the tower and the unrolled layers cost more than compiled)
_encode = jax.jit(kv.encode_video, static_argnums=(1, 3))
_apply = jax.jit(kv.apply, static_argnums=2)
_apply_video = jax.jit(
    lambda params, toks, patches, cfg: kv.apply(params, toks, cfg, (patches, GRID)),
    static_argnums=3)


def sizes_of(cfg, **changed):
    return {"head_dim": cfg.head_dim, "mrope_section": list(cfg.mrope_section),
            "rope_theta": cfg.rope_base, "index_n_heads": cfg.index_n_heads,
            "index_topk": cfg.index_topk, "top_k": cfg.experts_per_token,
            "rms_eps": cfg.rms_eps, "video_token_id": cfg.video_token_id,
            "vision_heads": cfg.vision_heads, **changed}


@pytest.fixture(scope="module")
def model():
    return tiny_model("keye_vl")


def a_video(cfg, frames, seed=0):
    """``frames`` frames of 4 x 4 patches of the seed's bytes."""
    return np.random.RandomState(seed).randint(
        0, 256, (frames, GRID[0] * GRID[1], 3 * cfg.vision_patch ** 2)
    ).astype(np.uint8)


def a_prompt(cfg, before, frames, after, seed=0):
    """Text, the placeholder once a merged row of the video, text."""
    rng = np.random.RandomState(seed)
    text = lambda n: [int(t) for t in rng.randint(0, cfg.video_token_id, n)]  # noqa: E731
    return text(before) + [cfg.video_token_id] * (frames * PER_FRAME) + text(after)


def ref_logits(model, video, seq, **changed):
    cfg, params = model
    seq = padded(seq)
    return ref.logits(params, np.asarray([seq]), sizes_of(cfg, **changed),
                      videos=[video])[0]


def visual_for(cfg, params, prompt, patches, first=0, width=None, rows=None):
    """The ``visual`` argument of a call over ``prompt[first:first + width]``
    (one row), the tower's rows for the whole video handed over at once."""
    width = width or len(prompt) - first
    at = prompt.index(cfg.video_token_id)
    frames = len(patches)
    if rows is None:
        rows = _encode(params, cfg, jnp.asarray(patches), GRID)
    positions, delta = kv.rope_index(len(prompt), at, (frames, 2, 2))
    index = np.full((1, width), -1, np.int32)
    p3 = np.zeros((1, 3, width), np.int32)
    for j in range(min(width, len(prompt) - first)):
        g = first + j
        p3[0, :, j] = positions[:, g]
        if at <= g < at + frames * PER_FRAME:
            index[0, j] = g - at
    return {"rows": (rows,), "index": jnp.asarray(index),
            "positions": jnp.asarray(p3), "delta": jnp.asarray([delta])}


def test_config_is_the_published_one_and_says_what_it_caches():
    cfg = make_config("keye_vl", "2.0-30b-a3b")
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (2048, 48, 32, 4, 128, 151936)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.d_expert,
            cfg.experts_held) == (128, 8, 768, (0, 128))
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (16, 64, 2048)
    assert cfg.mrope_section == (16, 24, 24) and cfg.rope_base == 1e7
    assert (cfg.vision_layers, cfg.vision_d_model, cfg.vision_heads,
            cfg.vision_d_ff, cfg.vision_patch) == (27, 1152, 16, 4304, 14)
    hash(cfg)  # jit closes over it
    assert gen.family_of(cfg) is kv and gen.rope_offset(cfg)
    assert gen.cached_tensors(cfg) == ("k", "v", "idx_k")
    assert gen.index_cache(cfg) == (64, 2048) and gen.can_continue(cfg)
    # the cell's cache: 13 rows x 17,536 positions x 6 layers x (2 x 4 x 128
    # + 64) values = 2.98 GB, and an offset a slot
    cell = make_config("keye_vl", "2.0-30b-a3b", n_layers=6)
    cache = jax.eval_shape(lambda: gen.init_cache(cell, 13, 17536))
    assert set(cache) == {"k", "v", "idx_k", "rope_delta", "pos"}
    assert cache["k"].shape == cache["v"].shape == (6, 13, 4, 128, 17536)
    assert cache["idx_k"].shape == (6, 13, 1, 64, 17536)
    assert sum(cache[n].size for n in ("k", "v", "idx_k")) * 2 == 2_976_350_208
    # the stage's parameters: 9.64 GB in bfloat16, the tower 0.89 of them
    tree = jax.eval_shape(lambda: kv.init(cell, jax.random.PRNGKey(0)))
    assert kv.num_params(tree) == 4_818_289_520
    assert kv.num_params(tree["vision"]) == 443_667_056
    with pytest.raises(AssertionError):
        kv.KeyeVLConfig.tiny(mrope_section=(2, 2, 2))  # not the head's pairs


def test_the_tower_against_the_reference(model):
    cfg, params = model
    patches = a_video(cfg, 3)
    got = np.asarray(_encode(params, cfg, jnp.asarray(patches), GRID))
    want = np.asarray(ref.tower(params, patches, GRID, sizes_of(cfg)))
    assert got.shape == (3, PER_FRAME, cfg.d_model)
    assert np.abs(want).max() > 0.5 and np.abs(got - want).max() < F32_TOL
    # frames are independent: a frame alone is the frame among others
    alone = np.asarray(_encode(params, cfg, jnp.asarray(patches[1:2]), GRID))
    assert np.abs(alone[0] - got[1]).max() < F32_TOL
    # ... and an uneven grid is another interpolation of the same table
    wide = np.random.RandomState(1).randint(
        0, 256, (1, 2 * 6, patches.shape[-1])).astype(np.uint8)
    assert np.abs(
        np.asarray(_encode(params, cfg, jnp.asarray(wide), (2, 6)))
        - np.asarray(ref.tower(params, wide, (2, 6), sizes_of(cfg)))).max() < F32_TOL


@pytest.mark.parametrize("before,frames,grid,after", [
    (5, 3, (2, 2), 7), (0, 9, (1, 3), 1), (4, 1, (4, 2), 0), (6, 0, (2, 2), 3)])
def test_positions_and_rope_delta_follow_the_rule(before, frames, grid, after):
    """text - video - text: a text token at ``next`` on every axis, the video's
    at ``(s + f, s + r, s + c)``, ``next = s + max(F, gh, gw)`` after it, and
    the slot decodes at ``n + delta``."""
    n_vis = frames * grid[0] * grid[1]
    tokens = np.array([1] * before + [255] * n_vis + [2] * after)
    positions, delta = kv.rope_index(len(tokens), before, (frames, *grid))
    want = ref.positions_of(tokens, 255, (frames, *grid) if frames else None)
    assert (positions == want).all()
    nxt = before + (max(frames, *grid) if frames else 0) + after
    assert delta == nxt - len(tokens) <= 0
    if frames:
        assert positions[:, before].tolist() == [before] * 3
        assert positions[:, before + n_vis - 1].tolist() == [
            before + frames - 1, before + grid[0] - 1, before + grid[1] - 1]
    if after:
        assert positions[:, -1].tolist() == [nxt - 1] * 3


def test_forward_against_the_reference(model):
    """The whole forward over 5 + 9 x 4 + 9 = 50 tokens (the selection keeps 8
    of up to 50 positions), with a video and without."""
    cfg, params = model
    patches, prompt = a_video(cfg, 9), a_prompt(cfg, 5, 9, 9)
    seq = padded(prompt)
    got = np.asarray(_apply_video(
        params, jnp.asarray([seq]), jnp.asarray(patches), cfg))[0, :len(prompt)]
    want = ref_logits(model, (patches, GRID), prompt)[:len(prompt)]
    assert np.abs(want).max() > 1.0 and np.abs(got - want).max() < F32_TOL
    text = [t for t in prompt if t != cfg.video_token_id] * 2
    got = np.asarray(_apply(
        params, jnp.asarray([padded(text)]), cfg))[0, :len(text)]
    assert np.abs(got - ref_logits(model, None, text)[:len(text)]).max() < F32_TOL


def test_the_controls_move_the_logits(model):
    """What the cell's controls change in the reference is far over the
    tolerance: the selection left out, one-axis positions for the video."""
    cfg, _ = model
    patches, prompt = a_video(cfg, 9), a_prompt(cfg, 5, 9, 9)
    sound = ref_logits(model, (patches, GRID), prompt)[:len(prompt)]
    for changed in ({"index_topk": 1 << 20}, {"one_axis_positions": True}):
        other = ref_logits(model, (patches, GRID), prompt, **changed)[:len(prompt)]
        assert np.abs(other - sound).max() > 0.5, changed


WALK = dict(steps=6, bucket=64, cache_len=128)


def served_with_video(cfg, params, prompt, patches, chunks, cache=None):
    """Prefill ``prompt`` (whole, its video's rows handed over) into slot 2 of
    three, then chunks of 6 steps, whole or cut -> served tokens, cache, the
    chunks' counts."""
    cache = gen.init_cache(cfg, 3, WALK["cache_len"]) if cache is None else cache
    toks = np.zeros((1, WALK["bucket"]), np.int32)
    toks[0, :len(prompt)] = prompt
    last, cache, _ = prefill_at(
        params, cfg, jnp.asarray(toks), jnp.asarray([len(prompt)]), cache,
        jnp.asarray([2]), visual=visual_for(
            cfg, params, prompt, patches, width=WALK["bucket"]))
    first = int(jnp.argmax(last[0]))
    tokens = jnp.zeros((3,), jnp.int32).at[2].set(first)
    active = jnp.zeros((3,), bool).at[2].set(True)
    served, counted = [first], []
    for n in chunks:
        emitted, cache, active, _, routed = decode_chunk(
            params, cfg, cache, tokens, active, steps=WALK["steps"], n=n)
        counted.append(routed)
        tokens = emitted[:, -1]
        served += [int(t) for t in emitted[2, :n]]
    return served, cache, counted


@pytest.mark.parametrize("chunks", [(None, None), (4, None, 1)],
                         ids=["whole", "cut"])
def test_prefill_then_decode_through_the_cache(model, chunks):
    """A prompt of 5 + 7 x 4 + 6 = 39 tokens (39 cache positions, rotary
    position 13 after them: delta -26), then chunks of 6 steps: every step
    selects 8 of 40..51 CACHE positions across the slab and the chunk's own
    columns and rotates by ``pos + delta``.  Each served token's LOGIT is the
    reference's best at its position within float32 rounding."""
    cfg, params = model
    patches, prompt = a_video(cfg, 7), a_prompt(cfg, 5, 7, 6)
    served, cache, counted = served_with_video(cfg, params, prompt, patches, chunks)
    assert set(cache) == {"k", "v", "idx_k", "rope_delta", "pos"}
    assert worst_gap(partial(ref_logits, model, (patches, GRID)),
                     [prompt], [served]) < F32_TOL
    steps = [6 if n is None else n for n in chunks]
    assert int(cache["pos"][2]) == 39 + sum(steps)
    assert cache["rope_delta"].tolist() == [0, 0, 5 + 7 + 6 - 39]
    done = 0
    for counts, n in zip(counted, steps):
        ctx = [39 + done + i + 1 for i in range(n)]
        assert counts["dsa_scored"].tolist() == [sum(ctx)] * cfg.n_layers
        assert counts["dsa_selected"].tolist() == [8 * n] * cfg.n_layers
        assert int(counts["touched"].sum()) <= 2 * n * cfg.n_layers
        done += n


def test_the_chip_path_serves_the_same_tokens(model, lowered_for_tpu):
    """The same walk with the decode program as a chip runs it: the ragged
    kernel over k and v handed the step's selection as its mask, the flush
    kernel over ``k``, ``v`` and ``idx_k``, in the TPU interpreter."""
    cfg, params = model
    patches, prompt = a_video(cfg, 7), a_prompt(cfg, 5, 7, 6)
    served, _, counted = served_with_video(
        cfg, params, prompt, patches, (None, 2))
    assert worst_gap(partial(ref_logits, model, (patches, GRID)),
                     [prompt], [served]) < F32_TOL
    # the kernel reads a slot's live tiles whole: 128 rows at this length
    assert counted[0]["dsa_read"].tolist() == [
        sum(128 + i + 1 for i in range(6))] * cfg.n_layers


def test_a_prompt_in_parts_with_a_frame_astride(model):
    """5 + 9 x 4 + 7 = 48 tokens in parts of 16: the part boundaries at 16 and
    32 fall INSIDE frames 2 and 6 (a frame's rows on both sides), and every
    part after the first selects over cached and own positions.  The last
    part's logits and everything the slot holds are the whole prompt's."""
    cfg, params = model
    patches, prompt = a_video(cfg, 9), a_prompt(cfg, 5, 9, 7)
    rows = _encode(params, cfg, jnp.asarray(patches), GRID)
    toks = np.zeros((1, 64), np.int32)
    toks[0, :48] = prompt
    whole, held, _ = prefill_at(
        params, cfg, jnp.asarray(toks), jnp.asarray([48]),
        gen.init_cache(cfg, 2, 128), jnp.asarray([0]),
        visual=visual_for(cfg, params, prompt, patches, width=64, rows=rows))
    cache = gen.init_cache(cfg, 2, 128)
    for first in (0, 16, 32):
        part = np.asarray([prompt[first:first + 16]], np.int32)
        last, cache, _ = prefill_at(
            params, cfg, jnp.asarray(part), jnp.asarray([16]), cache,
            jnp.asarray([0]), jnp.asarray([first]), bound=64,
            visual=visual_for(cfg, params, prompt, patches, first, 16, rows))
    assert np.abs(np.asarray(last) - np.asarray(whole)).max() < F32_TOL
    assert np.abs(np.asarray(whole)[0] - ref_logits(
        model, (patches, GRID), prompt)[47]).max() < F32_TOL
    for name in ("k", "v", "idx_k"):
        assert np.abs(np.asarray(cache[name][:, 0, ..., :48])
                      - np.asarray(held[name][:, 0, ..., :48])).max() < F32_TOL
    assert cache["pos"].tolist() == held["pos"].tolist() == [48, 0]
    assert cache["rope_delta"].tolist() == held["rope_delta"].tolist()


def test_a_text_request_is_the_model_fed_one_axis_positions(model):
    """A request without a video runs the program for token ids alone
    (positions are cache positions), BIT-equal to the same tokens through the
    program that takes three-axis positions, every axis the same; and it
    leaves the slot's offset 0 whatever its last tenant left."""
    cfg, params = model
    prompt = [int(t) for t in np.random.RandomState(3).randint(0, 250, 23)]
    toks = np.zeros((1, 32), np.int32)
    toks[0, :23] = prompt
    args = (jnp.asarray(toks), jnp.asarray([23]))
    dirty = {**gen.init_cache(cfg, 2, 64), "rope_delta": jnp.asarray([-7, -7])}
    plain, cache, _ = prefill_at(params, cfg, *args, dirty, jnp.asarray([0]))
    same = {"rows": (jnp.zeros((1, PER_FRAME, cfg.d_model)),),
            "index": jnp.full((1, 32), -1, jnp.int32),
            "positions": jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (1, 3, 32)),
            "delta": jnp.asarray([0])}
    three, other, _ = prefill_at(params, cfg, *args, gen.init_cache(cfg, 2, 64),
                                 jnp.asarray([0]), visual=same)
    assert np.array_equal(np.asarray(plain), np.asarray(three))
    assert all(np.array_equal(np.asarray(cache[n]), np.asarray(other[n]))
               for n in ("k", "v", "idx_k"))
    assert cache["rope_delta"].tolist() == [0, -7]
    assert np.abs(np.asarray(plain)[0] - ref_logits(model, None, prompt)[22]
                  ).max() < F32_TOL


@pytest.fixture(scope="module")
def small_parts():
    """Parts of 16 tokens and tower calls of 2 frames, so that prompts of 50
    tokens go in parts and a video in several calls (the engine reads both
    when it plans a call; put back after the module)."""
    was = llm.PREFILL_PART_TOKENS, llm.VISION_CALL_PATCHES
    llm.PREFILL_PART_TOKENS, llm.VISION_CALL_PATCHES = 16, 32
    yield
    llm.PREFILL_PART_TOKENS, llm.VISION_CALL_PATCHES = was


def test_engine_serves_videos_and_text_side_by_side(model, small_parts):
    """Through ``GenerationEngine``: a video in parts (frames astride the
    parts' ends, the last tower call padded), a short video and a text request
    in ONE whole call, a video after long text; every answer is the
    reference's greedy one by its logits, and the counters add up."""
    cfg, params = model
    eng, _, _ = engine("keye_vl", n_slots=3, max_new_tokens=6,
                       decode_chunk_steps=4, prefill_buckets=(16, 32, 64),
                       prefill_token_budget=64)
    cases = [(a_prompt(cfg, 5, 9, 7, 1), a_video(cfg, 9, 1)),
             (a_prompt(cfg, 3, 2, 4, 2), a_video(cfg, 2, 2)),
             (a_prompt(cfg, 12, 0, 0, 3), None),
             (a_prompt(cfg, 1, 5, 30, 4), a_video(cfg, 5, 4))]
    futs = [eng.submit(p, 6, video=None if v is None else {
        "grid": [len(v), *GRID], "patches": v.reshape(-1, v.shape[-1])})
        for p, v in cases]
    for _ in range(400):
        if all(f.done() for f in futs):
            break
        eng.step()
    for (prompt, video), fut in zip(cases, futs):
        served = fut.result(0)
        assert len(served) == 6
        assert worst_gap(partial(
            ref_logits, model, None if video is None else (video, GRID)),
            [prompt], [served]) < F32_TOL
    stats = eng.perf_stats()
    vision = stats["vision"]
    assert vision["requests"] == 3 and vision["requests_refused"] == 0
    assert vision["frames"] == 16 and vision["patches"] == 16 * 16
    assert vision["visual_tokens"] == 16 * PER_FRAME
    # 9 frames in calls of 2: five calls, the last padded with one frame
    assert vision["calls"] == 5 + 1 + 3 and vision["padded_patches"] == 2 * 16
    assert {k[7:]: v for k, v in stats["cache_tiles"].items()
            if k.startswith("vision_")} == {
        k: v for k, v in vision.items() if k != "requests_refused"}
    assert stats["prefill"]["parts"]["prompts"] == 2
    assert stats["dsa"]["decode"]["rows_selected"] > 0
    assert all(r.video is None or r.video["patches"] is None
               for r in [f for f in futs] if hasattr(r, "video"))
    eng.stop()


def test_parts_planned_in_one_tick_keep_their_own_tower_calls(model, small_parts):
    """Nobody decodes and the budget is wide: ALL parts of a prompt are planned
    in one tick, before any is dispatched.  Each part still gets the tower
    calls planned for IT (a later part's must not ride with the first, whose
    last result the next part reads its straddling frame from), and the
    pixels stay on the host until the last call has taken its frames."""
    cfg, params = model
    eng, _, _ = engine("keye_vl", n_slots=3, max_new_tokens=6,
                       decode_chunk_steps=4, prefill_buckets=(16, 32, 64),
                       prefill_token_budget=1 << 20)
    prompt, video = a_prompt(cfg, 5, 9, 7, 1), a_video(cfg, 9, 1)
    fut = eng.submit(prompt, 6, video={
        "grid": [9, *GRID], "patches": video.reshape(-1, video.shape[-1])})
    for _ in range(50):
        if fut.done():
            break
        eng.step()
    assert worst_gap(partial(ref_logits, model, (video, GRID)),
                     [prompt], [fut.result(0)]) < F32_TOL
    assert eng.perf_stats()["vision"]["calls"] == 5
    eng.stop()


@pytest.mark.parametrize("held", [(0, 8), (0, 4)],
                         ids=["every_expert_held", "a_share_held"])
def test_the_engine_counts_a_prefill_calls_rows_and_trips(
        model, small_parts, monkeypatch, held):
    """``perf_stats()["moe"]["prefill"]``: a stage that holds EVERY expert
    sends a call of more than one block of pairs (made 16 here; a part of 16
    tokens is 32) through the grouped matmuls at once: ``trips`` 0 and the
    call's pairs as ``rows_computed``, a layer each, and the answer is still
    the reference's.  The same family handed a SHARE walks its held pairs in
    trips (of 8 rows here), and the counters say what the loop was handed."""
    monkeypatch.setattr(moe, "_ONE_BLOCK_PAIRS", 16)
    monkeypatch.setattr(moe, "_TRIP_ROWS", 8)
    # the block's size is read at trace time: programs of a path of their own
    monkeypatch.setattr(family_harness, "PATH", "one_block_is_16_pairs")
    eng, cfg, _ = engine("keye_vl", changed=(("experts_held", held),),
                         n_slots=3, max_new_tokens=6, decode_chunk_steps=4,
                         prefill_buckets=(16, 32, 64), prefill_token_budget=64)
    assert cfg.all_experts_held == (held == (0, 8))
    landed, count = [], eng._count_routed
    monkeypatch.setattr(eng, "_count_routed", lambda phase, counts, *a, **kw: (
        landed.append((phase, counts, kw.get("padded"))),
        count(phase, counts, *a, **kw))[1])
    prompt, video = a_prompt(cfg, 5, 9, 7, 1), a_video(cfg, 9, 1)
    fut = eng.submit(prompt, 6, video={
        "grid": [9, *GRID], "patches": video.reshape(-1, video.shape[-1])})
    for _ in range(50):
        if fut.done():
            break
        eng.step()
    routed = eng.perf_stats()["moe"]["prefill"]
    calls = [(np.asarray(c["tokens"]).sum(-1), padded)
             for phase, c, padded in landed if phase == "prefill"]
    # 48 tokens in parts of 16: the bucket's call, three rows wide, then two
    wide = [padded for _, padded in calls]
    assert wide == [3 * 16, 16, 16]
    pairs = cfg.n_layers * cfg.experts_per_token * sum(wide)
    assert all(counted.shape == (cfg.n_layers,) for counted, _ in calls)
    if cfg.all_experts_held:
        assert worst_gap(partial(ref_logits, model, (video, GRID)),
                         [prompt], [fut.result(0)]) < F32_TOL
        assert routed["trips"] == 0
        assert routed["rows_computed"] == pairs
        # (the bucket's two unfilled rows are 1-token dummies, routed too)
        assert np.sum(routed["tokens"]) == (len(prompt) + 2) * 2 * cfg.n_layers
    else:
        trips = sum(int((-(-counted // 8)).sum()) for counted, _ in calls)
        assert 0 < routed["trips"] == trips
        assert routed["rows_computed"] == 8 * trips < pairs
    assert "trips" not in eng.perf_stats()["moe"]["decode"]
    eng.stop()


REFUSED = {
    "tokens-no-list": (dict(tokens="abc"), "tokens"),
    "tokens-a-float": (dict(tokens=[1, 2.5]), "tokens"),
    "tokens-empty": (dict(tokens=[]), "tokens"),
    "max-new-negative": (dict(tokens=[1, 2], max_new=-1), "max_new_tokens"),
    "video-without-grid": (dict(tokens=[1, 255, 255, 255, 255],
                                video={"patches": ""}), "video"),
    "placeholders-not-the-grid": (dict(
        tokens=[1, 255, 255, 3], video={
            "grid": [1, 4, 4], "patches": np.zeros((16, 12), np.uint8)}), "video"),
    "placeholders-in-two-runs": (dict(
        tokens=[255, 255, 1, 255, 255], video={
            "grid": [1, 4, 4], "patches": np.zeros((16, 12), np.uint8)}), "video"),
    "an-odd-grid": (dict(tokens=[1, 255, 3], video={
        "grid": [1, 3, 2], "patches": np.zeros((6, 12), np.uint8)}), "video"),
    "patches-of-another-size": (dict(
        tokens=[1, 255, 255, 255, 255], video={
            "grid": [1, 4, 4], "patches": np.zeros((15, 12), np.uint8)}), "video"),
    "placeholders-and-no-video": (dict(tokens=[1, 255, 3]), "video"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_a_malformed_request_is_refused_at_submission(case):
    """In the caller's thread, with an error that names the fault, counted by
    reason, and never admitted."""
    request, reason = REFUSED[case]
    eng, _, _ = engine("keye_vl", n_slots=2, max_new_tokens=4,
                       decode_chunk_steps=2, prefill_buckets=(16,))
    before = eng.stats()
    with pytest.raises(RequestRefused) as refused:
        eng.submit(request["tokens"], request.get("max_new"),
                   video=request.get("video"))
    assert refused.value.reason == reason and isinstance(refused.value, ValueError)
    after = eng.stats()
    assert after["refused"].get(reason, 0) == before["refused"].get(reason, 0) + 1
    assert after["queued"] == after["total_requests"] - before["total_requests"] == 0
    assert not eng.step()
    eng.stop()


def test_a_family_without_a_tower_refuses_a_video():
    eng, _, _ = engine("gpt2", n_slots=2, max_new_tokens=4,
                       decode_chunk_steps=2, prefill_buckets=(16,))
    with pytest.raises(RequestRefused, match="token ids alone"):
        eng.submit([1, 2, 3], 2, video={"grid": [1, 2, 2], "patches": ""})
    assert "vision" not in eng.perf_stats()
    eng.stop()


@pytest.fixture
def serve_instance():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield serve.start(serve.HTTPOptions(host="127.0.0.1", port=0))
    serve.shutdown()
    ray_tpu.shutdown()


def test_llm_deployment_end_to_end(serve_instance, model):
    """Over HTTP: a video request as the benchmark's client sends it (base64),
    a text request, and a malformed body answered 400 with the fault named."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import llm_deployment

    cfg, params = model
    dep = llm_deployment(
        "keye_vl", "tiny",
        engine_kwargs=dict(n_slots=2, max_new_tokens=4, decode_chunk_steps=2,
                           prefill_buckets=(32, 64)),
        config_kwargs=dict(dtype=jnp.float32, **TINY["keye_vl"]))
    handle = serve.run(dep.bind(), port=0)
    host, port = serve.get_http_address()

    def post(body):
        req = urllib.request.Request(
            f"http://{host}:{port}/{dep.name}", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=240) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    patches, prompt = a_video(cfg, 6), a_prompt(cfg, 4, 6, 5)
    video = {"grid": [6, *GRID],
             "patches": base64.b64encode(patches.tobytes()).decode()}
    status, out = post({"tokens": prompt, "max_new_tokens": 4, "video": video})
    assert status == 200 and len(out["tokens"]) == 4
    assert worst_gap(partial(ref_logits, model, (patches, GRID)),
                     [prompt], [out["tokens"]]) < F32_TOL
    status, out = post({"tokens": prompt[:4], "max_new_tokens": 2})
    assert status == 200 and len(out["tokens"]) == 2
    status, out = post({"tokens": prompt, "max_new_tokens": 4,
                        "video": {"grid": [5, *GRID], "patches": video["patches"]}})
    assert status == 400 and "placeholders" in out["error"]
    status, out = post({"max_new_tokens": 4})
    assert status == 400 and "tokens" in out["error"]
    stats = ray_tpu.get(handle.stats.remote(), timeout=60)
    assert stats["refused"] == {"video": 1, "tokens": 1}
    assert stats["total_requests"] == 2
    perf = ray_tpu.get(handle.perf_stats.remote(), timeout=60)
    assert perf["vision"]["frames"] == 6 and perf["vision"]["requests_refused"] == 1
    assert "engine.vision_encode" in perf["stages"]
