"""The dots3-note family (two kinds of latent attention, a learned index that
picks the cached positions a full layer's queries read, a headwise gate,
sigmoid-routed held experts) against its plain reference
(``benchmark/reference/dots3_note_ref.py``), at a small size on the CPU:
``index_topk`` 8 and window 5 against contexts of ~40, so that both bite.

Tolerances.  With ``dtype=float32`` the program and the reference do the same
arithmetic in another order, so logits of size ~1 agree to a few 1e-6; the
limit is ``F32_TOL = 2e-4``, far under what any departure makes (a selection
left out, one position more in a window, a stale index key: > 1e-2).  The
selected SETS are compared exactly.
"""

import dataclasses
import importlib
from functools import partial
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_harness import (
    TINY,
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
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import dots3_note_ref as ref  # noqa: E402
from ray_tpu.models import dots3_note as dn  # noqa: E402
from ray_tpu.models import generate as gen  # noqa: E402
from ray_tpu.ops import dsa, moe  # noqa: E402
from ray_tpu.serve.llm import make_config  # noqa: E402

attention = importlib.import_module("ray_tpu.ops.attention")

pytestmark = pytest.mark.usefixtures("kept_engine_programs")
F32_TOL = 2e-4


def sizes_of(cfg, **changed):
    return {"layer_types": list(cfg.layer_types),
            "full": {"qk_nope_head_dim": cfg.qk_nope_head_dim,
                     "rope_theta": cfg.rope_base},
            "sliding": {"qk_nope_head_dim": cfg.swa_qk_nope_head_dim,
                        "rope_theta": cfg.swa_rope_base},
            "index_n_heads": cfg.index_n_heads, "index_topk": cfg.index_topk,
            "index_rope_dim": cfg.qk_rope_head_dim,
            "sliding_window": cfg.sliding_window,
            "lora_rescale": cfg.lora_rescale, "top_k": cfg.experts_per_token,
            "routed_scale": cfg.routed_scale,
            "first_expert": cfg.experts_held[0], "rms_eps": cfg.rms_eps,
            **changed}


@pytest.fixture(scope="module")
def model():
    # 5 layers as the cell's: full + dense, full + sparse, three sliding +
    # sparse; 4 | 2 heads, rows of 16 + 8 and 24 + 8 values, index keys of 16,
    # top-8 positions, window 5, 16 experts of which 4..11 are held, top-4
    return tiny_model("dots3_note")


# the five unrolled layers cost more op by op than compiled
_block = jax.jit(dn.block, static_argnums=2,
                 static_argnames=("window", "absorbed"))
_apply = jax.jit(dn.apply, static_argnums=2, static_argnames=("absorbed",))


def ref_logits(model, seq, **changed):
    cfg, params = model
    return ref.logits(params, jnp.asarray([padded(seq)]),
                      sizes_of(cfg, **changed))[0][:len(seq)]


def test_config_is_the_published_one_and_says_what_it_caches():
    cfg = make_config("dots3_note", "note-prev", experts_held=[0, 8])
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        5120, 128, 1024, 512, 128, 64, 128)
    assert (cfg.swa_n_heads, cfg.swa_q_lora_rank, cfg.swa_kv_lora_rank,
            cfg.swa_qk_nope_head_dim, cfg.swa_qk_rope_head_dim,
            cfg.swa_v_head_dim, cfg.sliding_window) == (64, 1024, 1024, 192, 64, 128, 513)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (64, 128, 2048)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.routed_scale, cfg.d_ff,
            cfg.d_expert, cfg.vocab_size) == (256, 8, 1.0, 13824, 1536, 152064)
    kinds = [w == 0 for w in cfg.sliding_windows]
    assert sum(kinds) == 13 and len(kinds) == 46 and kinds[:6] == [
        True, True, False, False, False, True]
    assert cfg.latent_cache == (576, 512) and cfg.window_latent_cache == (1088, 1024)
    assert cfg.index_cache == (128, 2048)
    assert abs(cfg.attention_scale - 192 ** -0.5) < 1e-12
    assert abs(cfg.window_attention_scale - 256 ** -0.5) < 1e-12
    assert cfg.sizes(False)["rescale"] == (5.0 ** 0.5, 10.0 ** 0.5)
    assert cfg.sizes(True)["rescale"] == (5.0 ** 0.5, 5.0 ** 0.5)
    hash(cfg)  # jit closes over it
    assert gen.family_of(cfg) is dn
    assert gen.cached_tensors(cfg) == ("c", "idx_k")
    assert gen.cached_tensors(cfg, True) == ("c_ring",)
    # the cell's cache: 33 rows x 17,536 positions x 2 full layers x (576 +
    # 128) values, and three rings of 1,152 rows of 1,088 (nine whole tiles:
    # what the chip stores of twice the window's 1,026 either way)
    cell = dataclasses.replace(cfg, n_layers=5)
    cache = jax.eval_shape(lambda: gen.init_cache(cell, 33, 17536))
    assert set(cache) == {"c", "idx_k", "c_ring", "pos"}
    assert cache["c"].shape == (2, 33, 1, 576, 17536)
    assert cache["idx_k"].shape == (2, 33, 1, 128, 17536)
    assert cache["c_ring"].shape == (3, 33, 1, 1088, 1152)
    assert (cache["c"].size + cache["idx_k"].size) * 2 == 1_629_585_408
    assert cache["c_ring"].size * 2 == 248_168_448
    with pytest.raises(AssertionError):
        dn.Dots3NoteConfig.tiny(experts_held=(12, 8))  # past the router


@pytest.mark.parametrize("layer", [0, 1, 2],
                         ids=["full_dense", "full_sparse", "sliding_sparse"])
def test_one_block_of_each_kind_against_the_reference(model, layer):
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(layer), (1, 41, cfg.d_model))
    p = params["layers"][layer]
    got, routed, _ = _block(
        x, served_layer(p), cfg, window=cfg.sliding_windows[layer])
    with jax.default_matmul_precision("highest"):
        want = ref._layer(x, p, **ref.layer_statics(sizes_of(cfg), layer))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < F32_TOL
    assert (routed is None) == (layer == 0)


@pytest.mark.parametrize("absorbed", [False, True], ids=["unabsorbed", "absorbed"])
def test_forward_against_the_reference(model, absorbed):
    cfg, params = model
    seq = list(np.random.RandomState(1).randint(0, cfg.vocab_size, 40))
    got = np.asarray(_apply(params, jnp.asarray([seq]), cfg, absorbed=absorbed)[0])
    assert np.abs(got - ref_logits(model, seq)).max() < F32_TOL
    # and the mechanisms bite: a reference that selects nothing, or whose
    # window is one position wider, is another model
    dense = ref_logits(model, seq, index_topk=1 << 20)
    assert np.abs(got - dense).max() > 1e-2
    wider = ref_logits(model, seq, sliding_window=cfg.sliding_window + 1)
    assert np.abs(got - wider).max() > 1e-3


@pytest.mark.parametrize("layer", [1, 2], ids=["full", "sliding"])
def test_absorbed_against_unabsorbed(model, layer):
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 33, cfg.d_model))
    p, w = served_layer(params["layers"][layer]), cfg.sliding_windows[layer]
    plain, _, _ = _block(x, p, cfg, window=w)
    folded, _, _ = _block(x, p, cfg, window=w, absorbed=True)
    assert np.abs(np.asarray(plain) - np.asarray(folded)).max() < F32_TOL
    no_rescale, _, _ = _block(
        x, p, dataclasses.replace(cfg, lora_rescale=False), window=w)
    assert np.abs(np.asarray(plain) - np.asarray(no_rescale)).max() > 1e-2


def test_selected_sets_are_the_references_exactly(model):
    """Layer 1's selection over 44 positions, the program's bisection against
    the reference's ``lax.top_k`` on the same float32 inputs: the same SET a
    query, 8 of them from row 7 on and every position before."""
    cfg, params = model
    p = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 44, cfg.d_model))
    seen = {}
    dn.block(x, served_layer(p), cfg, attend=lambda q, k, v, row, index: (
        seen.update(index=index) or (jnp.zeros_like(v), None)))
    qi, w, ki = seen["index"]
    got = np.asarray(dsa.causal_top_k_mask(qi, w, ki[:, 0], cfg.index_topk))[0]
    f = ref._through(None)
    h = ref._rmsnorm(x, f(p["attn_norm"]), cfg.rms_eps)[0]
    c_q = (cfg.d_model / cfg.q_lora_rank) ** 0.5 * ref._rmsnorm(
        h @ f(p["w_dq"]), f(p["q_norm"]), cfg.rms_eps)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.selection(
            h, c_q, p, f, index_heads=cfg.index_n_heads,
            index_topk=cfg.index_topk, rope_dim=cfg.qk_rope_head_dim,
            theta=cfg.rope_base, eps=cfg.rms_eps))
    assert (got.astype(bool) == want).all()
    assert (got.sum(-1) == np.minimum(np.arange(44) + 1, 8)).all()


def test_contexts_up_to_index_topk_are_dense_latent_attention(model):
    """With ``index_topk`` at or over the context every position is chosen:
    the full layers are plain latent attention (no selection is built), and
    the reference that selects nothing agrees."""
    _, params = model
    cfg = dn.Dots3NoteConfig.tiny(dtype=jnp.float32, experts_held=(4, 8),
                                  index_topk=40)
    seq = list(np.random.RandomState(2).randint(0, cfg.vocab_size, 40))
    got = np.asarray(_apply(params, jnp.asarray([seq]), cfg)[0])
    dense = ref_logits((cfg, params), seq, index_topk=1 << 20)
    assert np.abs(got - dense).max() < F32_TOL


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_top_k_mask_is_exact_and_takes_the_lower_position_among_equals(ties):
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 300))
    if ties:
        x = jnp.round(x * 4) / 4
    valid = jnp.arange(300)[None, :] < jnp.asarray([300, 10, 0, 123, 64])[:, None]
    got = np.asarray(dsa.top_k_mask(x, valid, 64))
    for r in range(5):
        k, want = min(64, int(valid[r].sum())), np.zeros(300, bool)
        if k:
            _, top = jax.lax.top_k(jnp.where(valid[r], x[r], -jnp.inf), k)
            want[np.asarray(top)] = True
        assert (got[r] == want).all(), r


# the cache walks below: prompts into slots 2 and 0 of a three-slot cache of 128
# positions (slot 1 sits idle), chunks of 6 steps
WALK = dict(steps=6, bucket=64, cache_len=128)


@pytest.mark.parametrize("chunks", [(None, None, None), (4, None, 1, 3)],
                         ids=["whole", "cut"])
def test_prefill_then_decode_through_the_three_caches(model, chunks):
    """Prompts of 37 and 5 tokens, then chunks of 6 steps, whole or CUT: the
    long slot selects 8 of 37..55 positions at every step, across the slab and
    the chunk's own columns (a chunk boundary lies inside every selection
    after the first chunk), its rings wrap (10 entries), and the short slot
    goes from "every position" to selecting in its first chunk.  Each served
    token's LOGIT is the reference's best at its position, within float32
    rounding, and the counters say what was scored and chosen."""
    cfg, params = model
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, cfg.vocab_size, 37)),
               list(rng.randint(0, cfg.vocab_size, 5))]
    served, cache, counted = serve(cfg, params, prompts, chunks, **WALK)
    assert set(cache) == {"c", "idx_k", "c_ring", "pos"}
    assert worst_gap(partial(ref_logits, model), prompts, served) < F32_TOL
    steps = [6 if n is None else n for n in chunks]
    assert int(cache["pos"][2]) == 37 + sum(steps)
    # a step at context c (the cache below and its own position) scores c
    # positions and chooses min(c, 8), a full layer
    contexts = [[start + done + i + 1 for start in (37, 5) for i in range(n)]
                for done, n in zip(np.cumsum([0] + steps[:-1]), steps)]
    for counts, ctx in zip(counted, contexts):
        assert counts["dsa_scored"].tolist() == [sum(ctx)] * 2
        assert counts["dsa_selected"].tolist() == [
            sum(min(c, 8) for c in ctx)] * 2


def test_the_chip_path_serves_the_same_tokens(model, lowered_for_tpu):
    """The same walk with the decode program as a chip runs it (the latent
    kernel given the selection as a mask, the flush kernel over ``c`` and
    ``idx_k``, in the TPU interpreter), cut chunks included."""
    cfg, params = model
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, cfg.vocab_size, 37)),
               list(rng.randint(0, cfg.vocab_size, 5))]
    served, _, counted = serve(cfg, params, prompts, (None, 2, None), **WALK)
    assert worst_gap(partial(ref_logits, model), prompts, served) < F32_TOL
    # the kernel reads a slot's live tiles whole: 128 rows each at these
    # lengths, and the chunk's own columns
    assert counted[0]["dsa_read"].tolist() == [
        sum(128 + i + 1 for _ in range(2) for i in range(6))] * 2


def test_a_reused_slot_sees_nothing_of_its_predecessor(model):
    """A cache whose every entry holds a large value (a predecessor's rows in
    ``c``, ``idx_k`` and the rings): the slot's answer is still the
    reference's, so nothing beyond what prefill wrote is read."""
    cfg, params = model
    rng = np.random.RandomState(9)
    prompts = [list(rng.randint(0, cfg.vocab_size, 19)),
               list(rng.randint(0, cfg.vocab_size, 3))]
    dirty = lambda cache: {  # noqa: E731
        k: v if k == "pos" else jnp.full_like(v, 7.0) for k, v in cache.items()}
    reused, cache, _ = serve(cfg, params, prompts, (None, 3), cache=dirty(
        gen.init_cache(cfg, 3, 128)), **WALK)
    assert worst_gap(partial(ref_logits, model), prompts, reused) < F32_TOL
    # what the slots hold below their positions is what prefill and the
    # flushes wrote, in all three tensors
    for name in ("c", "idx_k"):
        assert float(jnp.abs(cache[name][:, 2, :, :, :19 + 9]).max()) < 7.0


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """8 experts over 4 chips, 2 a chip: the parts the shares give, the
    shared expert counted once, are the uncut layer (the reference's sums,
    given every expert)."""
    whole = dn.Dots3NoteConfig.tiny(dtype=jnp.float32, n_experts=8)
    p = dn.init_layer(whole, jax.random.PRNGKey(3), 2)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 9, whole.d_model))
    top_k, scale = whole.experts_per_token, whole.routed_scale
    worst, counted = shares_add_up(
        p, h, 4, moe.route_sigmoid_top_k(
            h.reshape(18, -1), p["router"], p["router_bias"], top_k, scale),
        sigmoid_top_k_by_hand(h, p, top_k, scale), ref._swiglu, whole.n_experts)
    assert counted == 18 * top_k  # every choice, once
    assert worst < F32_TOL


def test_engine_serves_a_mixed_batch_and_counts_the_selection(model):
    """Prompts of three buckets through ``GenerationEngine`` (a never-started
    engine: the test is the engine thread), whole and cut chunks as the
    engine chooses them: every answer is the reference's greedy one, and
    ``perf_stats()["dsa"]`` holds ``rows_selected == sum min(context,
    topk)`` over the steps and prompt rows, a full layer."""
    cfg, params = model
    eng, _, _ = engine(
        "dots3_note", n_slots=3, max_new_tokens=6, decode_chunk_steps=3,
        prefill_buckets=(8, 32))
    assert set(eng.cache) == {"c", "idx_k", "c_ring", "pos"}
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(0, cfg.vocab_size, n)) for n in (5, 20, 12, 3)]
    new = [6, 5, 6, 2]
    futs = [eng.submit(p, m) for p, m in zip(prompts, new)]
    run_engine(eng, futs)
    # every answer is the reference's greedy one (teacher-forced: each served
    # token's logit is the reference's best at its position)
    served = [f.result(timeout=1) for f in futs]
    assert [len(out) for out in served] == new
    assert worst_gap(partial(ref_logits, model), prompts, served) < F32_TOL
    stats = eng.perf_stats()
    tiles = stats["cache_tiles"]
    assert tiles["layers"] == {"full": 2, "window": 3}
    # float32 rows: 16 + 8 values and a 16-value index key a position of a
    # full layer; 24 + 8 a ring entry
    assert tiles["tile_bytes"] == {"full": 128 * (24 + 16) * 4, "window": 128 * 32 * 4}
    dsa_stats = stats["dsa"]
    topk, full = cfg.index_topk, 2
    # prefill: row t of a prompt scores t + 1 positions and keeps min(t + 1,
    # 8); a call's padding row is a prompt of one token
    padding = sum(b["rows"] - b["prompts"] for b in stats["prefill"].values())
    assert dsa_stats["prefill"]["rows_scored"] == full * (padding + sum(
        n * (n + 1) // 2 for n in map(len, prompts)))
    assert dsa_stats["prefill"]["rows_selected"] == full * (padding + sum(
        min(t + 1, topk) for n in map(len, prompts) for t in range(n)))
    # decode: the m - 1 steps after the prefill's token, at contexts n + 1 ..
    decode = dsa_stats["decode"]
    contexts = [len(p) + i + 1 for p, m in zip(prompts, new) for i in range(m - 1)]
    # (a cut chunk may run a finished row's slot further: never fewer)
    assert decode["rows_scored"] >= full * sum(contexts)
    assert decode["rows_selected"] >= full * sum(min(c, topk) for c in contexts)
    assert decode["rows_selected"] <= decode["rows_scored"] <= decode["rows_read"]
    assert decode["steps"] == stats["moe"]["decode_steps"] > 0
    assert decode["dispatches"] == stats["moe"]["decode_dispatches"] > 0
    assert np.asarray(stats["moe"]["decode"]["tokens"]).shape == (4, 8)


def test_engine_counts_the_ring_tiles_a_chunk_lists():
    """A window of 100: rings of 256 entries, two whole tiles, which a chunk
    reads by tile.  One dispatch with two live rows, at positions 40 and 300:
    ``read_window`` counts the tiles of the entries each row's ring holds (one
    of the ring not yet full, both of the one that wrapped; a sliding layer),
    ``held_window`` every row's whole ring, which is what the parent read."""
    eng, cfg, _ = engine(
        "dots3_note", changed=(("sliding_window", 100),), n_slots=3,
        max_new_tokens=8, decode_chunk_steps=3, prefill_buckets=(64, 512))
    assert eng.cache["c_ring"].shape[-1] == 256
    assert gen.ring_read_by_tile(eng.cache, cfg)
    rng = np.random.RandomState(13)
    futs = [eng.submit(list(rng.randint(0, cfg.vocab_size, n)), 8)
            for n in (40, 300)]
    for _ in range(8):
        before = eng.perf_stats()["cache_tiles"]
        eng.step()
        chunk = eng._pending
        if chunk is not None and chunk.chunk_dev is not None and len(chunk.rows) == 2:
            break
    after = eng.perf_stats()["cache_tiles"]
    # where the two rows stood when the chunk was dispatched
    stands = sorted(len(req.tokens) + req.scheduled - chunk.steps - 1
                    for _, req in chunk.rows)
    assert stands == [40, 300]
    assert after["read_window"] - before["read_window"] == 1 + 2
    assert after["held_window"] - before["held_window"] == (3 + 1) * 2
    run_engine(eng, futs)
    tiles = eng.perf_stats()["cache_tiles"]
    assert 0 < tiles["read_window"] < tiles["held_window"]
    # the tiny preset's rings are under a tile (10 entries): every row's is
    # read whole, as a ring of K and V per head is
    tiny = tiny_model("dots3_note")[0]
    assert not gen.ring_read_by_tile(jax.eval_shape(
        lambda: gen.init_cache(tiny, 4, 64)), tiny)


def test_masked_flash_kernel_against_the_materialised_mask():
    """``masked_attention``: the Pallas forward kernel with the row mask as a
    fourth operand (TPU interpreter), cells that hold none of a row's
    positions included, against the XLA form and a dense softmax; keys wider
    than values."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    B, H, T = 1, 2, 1536
    q, k = (jax.random.normal(key, (B, H, T, 24)) for key in ks[:2])
    v = jax.random.normal(ks[2], (B, H, T, 16))
    keep = jax.random.uniform(ks[3], (B, T, T)) < 0.05
    keep = (keep.at[:, 600:, :512].set(False) | jnp.eye(T, dtype=bool)).astype(jnp.int8)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.3
    mask = (keep != 0)[:, None] & (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])
    want = jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), v)
    xla = attention.masked_attention(q, k, v, keep, scale=0.3)
    kernel = attention.masked_attention(q, k, v, keep, scale=0.3,
                                        interpret=pltpu.InterpretParams())
    assert np.abs(np.asarray(xla) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(kernel) - np.asarray(want)).max() < 1e-5


def test_latent_kernel_takes_a_selection():
    """``ragged_latent_decode_attention`` with ``keep`` (TPU interpreter)
    against the masked einsums over the slab: a slot whose first tile holds
    none of its chosen positions, a dead slot, a slot of a tile and one."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, H, dk, dv, S = 3, 8, 48, 32, 384
    c = jax.random.normal(ks[0], (2, B, 1, dk, S))
    q = jax.random.normal(ks[1], (B, H, dk))
    n = jnp.asarray([300, 0, 129])
    keep = (jax.random.uniform(ks[2], (B, S)) < 0.2).at[0, :128].set(False)
    plan = attention.ragged_decode_plan(n, S // attention.DECODE_TILE)
    want = attention.latent_slab_attention(
        q, c, jnp.int32(1), (jnp.arange(S)[None, :] < n[:, None]) & keep,
        scale=0.2, dv=dv)
    got = attention.ragged_latent_decode_attention(
        q, c, jnp.int32(1), plan, scale=0.2, dv=dv, keep=keep,
        interpret=pltpu.InterpretParams())
    for name, g, w in zip(("acc", "m", "d"), got, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5, err_msg=name)


# a sliding layer's ring through the latent kernel: (where the slot stood
# when the chunk began, the step's position) of slot 0, window 100 in a ring of
# 256 entries (two tiles); slot 1 beside it, mid-ring, so that the walk crosses
# slots
RING_READS = {
    "shorter_than_the_window": (60, 63),
    "wrapped": (700, 705),
    "sits_the_chunk_out": (0, 0),
    "at_a_tiles_edge": (128, 128),
    "at_the_rings_edge": (256, 259),
}


@pytest.mark.parametrize("case", list(RING_READS))
def test_ring_read_through_the_latent_kernel(case, lowered_for_tpu):
    """``decode_chunk``'s read of a latent ring as a chip runs it
    (``_latent_cache_scores``: the latent kernel, TPU interpreter, over the
    tiles of the entries a slot's ring holds, the step's window its ``keep``)
    against ``latent_slab_attention`` under ``_ring_mask`` alone, which is the
    parent's read: a ring not yet full, one that has wrapped, a slot that sits
    the chunk out (``acc = 0, d = 0`` and no tile listed), a ring that ends
    exactly on a tile's edge and on its own."""
    window = 100
    ring = gen.ring_positions(window)
    assert ring == 256
    live = jnp.asarray([RING_READS[case][0], 150, 0])
    pos = jnp.asarray([RING_READS[case][1], 152, 0])
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    B, H, dk, dv = 3, 8, 48, 32
    c = jax.random.normal(ks[0], (2, B, 1, dk, ring))
    q = jax.random.normal(ks[1], (B, 1, H, dk))
    held = jnp.minimum(live, ring)
    plan = attention.ragged_decode_plan(held, ring // attention.DECODE_TILE)
    assert int(plan[0]) == sum(-(-int(n) // 128) for n in held)
    mask = gen._ring_mask(live, pos, window, ring)
    # what the window lets the slot attend, counted by hand
    assert int(mask[0].sum()) == max(0, min(
        int(live[0]), window - 1 - int(pos[0] - live[0])))
    want = attention.latent_slab_attention(
        q[:, 0], c, jnp.int32(1), mask, scale=0.2, dv=dv)
    got = gen._latent_cache_scores(
        q, c, jnp.int32(1), held, plan, scale=0.2, dv=dv, keep=mask)
    for name, g, w in zip(("acc", "m", "d"), got, want):
        np.testing.assert_allclose(g[:, 0], w, rtol=2e-5, atol=2e-5, err_msg=name)
    if not int(live[0]):
        assert float(jnp.abs(got[0][0]).max()) == 0.0 == float(got[2][0].max())


def test_band_attention_walks_large_bands_a_block_at_a_time(monkeypatch):
    """Window 513 over 2,048 positions: blocks of 1,024 (the smallest whole
    tiles that hold the window and divide the sequence), all at once and,
    where the scores would be large, one after another; values narrower than
    keys."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k = (jax.random.normal(key, (1, 2, 2048, 32)) for key in ks[:2])
    v = jax.random.normal(ks[2], (1, 2, 2048, 16))
    i, j = jnp.arange(2048)[:, None], jnp.arange(2048)[None, :]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 32 ** -0.5
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where((j <= i) & (j > i - 513), s, -jnp.inf), -1), v)
    at_once = attention.band_attention(q, k, v, window=513)
    monkeypatch.setattr(attention, "_BAND_SCORES_BYTES", 1)
    by_block = attention.band_attention(q, k, v, window=513)
    assert np.abs(np.asarray(at_once) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(by_block) - np.asarray(want)).max() < 1e-5


@pytest.fixture
def serve_instance():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield serve.start(serve.HTTPOptions(host="127.0.0.1", port=0))
    serve.shutdown()
    ray_tpu.shutdown()


def test_llm_deployment_end_to_end(serve_instance, model):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import llm_deployment

    dep = llm_deployment(
        "dots3_note", "tiny",
        engine_kwargs=dict(n_slots=2, max_new_tokens=6, decode_chunk_steps=3,
                           prefill_buckets=(16,)),
        config_kwargs=dict(dtype=jnp.float32, **TINY["dots3_note"]))
    handle = serve.run(dep.bind(), port=0)
    prompt = [3, 5, 7, 11, 2, 9, 4, 8, 1, 6, 12]  # 11 positions: over top-8
    outs = ray_tpu.get(
        [handle.remote({"tokens": prompt, "max_new_tokens": 6})
         for _ in range(3)], timeout=300)
    cfg, params = model  # the replica's: the same config, initialised alike
    one, = one_shot(params, cfg, [prompt], 6)
    assert all(o["tokens"] == one for o in outs)
    stats = ray_tpu.get(handle.perf_stats.remote(), timeout=60)
    assert stats["dsa"]["decode"]["rows_selected"] > 0
