"""KV-cache generation: decode must reproduce the full forward exactly.

The reference has no decode engine (serving calls a plain user forward,
``python/ray/serve/_private/replica.py:250``); these tests pin our cache
semantics instead: greedy cached decode == greedy full-recompute decode,
per-slot positions, EOS freezing.  f32 configs so argmax never flips on
accumulation-order noise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_harness import (
    LONG,
    SHORT,
    Slots,
    decode_chunk,
    greedy_reference,
    one_shot,
    served,
    tiny_model,
)

from ray_tpu.models import generate as gen
from ray_tpu.models import gpt2

pytestmark = pytest.mark.usefixtures("kept_engine_programs")


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_cached_decode_matches_full_forward(family):
    cfg, params = tiny_model(family)
    prompt = [3, 17, 5, 9, 2, 11]
    want = greedy_reference(family, params, cfg, prompt, 8)
    assert one_shot(params, cfg, [prompt], 8) == [want]


def test_batched_slots_with_different_lengths():
    """Two prompts of different lengths decode in one batch exactly as they
    would alone (padding + per-slot positions change nothing)."""
    cfg, params = tiny_model("llama", seed=1)
    p_a, p_b = [5, 9, 2], [7, 1, 4, 8, 3, 6, 12]
    solo = [one_shot(params, cfg, [p], 6, pad_to=1)[0] for p in (p_a, p_b)]
    assert one_shot(params, cfg, [p_a, p_b], 6) == solo


def test_eos_freezes_slot():
    cfg, params = tiny_model("gpt2", seed=2)
    toks, = one_shot(params, cfg, [[3, 17, 5, 9]], 10)
    # re-run declaring the 3rd emitted token as EOS: everything after must
    # repeat it (the slot went inactive)
    eos = toks[2]
    toks2, = one_shot(params, cfg, [[3, 17, 5, 9]], 10, eos_id=eos)
    assert toks2[:3] == toks[:3]
    assert all(t == eos for t in toks2[2:])


def test_prefill_then_chunked_decode_equals_one_shot():
    """The serving path (prefill + several decode_chunk calls) must equal
    one-shot generate."""
    cfg, params = tiny_model("llama", seed=3)
    prompt = [9, 4, 7, 2, 5]
    one, = one_shot(params, cfg, [prompt], 9)

    cache = gen.init_cache(cfg, 1, len(prompt) + 9)
    last, cache = gen.prefill(
        params, cfg, jnp.asarray([prompt]), jnp.asarray([len(prompt)]),
        cache, jnp.int32(0))
    tok = gen.sample_logits(last, jax.random.PRNGKey(0))
    emitted = [int(tok[0])]
    active = jnp.ones((1,), bool)
    key = jax.random.PRNGKey(0)
    for _ in range(2):  # 2 chunks of 4 = the remaining 8 tokens
        chunk, cache, active, key, _ = decode_chunk(
            params, cfg, cache, tok, active, key, steps=4)
        emitted.extend(int(t) for t in np.asarray(chunk[0]))
        tok = chunk[:, -1]
    assert emitted == one


# ---------------------------------------------------------------------------
# the chunk-local K/V buffer and its once-a-chunk flush (PR 28): inside a
# chunk no step writes the cache; what the NEXT chunk reads is the flush
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", list(gen.FAMILIES))
@pytest.mark.parametrize("chunks", [2, 3])
def test_chunked_slots_at_different_positions(family, chunks, positions):
    """Slots at different positions in one batch, an idle slot and the
    scratch slot beside them, over two and three consecutive chunks: every
    slot's tokens equal the full forward's (the flush of chunk n is what
    chunk n+1 attends)."""
    eng = Slots(family, positions(SHORT))
    prompts = {0: [3, 17, 5], 1: [9, 4, 7, 2, 5, 11, 6, 8], 3: [12, 1, 6, 3, 9]}
    for slot, prompt in prompts.items():
        eng.admit(slot, prompt, 8)
    for _ in range(chunks):
        eng.decode(4)
    eng.assert_greedy(dict.fromkeys(prompts, 1 + 4 * chunks))
    assert [int(p) for p in eng.cache["pos"]] == [
        3 + 4 * chunks, 8 + 4 * chunks, 0, 5 + 4 * chunks, 8]


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_slot_admitted_between_chunks(family, positions):
    """A slot that joins while another is mid-answer (the engine admits
    between chunks): the newcomer's prefill does not disturb the columns
    the other slot flushed, and both match the full forward."""
    eng = Slots(family, positions(SHORT))
    a, b = [5, 9, 2, 14], [7, 1, 4, 8, 3, 6]
    eng.admit(0, a, 8)
    eng.decode(4)
    eng.admit(1, b, 8)
    eng.decode(4)
    eng.decode(4)
    eng.assert_greedy({0: 13, 1: 9})


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_eos_mid_chunk_then_slot_reused(family, positions):
    """EOS in the middle of a chunk freezes the slot's ``pos``; the columns
    it flushed after that lie beyond ``pos``.  Re-prefilled with a SHORTER
    prompt and decoded again, the slot must not attend them."""
    probe = Slots(family, positions(SHORT))
    first = [3, 17, 5, 9, 2, 11, 4]
    probe.admit(0, first, 8)
    free_run = probe.decode(6)[0]
    eos = int(free_run[2])  # the 4th token of the answer ends it
    assert eos not in [probe.out[0][0]] + [int(t) for t in free_run[:2]]

    eng = Slots(family, positions(SHORT), eos_id=eos)
    eng.admit(0, first, 8)
    row = eng.decode(6)[0]
    assert [int(t) for t in row] == [int(t) for t in free_run[:3]] + [eos] * 3
    assert int(eng.cache["pos"][0]) == len(first) + 3 and not eng.active[0]
    # an idle chunk: on the CPU the frozen slot flushes garbage again, at
    # its frozen pos; the flush kernel does not visit it
    eng.decode(6)
    assert int(eng.cache["pos"][0]) == len(first) + 3

    second = [6, 2]
    eng.eos_id = None
    eng.admit(0, second, 4)  # bucket 4: columns 4.. keep the old request's
    eng.decode(6)
    eng.decode(6)
    eng.assert_greedy({0: 13})


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_chunk_straddles_a_128_position_boundary(family, positions):
    """A chunk whose ``pos0 .. pos0 + steps`` crosses position 128 (a lane
    tile of the S-minor cache on the chip: the flush kernel merges two
    tiles), next to a slot that ends its chunk exactly on the boundary."""
    eng = Slots(family, positions(LONG))
    rng = np.random.default_rng(0)
    long_a = [int(t) for t in rng.integers(1, 200, size=123)]
    long_b = [int(t) for t in rng.integers(1, 200, size=120)]
    eng.admit(0, long_a, 128)
    eng.admit(1, long_b, 128)
    eng.decode(8)   # 123..131 crosses; 120..128 ends on the boundary
    eng.decode(8)   # 128..136 starts on it
    eng.assert_greedy({0: 17, 1: 17})


def test_a_latent_ring_of_whole_tiles_is_read_by_tile(positions):
    """dots3-note with a window of 64: its rings are one whole tile (128
    entries), so a chunk reads them as it reads the latent slabs: the tiles of
    the entries a live slot's ring holds (``decode_chunk``'s ring plan; as a
    chip runs it the latent kernel with the step's window as its mask, on the
    CPU the masked einsums).  A ring that has wrapped (150 > 128), one shorter
    than the window, one exactly full, an idle slot and the scratch slot
    beside them, over two chunks: every token is the full forward's."""
    eng = Slots("dots3_note", positions(LONG), sliding_window=64)
    assert eng.cache["c_ring"].shape[-1] == 128
    assert gen.ring_read_by_tile(eng.cache, eng.cfg)
    rng = np.random.default_rng(1)
    prompts = {0: 150, 1: 20, 3: 128}
    for slot, length in prompts.items():
        eng.admit(slot, [int(t) for t in rng.integers(1, 200, size=length)],
                  -(-length // 32) * 32)
    eng.decode(4)
    eng.decode(4)
    eng.assert_greedy(dict.fromkeys(prompts, 9))
    assert [int(p) for p in eng.cache["pos"]][:4] == [158, 28, 0, 136]


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_chunk_of_one_and_of_none(family):
    """``steps=1`` is the one-step form; ``steps=0`` returns the cache as
    it came (``generate(max_new_tokens=1)`` asks for it)."""
    eng = Slots(family, SHORT)
    prompt = [9, 4, 7, 2, 5]
    eng.admit(0, prompt, 8)
    for _ in range(3):
        eng.decode(1)
    eng.assert_greedy({0: 4})
    none, cache, _, _ = gen.decode_chunk(
        served(eng.params, eng.cfg), eng.cfg, eng.cache, eng.tok, jnp.asarray(eng.active),
        eng.key, steps=0)
    assert none.shape == (eng.n, 0) and cache is eng.cache
    assert one_shot(eng.params, eng.cfg, [prompt], 1) == [eng.out[0][:1]]


def test_flush_that_would_not_fit_is_refused():
    """A dynamic_update_slice clamps silently, so a chunk longer than the
    cache is refused where the sizes are static."""
    eng = Slots("gpt2", SHORT)
    eng.admit(0, [1, 2, 3], 8)
    with pytest.raises(AssertionError):
        gen.decode_chunk(eng.params, eng.cfg, eng.cache, eng.tok,
                         jnp.asarray(eng.active), eng.key, steps=SHORT + 1)


# -- the seam: a new family is one module -------------------------------------

class _NoPEConfig(gpt2.TransformerConfig):
    """GPT-2 without its position table (causal attention alone orders it)."""


def _nope_family():
    """A third family from the existing pieces: GPT-2's block, init and head,
    the position table left out.  Everything generate.py and the engine ask
    of a family, and its own full forward (``apply``) to compare with."""
    import types

    from ray_tpu.models.transformer import apply_stack

    def init(cfg, key):
        return {k: v for k, v in gpt2.init(cfg, key).items() if k != "wpe"}

    def embed(params, tokens, cfg, positions=None, mesh=None, rules=None):
        return params["wte"][tokens].astype(cfg.dtype)

    def apply(params, tokens, cfg):
        x, _ = apply_stack(embed(params, tokens, cfg), params["blocks"], cfg)
        return gpt2.unembed(params, x, cfg)

    tiny = lambda **kw: _NoPEConfig(**{  # noqa: E731
        **vars(gpt2.GPT2Config.tiny()), "dtype": jnp.float32, **kw})
    return types.SimpleNamespace(
        Config=_NoPEConfig, SIZES={"tiny": tiny}, init=init, embed=embed,
        block=gpt2.block, unembed=gpt2.unembed, kv_heads=gpt2.kv_heads,
        apply=apply)


@pytest.fixture
def nope():
    """The family, in the table for the length of one test."""
    fam = gen.FAMILIES["nope"] = _nope_family()
    try:
        yield fam
    finally:
        del gen.FAMILIES["nope"]


@pytest.mark.parametrize("through", ["generate", "engine"])
def test_a_new_family_is_one_module(nope, through):
    """Registered in the table and nowhere else, the family generates: cached
    decode equals its own full forward token for token, and an engine built
    on its config alone (make_config, default init) answers a request."""
    from ray_tpu.serve import llm

    cfg = llm.make_config("nope", "tiny")
    assert gen.family_of(cfg) is nope and "wpe" not in llm._default_init(cfg, 0)
    prompt = [3, 17, 5, 9, 2, 11]
    if through == "generate":
        params = nope.init(cfg, jax.random.PRNGKey(0))
        params["blocks"] = jax.tree.map(  # context-dependent, as tiny_model's
            lambda w: w * 8 if w.ndim >= 3 else w, params["blocks"])
        assert one_shot(params, cfg, [prompt], 8) == [greedy_reference(
            "nope", params, cfg, prompt, 8)]
    else:
        eng = llm.GenerationEngine(
            cfg, n_slots=2, max_new_tokens=6, decode_chunk_steps=3,
            prefill_buckets=(8,)).start()
        try:
            got = eng.generate(prompt, timeout=120)
        finally:
            eng.stop()
        assert got == greedy_reference(
            "nope", llm._default_init(cfg, 0), cfg, prompt, 6)
