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
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.models import generate as gen
from ray_tpu.models import gpt2, llama


def _greedy_reference(apply_fn, params, cfg, prompt, n_new):
    """Teacher-forcing loop: full forward each step, argmax last logit."""
    toks = list(prompt)
    for _ in range(n_new):
        logits = apply_fn(params, jnp.asarray([toks]), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def _model(family, seed=0, block_scale=1, **cfg_kw):
    """A tiny f32 model.  As initialised it repeats its last token whatever
    it attends (tied embeddings, small blocks); ``block_scale=8`` makes the
    layers' matrices large enough that the answer depends on the context,
    so that a wrong or stale K/V column changes a token."""
    mod = gen.FAMILIES[family]
    cfg = mod.Config.tiny(dtype=jnp.float32, **cfg_kw)
    params = mod.init(cfg, jax.random.PRNGKey(seed))
    if "blocks" in params:  # a family that lists its layers scales them itself
        params["blocks"] = jax.tree.map(
            lambda w: w * block_scale if w.ndim >= 3 else w, params["blocks"])
    return cfg, params, mod.apply


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_cached_decode_matches_full_forward(family):
    cfg, params, apply_fn = _model(family)
    prompt = [3, 17, 5, 9, 2, 11]
    want = _greedy_reference(apply_fn, params, cfg, prompt, 8)
    out = gen.generate(
        params, cfg, jnp.asarray([prompt]), jnp.asarray([len(prompt)]),
        max_new_tokens=8)
    assert [int(t) for t in out[0]] == want


def test_batched_slots_with_different_lengths():
    """Two prompts of different lengths decode in one batch exactly as they
    would alone (padding + per-slot positions change nothing)."""
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init(cfg, jax.random.PRNGKey(1))
    p_a, p_b = [5, 9, 2], [7, 1, 4, 8, 3, 6, 12]
    solo = {}
    for name, p in (("a", p_a), ("b", p_b)):
        out = gen.generate(params, cfg, jnp.asarray([p]),
                           jnp.asarray([len(p)]), max_new_tokens=6)
        solo[name] = [int(t) for t in out[0]]
    pad = max(len(p_a), len(p_b))
    batch = jnp.asarray([p_a + [0] * (pad - len(p_a)), p_b])
    out = gen.generate(params, cfg, batch,
                       jnp.asarray([len(p_a), len(p_b)]), max_new_tokens=6)
    assert [int(t) for t in out[0]] == solo["a"]
    assert [int(t) for t in out[1]] == solo["b"]


def test_eos_freezes_slot():
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    params = gpt2.init(cfg, jax.random.PRNGKey(2))
    prompt = jnp.asarray([[3, 17, 5, 9]])
    out = gen.generate(params, cfg, prompt, jnp.asarray([4]),
                       max_new_tokens=10)
    toks = [int(t) for t in out[0]]
    # re-run declaring the 3rd emitted token as EOS: everything after must
    # repeat it (the slot went inactive)
    eos = toks[2]
    out2 = gen.generate(params, cfg, prompt, jnp.asarray([4]),
                        max_new_tokens=10, eos_id=eos)
    toks2 = [int(t) for t in out2[0]]
    assert toks2[:3] == toks[:3]
    assert all(t == eos for t in toks2[2:])


def test_prefill_then_chunked_decode_equals_one_shot():
    """The serving path (prefill + several decode_chunk calls) must equal
    one-shot generate."""
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init(cfg, jax.random.PRNGKey(3))
    prompt = [9, 4, 7, 2, 5]
    one = gen.generate(params, cfg, jnp.asarray([prompt]),
                       jnp.asarray([len(prompt)]), max_new_tokens=9)

    cache = gen.init_cache(cfg, 1, len(prompt) + 9)
    last, cache = gen.prefill(
        params, cfg, jnp.asarray([prompt]), jnp.asarray([len(prompt)]),
        cache, jnp.int32(0))
    tok = gen.sample_logits(last, jax.random.PRNGKey(0))
    emitted = [int(tok[0])]
    active = jnp.ones((1,), bool)
    key = jax.random.PRNGKey(0)
    for _ in range(2):  # 2 chunks of 4 = the remaining 8 tokens
        chunk, cache, active, key = gen.decode_chunk(
            params, cfg, cache, tok, active, key, steps=4)
        emitted.extend(int(t) for t in np.asarray(chunk[0]))
        tok = chunk[:, -1]
    assert emitted == [int(t) for t in one[0]]


# ---------------------------------------------------------------------------
# the chunk-local K/V buffer and its once-a-chunk flush (PR 28): inside a
# chunk no step writes the cache; what the NEXT chunk reads is the flush
# ---------------------------------------------------------------------------

# one program a config and chunk length, whatever the bound ``n``
_CUT_CHUNK = jax.jit(gen.decode_chunk, static_argnums=1,
                     static_argnames=("steps", "eos_id"))


class _Slots:
    """The engine's use of the programs, on the host: a cache of ``n`` slots
    (the last one the scratch slot), prompts admitted into any of them, all
    decoded together ``steps`` tokens a chunk."""

    def __init__(self, family, n, max_len, *, eos_id=None, **cfg_kw):
        self.cfg, self.params, self.apply = _model(
            family, seed=4, block_scale=8, **cfg_kw)
        self.n, self.eos_id = n, eos_id
        self.cache = gen.init_cache(self.cfg, n, max_len)
        self.tok = jnp.zeros((n,), jnp.int32)
        self.active = np.zeros((n,), bool)
        self.key = jax.random.PRNGKey(0)
        self.out, self._prompts = {}, {}

    def admit(self, slot, prompt, bucket):
        """Prefill ``prompt`` padded to ``bucket`` into ``slot``; the padding
        row of a two-row admission parks in the scratch slot, as the engine's
        does."""
        toks = np.zeros((2, bucket), np.int32)
        toks[0, :len(prompt)] = prompt
        toks[1] = 1
        last, self.cache = gen.prefill_at(
            self.params, self.cfg, jnp.asarray(toks),
            jnp.asarray([len(prompt), bucket]), self.cache,
            jnp.asarray([slot, self.n - 1]))
        first = int(jnp.argmax(last[0]))
        self.tok = self.tok.at[slot].set(first)
        self.active[slot] = first != self.eos_id
        self.out[slot], self._prompts[slot] = [first], prompt

    def decode(self, steps, n=None):
        """One chunk of ``steps``; ``n``: CUT to ``n`` steps, through a
        jitted program whose bound is an argument, as the engine's is."""
        was = self.active.copy()
        chunk, cut = (gen.decode_chunk, {}) if n is None else (
            _CUT_CHUNK, {"n": jnp.int32(n)})
        emitted, self.cache, active, self.key = chunk(
            self.params, self.cfg, self.cache, self.tok,
            jnp.asarray(self.active), self.key, steps=steps,
            eos_id=self.eos_id, **cut)
        emitted = np.asarray(emitted)
        self.tok = jnp.asarray(emitted[:, -1])
        self.active = np.array(active)
        for slot in np.flatnonzero(was):
            row = [int(t) for t in emitted[slot, :n]]
            if self.eos_id in row:  # what follows an EOS repeats it
                row = row[:row.index(self.eos_id) + 1]
            self.out[slot] += row
        return emitted

    def assert_greedy(self, slot, n_new):
        """The slot's answer is the full forward's greedy one, by teacher
        forcing: ONE forward over prompt + answer; by induction the answer
        is greedy iff every token is the argmax after the tokens before."""
        prompt, out = self._prompts[slot], self.out[slot]
        assert len(out) == n_new
        logits = self.apply(
            self.params, jnp.asarray([prompt + out[:-1]]), self.cfg)
        assert out == [
            int(t) for t in jnp.argmax(logits[0, len(prompt) - 1:], -1)], slot


@pytest.fixture
def lowered_for_tpu(monkeypatch):
    """``lax.platform_dependent`` takes its ``tpu`` branch, and Pallas calls
    run in the TPU interpreter: the decode program a chip would run, here."""
    monkeypatch.setattr(
        gen.lax, "platform_dependent",
        lambda *args, tpu, default: tpu(*args))
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.fixture(params=["cpu", "lowered_for_tpu"])
def positions(request):
    """The chunk cases below run twice: as the CPU runs them (masked einsums
    over the slab, a slice update a slot) and as a chip does (the ragged read
    and the flush kernel, through ``lowered_for_tpu``).  Gives the cache
    length a case asks for as the path needs it: the kernels are chosen for
    a cache of whole 128-position tiles, as the engine's always is."""
    if request.param == "cpu":
        return lambda n: n
    request.getfixturevalue("lowered_for_tpu")
    return lambda n: -(-n // 128) * 128


@pytest.mark.parametrize("family", ["gpt2", "llama", "exaone_moe", "kimi_k2"])
@pytest.mark.parametrize("chunks", [2, 3])
def test_chunked_slots_at_different_positions(family, chunks, positions):
    """Slots at different positions in one batch, an idle slot and the
    scratch slot beside them, over two and three consecutive chunks: every
    slot's tokens equal the full forward's (the flush of chunk n is what
    chunk n+1 attends)."""
    eng = _Slots(family, 5, positions(8 + 3 * 4))
    prompts = {0: [3, 17, 5], 1: [9, 4, 7, 2, 5, 11, 6, 8], 3: [12, 1, 6, 3, 9]}
    for slot, prompt in prompts.items():
        eng.admit(slot, prompt, 8)
    for _ in range(chunks):
        eng.decode(4)
    for slot in prompts:
        eng.assert_greedy(slot, 1 + 4 * chunks)
    assert [int(p) for p in eng.cache["pos"]] == [
        3 + 4 * chunks, 8 + 4 * chunks, 0, 5 + 4 * chunks, 8]


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_slot_admitted_between_chunks(family, positions):
    """A slot that joins while another is mid-answer (the engine admits
    between chunks): the newcomer's prefill does not disturb the columns
    the other slot flushed, and both match the full forward."""
    eng = _Slots(family, 3, positions(8 + 12))
    a, b = [5, 9, 2, 14], [7, 1, 4, 8, 3, 6]
    eng.admit(0, a, 8)
    eng.decode(4)
    eng.admit(1, b, 8)
    eng.decode(4)
    eng.decode(4)
    eng.assert_greedy(0, 13)
    eng.assert_greedy(1, 9)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_eos_mid_chunk_then_slot_reused(family, positions):
    """EOS in the middle of a chunk freezes the slot's ``pos``; the columns
    it flushed after that lie beyond ``pos``.  Re-prefilled with a SHORTER
    prompt and decoded again, the slot must not attend them."""
    probe = _Slots(family, 2, positions(8 + 12))
    first = [3, 17, 5, 9, 2, 11, 4]
    probe.admit(0, first, 8)
    free_run = probe.decode(6)[0]
    eos = int(free_run[2])  # the 4th token of the answer ends it
    assert eos not in [probe.out[0][0]] + [int(t) for t in free_run[:2]]

    eng = _Slots(family, 2, positions(8 + 12), eos_id=eos)
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
    eng.assert_greedy(0, 13)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_chunk_straddles_a_128_position_boundary(family, positions):
    """A chunk whose ``pos0 .. pos0 + steps`` crosses position 128 (a lane
    tile of the S-minor cache on the chip: the flush kernel merges two
    tiles), next to a slot that ends its chunk exactly on the boundary."""
    eng = _Slots(family, 3, positions(160), max_seq_len=160)
    rng = np.random.default_rng(0)
    long_a = [int(t) for t in rng.integers(1, 200, size=123)]
    long_b = [int(t) for t in rng.integers(1, 200, size=120)]
    eng.admit(0, long_a, 128)
    eng.admit(1, long_b, 128)
    eng.decode(8)   # 123..131 crosses; 120..128 ends on the boundary
    eng.decode(8)   # 128..136 starts on it
    eng.assert_greedy(0, 17)
    eng.assert_greedy(1, 17)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_chunk_of_one_and_of_none(family):
    """``steps=1`` is the one-step form; ``steps=0`` returns the cache as
    it came (``generate(max_new_tokens=1)`` asks for it)."""
    eng = _Slots(family, 2, 8 + 4)
    prompt = [9, 4, 7, 2, 5]
    eng.admit(0, prompt, 8)
    for _ in range(3):
        eng.decode(1)
    eng.assert_greedy(0, 4)
    none, cache, _, _ = gen.decode_chunk(
        eng.params, eng.cfg, eng.cache, eng.tok, jnp.asarray(eng.active),
        eng.key, steps=0)
    assert none.shape == (2, 0) and cache is eng.cache
    one = gen.generate(eng.params, eng.cfg, jnp.asarray([prompt]),
                       jnp.asarray([len(prompt)]), max_new_tokens=1)
    assert [int(t) for t in one[0]] == eng.out[0][:1]


def test_flush_that_would_not_fit_is_refused():
    """A dynamic_update_slice clamps silently, so a chunk longer than the
    cache is refused where the sizes are static."""
    eng = _Slots("gpt2", 1, 8)
    eng.admit(0, [1, 2, 3], 4)
    with pytest.raises(AssertionError):
        gen.decode_chunk(eng.params, eng.cfg, eng.cache, eng.tok,
                         jnp.asarray(eng.active), eng.key, steps=9)


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
        params["blocks"] = jax.tree.map(  # context-dependent, as in _model
            lambda w: w * 8 if w.ndim >= 3 else w, params["blocks"])
        out = gen.generate(
            params, cfg, jnp.asarray([prompt]), jnp.asarray([len(prompt)]),
            max_new_tokens=8)
        assert [int(t) for t in out[0]] == _greedy_reference(
            nope.apply, params, cfg, prompt, 8)
    else:
        eng = llm.GenerationEngine(
            cfg, n_slots=2, max_new_tokens=6, decode_chunk_steps=3,
            prefill_buckets=(8,)).start()
        try:
            got = eng.generate(prompt, timeout=120)
        finally:
            eng.stop()
        assert got == _greedy_reference(
            nope.apply, llm._default_init(cfg, 0), cfg, prompt, 6)
