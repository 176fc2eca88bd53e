"""A prompt longer than one PART goes into its slot a part at a time, each part
attending what the earlier ones left in the cache: the model's continuation
(``generate.prefill_at``'s ``offsets``) against the whole call, the flash
kernel under a runtime key length against the masked XLA reference, and the
engine that schedules the parts between its decode chunks."""

import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_harness import engine, prefill_at, run_engine, served, tiny_model

import ray_tpu.ops.attention  # noqa: F401  (the module, not ops' function)
from ray_tpu._private import events as events_mod
from ray_tpu.models import generate as gen
from ray_tpu.ops import dsa
from ray_tpu.scripts import cli
from ray_tpu.serve import llm

attention = sys.modules["ray_tpu.ops.attention"]
pytestmark = pytest.mark.usefixtures("kept_engine_programs")

CONTINUES = ["gpt2", "exaone_moe", "kimi_k2", "dots3_note"]
# (tokens a part, the prompt's): parts under both tiny rings (16 positions for
# exaone_moe's window of 8, 10 for dots3_note's of 5), one over them, and last
# parts of 3 of 4, 7 of 8 and 5 of 32 tokens
PARTS = [(4, 23, jnp.float32), (8, 23, jnp.float32), (32, 37, jnp.float32),
         (8, 23, jnp.bfloat16)]


def _in_parts(params, cfg, cache, prompt, part, slot, bound):
    """``prompt`` into ``slot`` of ``cache`` a ``part`` at a time; the last
    part's logits."""
    # ONE program for every part, the offset a runtime value (as the engine's)
    for at in range(0, len(prompt), part):
        row = np.zeros((1, part), np.int32)
        own = prompt[at:at + part]
        row[0, :len(own)] = own
        logits, cache, _ = prefill_at(
            params, cfg, jnp.asarray(row), jnp.asarray([len(own)]), cache,
            jnp.asarray([slot]), offsets=jnp.asarray([at]), bound=bound)
    return logits, cache


@pytest.mark.parametrize(
    "part,n,dtype", PARTS, ids=lambda v: v if isinstance(v, int) else v.__name__)
@pytest.mark.parametrize("family", CONTINUES)
def test_a_prompt_in_parts_leaves_what_the_whole_call_leaves(family, part, n, dtype):
    """The same cache (slab, ring, index keys; of a ring the entries that
    hold a position), the same ``pos``, the same last logits and first token
    as ONE call over the whole prompt, whatever the part."""
    cfg, params = tiny_model(family, dtype=dtype)
    prompt = np.random.default_rng(1).integers(1, cfg.vocab_size, n).tolist()
    whole = np.zeros((1, 64), np.int32)
    whole[0, :n] = prompt
    want_logits, want, _ = prefill_at(
        params, cfg, jnp.asarray(whole), jnp.asarray([n]),
        gen.init_cache(cfg, 3, 96), jnp.asarray([1]))
    # the static bound: 64 cached positions, whole parts of every size here
    got_logits, got = _in_parts(
        params, cfg, gen.init_cache(cfg, 3, 96), prompt, part, 1, 64)
    tol = 2e-5 if dtype == jnp.float32 else 0.06
    assert int(got["pos"][1]) == int(want["pos"][1]) == n
    assert set(got) == set(want)
    for name in set(want) - {"pos"}:
        a, b = (np.asarray(c[name][:, 1], np.float32) for c in (want, got))
        if name.endswith("_ring"):
            held = np.asarray(gen._ring_holds(jnp.asarray([n]), a.shape[-1]))[0] >= 0
            a, b = a[..., held], b[..., held]
        else:
            a, b = a[..., :n], b[..., :n]
        np.testing.assert_allclose(b, a, atol=tol, rtol=tol, err_msg=name)
    np.testing.assert_allclose(got_logits, want_logits, atol=tol * 4, rtol=tol)
    if dtype == jnp.float32:
        assert int(got_logits.argmax()) == int(want_logits.argmax())
    # the other slots were left alone
    for name in set(want) - {"pos"}:
        assert not np.asarray(got[name][:, 0]).any()
        assert not np.asarray(got[name][:, 2]).any()


def test_rows_of_one_call_continue_at_their_own_offsets():
    """A call's rows are parts of different prompts, each at its own offset."""
    cfg, params = tiny_model("exaone_moe")
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (20, 11)]
    cache = gen.init_cache(cfg, 3, 96)
    alone = [_in_parts(params, cfg, gen.init_cache(cfg, 3, 96), p, 8, s, 32)
             for s, p in enumerate(prompts)]
    # first parts apart (8 and 3 tokens in), then the rest together
    _, cache = _in_parts(params, cfg, cache, prompts[0][:16], 8, 0, 32)
    _, cache = _in_parts(params, cfg, cache, prompts[1][:8], 8, 1, 32)
    rows = np.zeros((2, 8), np.int32)
    rows[0, :4], rows[1, :3] = prompts[0][16:], prompts[1][8:]
    logits, cache, _ = prefill_at(
        params, cfg, jnp.asarray(rows), jnp.asarray([4, 3]), cache,
        jnp.asarray([0, 1]), offsets=jnp.asarray([16, 8]), bound=32)
    assert np.asarray(cache["pos"][:2]).tolist() == [20, 11]
    for s, (want, _) in enumerate(alone):
        np.testing.assert_allclose(logits[s], want[0], atol=5e-5, rtol=1e-4)


# -- what a part prepares of the cached positions -------------------------------

# a part of 8 tokens under a static bound of 72 cached positions: no other axis
# of a tiny model is 72 long, so a shape that holds it holds every position
LIVE_PART, LIVE_BOUND = 8, 72

def _opaque_middle(q, k, v, first, keep=None, scale=None):
    """``continued_attention`` as the chip's compiler sees it: a call it
    cannot look into (a host callback for the kernel), handed the keys and
    values by position up to the static bound, which reads none beyond a
    row's last live position."""
    def on_host(q, k, v, first, keep):
        if k.ndim == 5:  # in blocks of positions (``live_blocks``)
            k, v = (np.moveaxis(t, 0, 2).reshape(*t.shape[1:3], -1, t.shape[-1])
                    for t in (k, v))
        out = np.zeros((*q.shape[:3], v.shape[-1]), np.float32)
        for b, at in enumerate(first):
            n = int(at) + q.shape[2]
            s = np.einsum("hqd,hkd->hqk", q[b], k[b, :, :n]) * scale
            seen = np.arange(n)[None, :] <= int(at) + np.arange(q.shape[2])[:, None]
            if keep.size:
                seen = seen & (keep[b, :, :n] != 0)
            s = np.where(seen[None], s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            out[b] = np.einsum("hqk,hkd->hqd", p / p.sum(-1, keepdims=True),
                               v[b, :, :n])
        return out.astype(q.dtype)

    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return jax.pure_callback(
        on_host, jax.ShapeDtypeStruct((*q.shape[:3], v.shape[-1]), q.dtype),
        q, k, v, first, jnp.zeros((0,), jnp.int8) if keep is None else keep)


def _every_position(prepare, rows, live, block):
    """The static form, the parent's: ``prepare`` over ALL the bound's
    positions at once, whatever is live (``attention.live_blocks``' contract
    with nothing left unwritten).  Kept as the reference."""
    return prepare(*rows)


@pytest.fixture(scope="module")
def part_forms():
    """``of(family) -> (live, static, operations)``: the part program of the
    family's tiny model compiled ONCE a module in both forms (one row, a part
    of 8 under a bound of 72; the offset a runtime argument), the attention
    middle a call the compiler cannot look into (``_opaque_middle``), so that
    what is left over the bound is what is prepared FOR it; ``operations``:
    the live form's matmuls, broadcasts and concatenations
    (``sharding.operation_profile``)."""
    from ray_tpu.parallel.sharding import operation_profile

    def compiled(family):
        cfg, params = tiny_model(family)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        return jax.jit(
            lambda params, toks, lens, cache, slots, offsets: gen.prefill_at(
                params, cfg, toks, lens, cache, slots, offsets, LIVE_BOUND)
        ).lower(served(params, cfg), i32(1, LIVE_PART), i32(1), gen.init_cache(cfg, 3, 96),
                i32(1), i32(1)).compile()

    kept = {}

    def of(family):
        if family not in kept:
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(gen, "continued_attention", _opaque_middle)
                live = compiled(family)
                patch.setattr(gen, "live_blocks", _every_position)
                static = compiled(family)
            kept[family] = (live, static, operation_profile(
                live, ("dot", "broadcast", "concatenate")))
        return kept[family]

    return of


@pytest.mark.parametrize("offset", [0, LIVE_PART, LIVE_BOUND - LIVE_PART],
                         ids=["offset0", "one_part", "bound_less_a_part"])
@pytest.mark.parametrize("family", CONTINUES)
def test_a_part_prepares_the_live_blocks_alone(family, offset, part_forms):
    """The lowered part program holds no matmul, broadcast or concatenation
    over all the bound's cached positions outside a loop whose trip count is a
    RUNTIME value (the blocks below the part's end): none that gives keys,
    values or scores (floats; a fill is no preparation, and on the TPU the
    buffer is not even filled; the exact selection's masks and counts over a
    block of queries, booleans and integers, stay over the static width:
    ROADMAP S5(1)(i)).  And at an offset of 0, of one part and of the bound
    less a part it leaves the logits and the cache the static form leaves."""
    live, static, operations = part_forms(family)
    whole = [op for op in operations
             if re.match(r"(bf16|f32)\[", op["shape"])
             and str(LIVE_BOUND) in re.findall(r"\d+", op["shape"])
             and not op["runtime_loop"] and "dimensions={}" not in op["operands"]]
    assert not whole, whole
    # ... and the loop is there wherever something is prepared at all (a
    # family whose cache holds a key and value a query head prepares nothing)
    cfg, params = tiny_model(family)
    params = served(params, cfg)
    prepares = gen.latent_cache(cfg) or cfg.n_heads != gen.kv_heads(cfg)
    assert any(op["runtime_loop"] for op in operations) == bool(prepares)

    prompt = np.random.default_rng(7).integers(1, cfg.vocab_size, LIVE_BOUND)
    cache = gen.init_cache(cfg, 3, 96)
    for at in range(0, offset, LIVE_PART):  # what stands before the part
        _, cache = static(
            params, jnp.asarray(prompt[None, at:at + LIVE_PART], jnp.int32),
            jnp.asarray([LIVE_PART]), cache, jnp.asarray([1]), jnp.asarray([at]))
        cache.pop("routed", None)
    args = (params, jnp.asarray(prompt[None, offset:offset + LIVE_PART], jnp.int32),
            jnp.asarray([LIVE_PART - 3]), cache, jnp.asarray([1]),
            jnp.asarray([offset]))
    (want_logits, want), (got_logits, got) = static(*args), live(*args)
    np.testing.assert_allclose(got_logits, want_logits, atol=8e-5, rtol=2e-5)
    assert int(got_logits.argmax()) == int(want_logits.argmax())
    for name in set(want) - {"routed"}:
        np.testing.assert_allclose(got[name], want[name], atol=2e-5, rtol=2e-5,
                                   err_msg=name)


def test_a_family_with_recurrent_layers_keeps_whole_prompts():
    cfg, params = tiny_model("granite_hybrid")
    assert not gen.can_continue(cfg)
    assert all(gen.can_continue(tiny_model(f)[0]) for f in CONTINUES)
    with pytest.raises(AssertionError, match="prefilled whole"):
        gen.prefill_at(served(params, cfg), cfg, jnp.ones((1, 8), jnp.int32),
                       jnp.asarray([8]), gen.init_cache(cfg, 2, 32),
                       jnp.asarray([0]), offsets=jnp.asarray([0]))
    # ... and its engine never splits one, whatever the part
    eng = llm.GenerationEngine(cfg, params, n_slots=2, prefill_buckets=(8, 64))
    assert eng._part is None and eng._part_jit is None
    eng.stop()


def test_a_parts_selection_is_the_whole_prompts():
    """The places a part's queries select over cached and own index keys
    under ONE threshold are the whole prompt's rows of ``causal_top_k_mask``,
    ties at the threshold included."""
    B, H, T, d, top_k, part = 2, 3, 48, 8, 7, 16
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(keys[0], (B, H, T, d))
    w = jax.random.normal(keys[1], (B, T, H))
    k = jnp.round(jax.random.normal(keys[2], (B, T, d)) * 2) / 2  # ties
    whole = np.asarray(dsa.causal_top_k_mask(q, w, k, top_k, block=16))
    assert (whole.sum(-1) == np.minimum(np.arange(T) + 1, top_k)).all()
    bound = 64  # the slab's static bound: junk beyond the prompt
    slab = jnp.concatenate([k, 9.0 * jnp.ones((B, bound - T, d))], axis=1)
    for at in range(0, T, part):
        got = np.asarray(dsa.causal_top_k_mask(
            q[:, :, at:at + part], w[:, at:at + part], slab, top_k, block=8,
            first=jnp.full((B,), at)))
        assert got.shape == (B, part, bound) and not got[..., T:].any()
        assert (got[..., :T] == whole[:, at:at + part]).all()


@pytest.mark.parametrize("with_keep", [False, True], ids=["plain", "with_keep"])
@pytest.mark.parametrize("first", [0, 512, 1536],
                         ids=["offset0", "one_block", "bound_less_a_part"])
def test_flash_kernel_under_a_runtime_key_length(first, with_keep):
    """The forward kernel with the first query's position and the key length
    as prefetched scalars (interpret mode) against the masked XLA reference:
    a part of 512 queries over a static 2,048 keys, at an offset of 0, of one
    block and of the bound less one part; keys beyond the length are junk
    that must not count.  Heads of 64 side by side, and latent attention's
    192 | 128."""
    P, bound = 512, 2048
    for shape in ((2, 2, 64, 64), (1, 1, 192, 128)):
        B, H, dk, dv = shape
        keys = jax.random.split(jax.random.PRNGKey(first + dk), 4)
        q = jax.random.normal(keys[0], (B, H, P, dk), jnp.float32)
        k = jax.random.normal(keys[1], (B, H, bound, dk), jnp.float32)
        v = jax.random.normal(keys[2], (B, H, bound, dv), jnp.float32)
        # rows of one call at offsets of their own
        starts = jnp.asarray([first, bound - P - first][:B], jnp.int32)
        live = (jnp.arange(bound)[None, :] < (starts + P)[:, None])[:, None, :, None]
        k, v = jnp.where(live, k, 1e4), jnp.where(live, v, jnp.nan)
        keep = None
        if with_keep:
            own = jnp.arange(bound)[None, None, :] == (
                starts[:, None, None] + jnp.arange(P)[None, :, None])
            keep = (own | (jax.random.uniform(keys[3], (B, P, bound)) < 0.2)
                    ).astype(jnp.int8)
        want = attention._attention_by_query_block(
            q, jnp.where(live, k, 0.0), jnp.where(live, v, 0.0), keep, starts,
            dk ** -0.5)
        got = attention.continued_attention(
            q, k, v, starts, keep=keep, interpret=True)
        assert not np.isnan(np.asarray(got)).any()
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        # ... and the same tiles where the keys come in blocks of positions
        # (``live_blocks``: a part a block), through the index maps alone
        in_blocks = lambda t: jnp.moveaxis(  # noqa: E731
            t.reshape(B, H, bound // P, P, -1), 2, 0)
        blocks = attention.continued_attention(
            q, in_blocks(k), in_blocks(v), starts, keep=keep, interpret=True)
        np.testing.assert_array_equal(blocks, got)


def test_live_blocks_prepares_the_blocks_below_the_live_length():
    """The blocks below the live length hold what was prepared of them, the
    others what the buffer held (off the TPU: zeros), a block of positions an
    entry of the leading axis."""
    rows = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 64, 8))
    heads = lambda r: (jnp.repeat(r, 3, axis=1) * 2.0,)  # noqa: E731
    want = np.asarray(heads(rows)[0])
    prepared = jax.jit(lambda live: attention.live_blocks(heads, (rows,), live, 16))
    for live in (1, 16, 17, 48, 64):
        (got,) = prepared(jnp.int32(live))
        assert got.shape == (4, 2, 3, 16, 8)
        for i in range(4):
            block = want[:, :, i * 16:(i + 1) * 16] if i * 16 < live else 0.0
            np.testing.assert_array_equal(got[i], block)


def test_band_attention_after_reads_the_ring_ahead_of_the_part():
    """A window layer's part: the positions ahead of it by absolute position
    (those below 0 masked) then its own, against the band over the whole
    sequence, in one block and in several."""
    B, H, T, d, window = 1, 2, 768, 8, 100
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(key, (B, H, T, d)) for key in keys)
    want = attention.band_attention(q, k, v, window=window)
    before = 128
    for first, part in ((0, 256), (256, 512), (512, 256), (640, 128)):
        ahead = jnp.arange(first - before, first)
        own = slice(first, first + part)
        ext = lambda a: jnp.concatenate(  # noqa: E731
            [jnp.where((ahead >= 0)[:, None], a[:, :, ahead % T], 7.0),
             a[:, :, own]], axis=2)
        got = attention.band_attention_after(
            q[:, :, own], ext(k), ext(v), jnp.asarray([first]), window=window)
        np.testing.assert_allclose(got, want[:, :, own], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", [(2, 2, 1024, 64, 64, 512, 513),
                                   (1, 1, 1024, 256, 128, 512, 100)],
                         ids=["heads_of_64", "latent_256_128"])
def test_flash_kernel_over_a_band_against_the_blocks(shape):
    """A window layer's part through the forward kernel (interpret mode): the
    band as its mask, the keys ahead of position 0 left out by a runtime
    bound, against the masked scores a block at a time; rows of one call at
    offsets of their own (0: nothing ahead; 256: half of what is handed over;
    a part deep in the prompt)."""
    B, H, P, dk, dv, before, window = shape
    keys = jax.random.split(jax.random.PRNGKey(P + dk), 3)
    q = jax.random.normal(keys[0], (B, H, P, dk))
    k = jax.random.normal(keys[1], (B, H, before + P, dk))
    v = jax.random.normal(keys[2], (B, H, before + P, dv))
    for first in ([0, 256], [2048, 0]):
        first = jnp.asarray(first[:B], jnp.int32)
        want = attention._band_after_by_block(q, k, v, first, window, dk ** -0.5)
        got = attention.band_attention_after(
            q, k, v, first, window=window, interpret=True)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# -- the engine ---------------------------------------------------------------

def _engine(family, part, monkeypatch, **kw):
    monkeypatch.setattr(llm, "PREFILL_PART_TOKENS", part)
    eng, cfg, _ = engine(family, seed=3, **{
        "n_slots": 4, "max_new_tokens": 14, "decode_chunk_steps": 4,
        "prefill_buckets": (8, 16, 64), **kw})
    return eng, cfg


def _log_dispatches(eng):
    """The calls and chunks the engine dispatches from now on, in order: a
    prompt's ``part`` (its first, which goes through the bucket's own program
    where a part is a bucket, among them), a ``whole`` prompt's call, a
    ``chunk`` (whole or cut)."""
    log = []

    def logged(name, fn):
        def call(*args, **kw):
            if not kw.get("first_part"):
                log.append(name)
            return fn(*args, **kw)
        return call

    eng._part_call = logged("part", eng._part_call)
    eng._prefill_call = logged("whole", eng._prefill_call)
    eng._decode_jit = logged("chunk", eng._decode_jit)
    cut = eng._decode_cut.result()
    eng._decode_cut = type("Built", (), {
        "result": lambda self: logged("chunk", cut),
        "exception": lambda self: None})()
    return log


@pytest.mark.parametrize("family", CONTINUES)
def test_long_prompts_between_decode_chunks_token_for_token(family, monkeypatch):
    """Two rows decode; a prompt of 50 tokens and one of 33 arrive with a
    short one behind them.  In parts of 8 every request gets, token for
    token, what an engine that never splits gives it; the decoding rows get a
    chunk between any two parts; the counters add up to the long prompts'
    tokens; and the meter calls a part's tick interleaved."""
    rng = np.random.default_rng(0)
    sizes = ((5, 64), (7, 59), (50, 6), (33, 14), (6, 3))
    prompts = [rng.integers(1, 256, n).tolist() for n, _ in sizes]
    answers = {}
    for part in (10 ** 6, 8):
        eng, cfg = _engine(family, part, monkeypatch, max_new_tokens=64)
        seq = events_mod.buffer().last_seq()
        early = [eng.submit(p, m) for p, (_, m) in zip(prompts[:2], sizes)]
        eng.step()  # the two short prompts are in and decoding
        log = _log_dispatches(eng)
        late = [eng.submit(p, m) for p, (_, m) in zip(prompts[2:], sizes[2:])]
        run_engine(eng, early + late)
        eng.stop()
        answers[part] = [f.result(1) for f in early + late]
        stats = eng.perf_stats()
        if part != 8:
            assert "part" not in log and eng._part is None
            assert "parts" not in stats["prefill"]
            continue
        assert not any(a == b == "part" for a, b in zip(log, log[1:])), log
        assert log.count("part") == 7 + 5 and log.count("whole") == 1
        # a prompt of k parts prepares 1 + 2 + .. + k blocks of cached
        # positions for its keys (those below each part's end: 28 and 15)
        # where the static bound of 64 holds 8 a call
        assert stats["prefill"]["parts"] == {
            "prompts": 2, "calls": 12, "rows": 12, "padded_tokens": 96,
            "live_tokens": 83, "blocks_prepared": sum(range(1, 8)) + sum(range(1, 6)),
            "blocks_bound": 12 * 8}
        assert stats["prefill"]["8"]["prompts"] == 3  # admitted as ever
        assert stats["prefill"]["64"]["calls"] == 0   # never called again
        # every tick that ran a part while rows decoded is interleaved, the
        # ones that admitted nobody to their chunk among them
        assert stats["ticks"]["interleaved"] >= 10
        # the meter's event (what `ray_tpu perf` prints) carries the tally
        meter = [r for r in events_mod.buffer().since(seq)
                 if r.get("message") == "prefill interference"]
        assert meter[-1]["data"]["parts"] == stats["prefill"]["parts"]
        assert cli._parts_line(meter[-1]["data"]["parts"]).endswith(
            "43 of 96 blocks of cached positions prepared (45%)")
    assert answers[8] == answers[10 ** 6]
    assert [len(a) for a in answers[8]] == [m for _, m in sizes]


def test_a_lone_long_prompts_parts_go_back_to_back(monkeypatch):
    """Nobody decodes: the parts go in one tick, under the tick's budget of
    padded tokens (the first always goes), and the last samples the first
    token; with a budget of two parts a tick, three ticks of parts alone (no
    chunk: nobody to decode) and a fourth that ends the prompt."""
    prompt = list(range(1, 51))
    eng, _ = _engine("kimi_k2", 8, monkeypatch)
    log = _log_dispatches(eng)
    program, offsets = eng._part_jit, []
    eng._part_jit = lambda *args: (offsets.append(int(args[5][0])),
                                   program(*args))[1]
    fut = eng.submit(prompt, 3)
    eng.step()
    assert log == ["part"] * 7 + ["chunk"]
    # a part is the 8 bucket here: the FIRST part ran that bucket's program
    # (a slot from scratch), the part program the six that continue
    assert offsets == [8, 16, 24, 32, 40, 48]
    assert eng.perf_stats()["prefill"]["parts"]["calls"] == 7
    assert eng.perf_stats()["prefill"]["8"]["calls"] == 0
    run_engine(eng, [fut])
    want = fut.result(1)
    eng.stop()

    eng, _ = _engine("kimi_k2", 8, monkeypatch, prefill_token_budget=16)
    log = _log_dispatches(eng)
    fut = eng.submit(prompt, 3)
    for _ in range(3):
        eng.step()
        assert eng._pending.chunk_dev is None and not eng._pending.rows
    assert log == ["part"] * 6 and eng._slots[0].prefilled == 48
    assert eng.stats()["active_slots"] == 1
    run_engine(eng, [fut])
    assert log == ["part"] * 7 + ["chunk"]
    assert fut.result(1) == want
    ticks = eng.perf_stats()["ticks"]
    eng.stop()
    assert ticks["prefill_only"] >= 2 and ticks["interleaved"] == 0


def test_tick_meter_calls_a_parts_tick_interleaved():
    """A tick that ran a part and admitted nobody to its chunk is interleaved
    all the same, and the part's seconds are interference; a tick of parts
    alone, which decoded no row, is prefill only."""
    m = llm._TickMeter("test")
    m.begin(chained=False)
    m.chunk_landed(10.0, 0, 2)
    m.begin(chained=True)
    m.call_landed(10.2)               # a part: no row admitted
    m.chunk_landed(10.3, 0, 2)
    m.begin(chained=True)             # decode only
    m.chunk_landed(10.4, 0, 2)
    m.begin(chained=True)             # parts alone, nobody decoding
    m.call_landed(10.6)
    m.chunk_landed(10.6, 0, 0)
    snap = m.snapshot()
    assert snap["ticks"] == {"decode_only": 1, "interleaved": 1, "prefill_only": 1}
    assert snap["interference_s"] == pytest.approx(0.2)
    assert snap["tick_s"]["prefill_only"] == pytest.approx(0.2)


def test_a_failure_mid_prefill_fails_that_request_and_the_engine_serves_on(
        monkeypatch):
    eng, cfg = _engine("gpt2", 8, monkeypatch)
    eng.start()
    try:
        short = eng.submit([3, 5, 7], 14)
        real, calls = eng._part_jit, []

        def failing(*args):
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("the part program failed")
            return real(*args)

        eng._part_jit = failing
        doomed = eng.submit(list(range(1, 41)), 4)
        with pytest.raises(RuntimeError, match="part program failed"):
            doomed.result(timeout=120)
        # the rows that decoded beside it fail with it, as after any error
        # of a tick (the cache's lineage may be poisoned) ...
        with pytest.raises(RuntimeError):
            short.result(timeout=120)
        assert eng.stats()["active_slots"] == 0 and not eng._splitting
        # ... and the engine serves on, long prompts too
        again = eng.submit(list(range(1, 41)), 4)
        assert len(again.result(timeout=120)) == 4
        assert len(eng.generate([3, 5, 7], 5)) == 5
    finally:
        eng.stop()


def test_the_cache_holds_whole_parts_and_one_program_serves_every_offset(
        monkeypatch):
    """Buckets that are no whole number of parts: the cache is sized in whole
    parts (a last part's padding is written like any column), and the part
    program is built once whatever the offsets."""
    eng, _ = _engine("gpt2", 24, monkeypatch, prefill_buckets=(8, 64))
    assert llm.part_bound(64) == 72
    assert eng._max_len == llm.cache_positions(64, 14, 4) >= 72 + 14 + 4
    futs = [eng.submit(list(range(1, n + 1)), 3) for n in (64, 49, 25)]
    run_engine(eng, futs)
    eng.stop()
    assert eng._part_jit._cache_size() == 1
    assert eng.perf_stats()["prefill"]["parts"]["calls"] == 3 + 3 + 2
