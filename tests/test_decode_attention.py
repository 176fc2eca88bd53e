"""The decode chunk's kernels against what they replace: the ragged
decode-attention kernel against the masked einsums, the flush kernel against
the slice updates.

``generate._cache_scores`` has two forms held equal here: the Pallas kernel
that copies in only a slot's 128-position tiles below ``n[b]``
(``ops.attention.ragged_decode_attention``; what a program lowered for a TPU
with a cache of whole tiles runs) and the einsums over the whole padded slab
(``generate._cache_scores_slab``; what the CPU runs, and the plain
reference).  ``generate._flush`` likewise: ``ops.attention.cache_flush``,
which merges a chunk's columns into the one or two tiles they fall in, of
the slots that decoded only, and ``generate._flush_slices``, a
``dynamic_update_slice`` a slot.  The kernels run here in the TPU
interpreter.  To walk a whole ``decode_chunk`` through them the test steers
``lax.platform_dependent`` to its ``tpu`` branch; the program has no option
for that.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import family_harness
from family_harness import LONG, Slots
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.models import generate as gen

attention = importlib.import_module("ray_tpu.ops.attention")

SHAPES = {  # KV heads, query heads a KV head, head size
    "gpt2": (5, 1, 64),    # MHA, an odd number of heads
    "llama": (2, 4, 16),   # GQA
}
LIVE = {
    # (pos, active) a slot; the kernel sees n = pos where active, else 0
    "edges": ([0, 1, 127, 128, 129, None], [True] * 6),   # None: S
    "inactive_frozen": ([200, 77, 130, 5], [True, False, True, False]),
    "all_inactive": ([200, 77, 130], [False] * 3),
}


def _f32_reference(q, k, v, layer, n):
    """The same softmax, un-normalised, in float32 throughout."""
    k, v = k[layer].astype(jnp.float32), v[layer].astype(jnp.float32)
    s = jnp.einsum("bkgd,bkds->bkgs", q.astype(jnp.float32), k)
    s = s * q.shape[-1] ** -0.5
    mask = (jnp.arange(k.shape[-1]) < n[:, None])[:, None, None, :]
    m = jnp.where(mask, s, -1e30).max(-1)
    e = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    return jnp.einsum("bkgs,bkds->bkgd", e, v), m, e.sum(-1)


@pytest.mark.parametrize("S", [256, 896])
@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_matches_the_slab(shape, live, S):
    KV, G, dh = SHAPES[shape]
    pos, active = LIVE[live]
    pos = jnp.asarray([S if p is None else p for p in pos], jnp.int32)
    n = jnp.where(jnp.asarray(active), pos, 0)  # as decode_chunk has it
    B, L, layer = len(active), 3, 1
    keys = jax.random.split(jax.random.PRNGKey(S + dh), 3)
    q = jax.random.normal(keys[0], (B, KV, G, dh), jnp.bfloat16)
    k = jax.random.normal(keys[1], (L, B, KV, dh, S), jnp.bfloat16)
    v = jax.random.normal(keys[2], (L, B, KV, dh, S), jnp.bfloat16)
    plan = attention.ragged_decode_plan(n, S // attention.DECODE_TILE)
    # the work list is the live tiles, slot by slot, and nothing else
    count, ns, slot, tile = np.split(
        np.asarray(plan), [1, 1 + B, 1 + B + B * S // 128])
    want = [(b, t) for b in range(B) for t in range(-(-int(n[b]) // 128))]
    assert int(count[0]) == len(want) and list(ns) == list(np.asarray(n))
    assert list(zip(slot[:len(want)], tile[:len(want)])) == want

    got = attention.ragged_decode_attention(
        q, k, v, jnp.int32(layer), plan, interpret=pltpu.InterpretParams())
    exact = _f32_reference(q, k, v, layer, n)
    slab = gen._cache_scores_slab(
        q, k, v, jnp.int32(layer), jnp.arange(S)[None, :] < n[:, None])
    for name, g, e, s in zip(("acc", "m", "d"), got, exact, slab):
        # f32 rounding of the same sums in another order ...
        np.testing.assert_allclose(g, e, rtol=2e-5, atol=2e-5, err_msg=name)
        # ... and the slab form, which rounds the weights to bf16 before
        # the value product as the decode step always has
        np.testing.assert_allclose(g, s, rtol=2e-2, atol=2e-2, err_msg=name)
    dead = np.asarray(n) == 0
    assert (np.asarray(got[0])[dead] == 0).all()
    assert (np.asarray(got[2])[dead] == 0).all()
    assert (np.asarray(got[1])[dead] == -1e30).all()
    assert (np.asarray(slab[2])[dead] == 0).all()


SLABS = {  # KV heads, values a head: what a position of a full layer holds
    "mha_heads_x_64": (5, 64),    # GPT-2 XL's 25 x 64, fewer heads
    "gqa_8_x_128": (8, 128),      # K-EXAONE
    "latent_1_x_576": (1, 576),   # Kimi-K2's one row a position
}
STEPS = 16
FLUSHES = {
    # (pos0, active, steps taken before an EOS) a slot; S = 384
    "straddles_a_tile": ([120, 250, 5], [True, True, False], [16, 16, 0]),
    "first_lane": ([128, 0, 256], [True, True, True], [16, 16, 16]),
    "last_lane": ([127, 255, 40], [True, True, False], [16, 16, 0]),
    "all_idle": ([120, 128, 300], [False] * 3, [0] * 3),
    "one_live": ([77, 113, 300], [False, True, False], [0, 16, 0]),
    "every_slot_live": ([3, 112, 113, 368], [True] * 4, [16] * 4),
    "eos_mid_chunk": ([121, 60, 200], [True, True, False], [5, 16, 0]),
}


@pytest.mark.parametrize("case", list(FLUSHES))
@pytest.mark.parametrize("slab", list(SLABS))
def test_flush_kernel_matches_the_slice_updates(slab, case):
    """Bit for bit: every position below ``pos`` of every slot that decoded
    holds what the slice updates put there (all ``steps`` columns do, those
    after a mid-chunk EOS too), and a slot that sat the chunk out keeps its
    row as it was, every byte; the work list is the tiles the columns fall
    in and nothing else."""
    KV, dh = SLABS[slab]
    pos0, active, taken = (np.asarray(a) for a in FLUSHES[case])
    L, B, S = 2, len(pos0), 384
    keys = jax.random.split(jax.random.PRNGKey(KV + dh), 2)
    old = jax.random.normal(keys[0], (L, B, KV, dh, S), jnp.bfloat16)
    new = jax.random.normal(keys[1], (L, STEPS, B, KV, dh), jnp.bfloat16)
    plan = attention.cache_flush_plan(
        jnp.asarray(active), jnp.asarray(pos0, jnp.int32), STEPS, S)
    count, start, slot, tile = np.split(np.asarray(plan), [1, 1 + B, 1 + 3 * B])
    want = [(b, t) for b in range(B) if active[b]
            for t in range(pos0[b] // 128, (pos0[b] + STEPS - 1) // 128 + 1)]
    assert int(count[0]) == len(want) and list(start) == list(pos0)
    assert list(zip(slot[:len(want)], tile[:len(want)])) == want

    bits = lambda a: np.asarray(a).view(np.uint16)  # noqa: E731
    got = bits(attention.cache_flush(
        old, new, plan, interpret=pltpu.InterpretParams()))
    ref = bits(gen._flush_slices(old, new, jnp.asarray(pos0, jnp.int32)))
    for b in range(B):
        if active[b]:
            pos = pos0[b] + taken[b]
            assert (got[:, b, ..., :pos] == ref[:, b, ..., :pos]).all(), b
            assert (got[:, b] == ref[:, b]).all(), b  # the columns beyond too
        else:
            assert (got[:, b] == bits(old)[:, b]).all(), b


@pytest.mark.parametrize("family", ["gpt2", "llama", "exaone_moe", "kimi_k2"])
def test_decode_chunk_through_the_kernel(family, lowered_for_tpu):
    """Slots on both sides of a tile boundary, a short slot, an idle slot
    and the scratch slot, a slot admitted between chunks, over three chunks
    of a cache of two tiles: token for token the full forward's greedy
    answer (which ``test_generate`` holds the slab path to as well).  A
    latent family (``kimi_k2``) walks the same list through its own kernel,
    ``ragged_latent_decode_attention``.  Every chunk ends in the flush
    kernel, which leaves the rows of the slots that sat it out as they
    were."""
    eng = Slots(family, 256)
    rng = np.random.default_rng(0)
    eng.admit(0, [int(t) for t in rng.integers(1, 200, size=123)], 128)
    eng.admit(2, [9, 4, 7, 2, 5], 8)   # one tile, mostly masked
    names = gen.cached_tensors(eng.cfg)
    idle = {n: np.asarray(eng.cache[n][:, [1, 3, 4]]) for n in names}
    eng.decode(8)                      # slot 0 crosses position 128
    for n in names:  # the idle slots' and the scratch slot's rows: untouched
        assert (np.asarray(eng.cache[n][:, [1, 3, 4]]) == idle[n]).all(), n
    eng.admit(3, [int(t) for t in rng.integers(1, 200, size=128)], 128)
    eng.decode(8)                      # slot 3 starts on the boundary
    eng.decode(8)
    eng.assert_greedy({0: 25, 2: 25, 3: 17})
    assert [int(p) for p in eng.cache["pos"]] == [147, 0, 29, 144, 128]


def test_cache_that_is_not_whole_tiles_takes_the_slab(monkeypatch):
    """The kernel is chosen by the cache's shape: a cache of 161 positions
    never reaches ``platform_dependent``."""
    def refuse(*args, **kw):
        raise AssertionError("a 161-position cache must take the slab")

    monkeypatch.setattr(gen.lax, "platform_dependent", refuse)
    # a path of its own: its programs are traced here, under the refusal
    monkeypatch.setattr(family_harness, "PATH", "platform_dependent_refused")
    eng = Slots("gpt2", LONG)
    eng.admit(0, [3, 17, 5], 8)
    eng.decode(4)
    eng.assert_greedy({0: 5})
