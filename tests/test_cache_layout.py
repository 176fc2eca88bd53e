"""``generate.cache_layout``: the one table of what a config's cache holds.

Shapes only (``jax.eval_shape``; nothing is compiled or run), over the nine
families of ``family_harness.TINY``.  The expectations are LITERALS, written
down from the tree before the table existed (PR 61's parent): a later PR that
moves a cached tensor's shape, a name or a byte count the engine reports has
to change them here, knowingly.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from family_harness import TINY
from ray_tpu.models import generate as gen
from ray_tpu.serve import llm

F32, I32 = "float32", "int32"
POS = {"pos": ((5,), I32)}

# init_cache(cfg, 5, 161): name -> (shape, dtype)
TREES = {
    "gpt2": {"k": ((2, 5, 4, 16, 161), F32), "v": ((2, 5, 4, 16, 161), F32)},
    "llama": {"k": ((2, 5, 2, 16, 161), F32), "v": ((2, 5, 2, 16, 161), F32)},
    "exaone_moe": {
        "k": ((1, 5, 2, 16, 161), F32), "v": ((1, 5, 2, 16, 161), F32),
        "k_ring": ((4, 5, 2, 16, 16), F32), "v_ring": ((4, 5, 2, 16, 16), F32)},
    "kimi_k2": {"c": ((3, 5, 1, 24, 161), F32)},
    "granite_hybrid": {
        "k": ((1, 5, 2, 16, 161), F32), "v": ((1, 5, 2, 16, 161), F32),
        "ssm": ((4, 5, 1, 16, 64), F32), "conv": ((4, 3, 5, 96), F32)},
    "dots3_note": {
        "c": ((2, 5, 1, 24, 161), F32), "idx_k": ((2, 5, 1, 16, 161), F32),
        "c_ring": ((3, 5, 1, 32, 10), F32)},
    "evabyte": {
        "k": ((2, 5, 4, 16, 64), F32), "v": ((2, 5, 4, 16, 64), F32),
        "ks": ((2, 5, 4, 16, 40), F32), "vs": ((2, 5, 4, 16, 40), F32)},
    "phi4_flash": {
        "k": ((1, 5, 1, 16, 161), F32), "v": ((1, 5, 1, 16, 161), F32),
        "k_ring": ((2, 5, 1, 16, 16), F32), "v_ring": ((2, 5, 1, 16, 16), F32),
        "ssm": ((3, 5, 4, 1, 64), F32), "conv": ((3, 3, 5, 64), F32)},
    "keye_vl": {
        "k": ((3, 5, 2, 16, 161), F32), "v": ((3, 5, 2, 16, 161), F32),
        "idx_k": ((3, 5, 1, 8, 161), F32), "rope_delta": ((5,), I32)},
}

# cached_tensors(cfg), cached_tensors(cfg, True).  (Where a family has no
# window layer the parent named rings its cache never held, ("k_ring",
# "v_ring"); the filter over the table names what the cache holds: none.)
TENSORS = {
    "gpt2": (("k", "v"), ()),
    "llama": (("k", "v"), ()),
    "exaone_moe": (("k", "v"), ("k_ring", "v_ring")),
    "kimi_k2": (("c",), ()),
    "granite_hybrid": (("k", "v"), ()),
    "dots3_note": (("c", "idx_k"), ("c_ring",)),
    "evabyte": (("k", "v"), ()),
    "phi4_flash": (("k", "v"), ("k_ring", "v_ring")),
    "keye_vl": (("k", "v", "idx_k"), ()),
}

# what the engine's constructor counted for slots of 384 positions (buckets up
# to 256, 8 new tokens, chunks of 4): its ``_layers``, ``_ring_tiles``,
# ``_slab_tiles``, ``_tile_bytes`` and the state's bytes a row a layer
COUNTS = {
    "gpt2": ({"full": 2, "window": 0}, 0, 3, {"full": 65536, "window": 0}, 0),
    "llama": ({"full": 2, "window": 0}, 0, 3, {"full": 32768, "window": 0}, 0),
    "exaone_moe": ({"full": 1, "window": 4}, 1, 3,
                   {"full": 32768, "window": 32768}, 0),
    "kimi_k2": ({"full": 3, "window": 0}, 0, 3, {"full": 12288, "window": 0}, 0),
    "granite_hybrid": ({"full": 1, "window": 0, "state": 4}, 0, 3,
                       {"full": 32768, "window": 0}, 5248),
    "dots3_note": ({"full": 2, "window": 3}, 1, 3,
                   {"full": 20480, "window": 16384}, 0),
    "evabyte": ({"full": 2, "window": 0}, 0, 2, {"full": 65536, "window": 0}, 0),
    "phi4_flash": ({"full": 1, "window": 2, "state": 3}, 1, 3,
                   {"full": 16384, "window": 16384}, 1792),
    "keye_vl": ({"full": 3, "window": 0}, 0, 3, {"full": 36864, "window": 0}, 0),
}


def _cfg(family):
    return gen.FAMILIES[family].Config.tiny(
        **{"dtype": jnp.float32, **TINY[family]})


@pytest.mark.parametrize("family", list(TINY))
def test_init_cache_makes_the_parents_tree(family):
    cfg = _cfg(family)
    tree = jax.eval_shape(lambda: gen.init_cache(cfg, 5, 161))
    assert {name: (t.shape, str(t.dtype)) for name, t in tree.items()} == {
        **TREES[family], **POS}
    # ... which is the table's rows, and nothing the table does not name
    assert {row.name: (row.shape(5, 161), str(jnp.dtype(row.dtype)))
            for row in gen.cache_layout(cfg)} == TREES[family]


@pytest.mark.parametrize("family", list(TINY))
def test_cached_tensors_is_a_filter_over_the_table(family):
    cfg = _cfg(family)
    full, rings = TENSORS[family]
    assert (gen.cached_tensors(cfg), gen.cached_tensors(cfg, True)) == (full, rings)
    layout = gen.cache_layout(cfg)
    assert full == tuple(
        row.name for row in layout if row.layers == gen.FULL_LAYERS
        and row.arrangement in (gen.SLAB, gen.WINDOW))
    assert rings == tuple(
        row.name for row in layout if row.arrangement == gen.RING)
    # a summary names the window it pools, and every row is named once
    assert all(row.pools in full for row in layout
               if row.arrangement == gen.SUMMARY)
    assert len({row.name for row in layout}) == len(layout)


@pytest.mark.parametrize("family", list(TINY))
def test_the_engines_counts_are_sums_over_the_table(family):
    layers, ring_tiles, slab_tiles, tile_bytes, state_row_bytes = COUNTS[family]
    max_len = llm.cache_positions(256, 8, 4)
    assert max_len == 384
    got = llm.cache_counts(_cfg(family), max_len)
    assert got["layers"] == layers and list(got["layers"]) == list(layers)
    assert (got["ring_tiles"], got["slab_tiles"]) == (ring_tiles, slab_tiles)
    assert got["tile_bytes"] == tile_bytes
    assert got["state_row_bytes"] == state_row_bytes
    # the tensor whose shape says which state kernel a chunk runs: the one
    # with its slots second
    assert (got["state"] is None) == ("state" not in layers)
    if got["state"]:
        assert TREES[family][got["state"]][0][1] == 5


def test_a_combination_no_body_serves_is_refused():
    """A latent family with recurrent layers: the parent's ``init_cache``
    returned before it made the state; the table refuses the config."""
    dots3 = gen.FAMILIES["dots3_note"]

    class Odd(dots3.Config):
        sliding_windows = property(
            lambda self: (0, gen.RECURRENT) + (5,) * (self.n_layers - 2))

    tiny = _cfg("dots3_note")
    cfg = Odd(**{f.name: getattr(tiny, f.name) for f in dataclasses.fields(tiny)})
    assert gen.RECURRENT in gen.layer_windows(cfg)
    with pytest.raises(AssertionError, match="latent"):
        gen.cache_layout(cfg)
