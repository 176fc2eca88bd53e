"""The Kimi-K2 family (multi-head latent attention over a cache of one latent
row a position, YaRN rotary, sigmoid-routed experts of which this chip holds
a block, beside a shared one) against its plain reference
(``benchmark/reference/kimi_k2_ref.py``), at a small size on the CPU.

Tolerances.  With ``dtype=float32`` the program and the reference do the same
arithmetic in another order (the absorbed decode form against the reference's
un-absorbed one, grouped matmuls against a masked loop, one softmax merged
from the cache and a chunk's columns), so logits of size ~1 agree to a few
1e-6; the limit is ``F32_TOL = 2e-4``, far under what any departure makes: a
cache rounded through float8 (>1e-2), the scale without YaRN's ``m * m``
(>1e-1), a latent row read one position off (>1e-2), a dropped token (>1e-1).
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

from benchmark.reference import kimi_k2_ref as ref  # noqa: E402
from ray_tpu.models import generate as gen  # noqa: E402
from ray_tpu.models import kimi_k2 as kk  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.serve.llm import make_config  # noqa: E402

attention = importlib.import_module("ray_tpu.ops.attention")

pytestmark = pytest.mark.usefixtures("kept_engine_programs")
F32_TOL = 2e-4


def sizes_of(cfg):
    return {"n_heads": cfg.n_heads, "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "top_k": cfg.experts_per_token, "routed_scale": cfg.routed_scale,
            "first_expert": cfg.experts_held[0], "rope_theta": cfg.rope_base,
            "rope_scaling": {
                "type": "yarn", "factor": cfg.rope_factor,
                "original_max_position_embeddings": cfg.rope_original_positions,
                "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
                "mscale": cfg.rope_mscale,
                "mscale_all_dim": cfg.rope_mscale_all_dim},
            "rms_eps": cfg.rms_eps}


@pytest.fixture(scope="module")
def model():
    # 3 layers (one dense, two sparse), 4 heads of 8 | 8 | 8, latent rows of
    # 16 + 8 values, 16 experts of which 4..11 are held, top-4; YaRN's ramp
    # lies inside the 4 rotary frequencies (32 original positions)
    return tiny_model("kimi_k2")


def ref_logits(model, seq):
    cfg, params = model
    return ref.logits(
        params, jnp.asarray([padded(seq)]), sizes_of(cfg))[0][:len(seq)]


def test_config_is_the_published_one_and_says_what_it_caches():
    cfg = make_config("kimi_k2", "k2.7-code", experts_held=[0, 12])
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        7168, 64, 1536, 512, 128, 64, 128)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.routed_scale,
            cfg.d_ff, cfg.d_expert) == (384, 8, 2.827, 18432, 2048)
    assert cfg.latent_cache == (576, 512) and cfg.experts_held == (0, 12)
    hash(cfg)  # jit closes over it
    assert gen.family_of(cfg) is kk and gen.cached_tensors(cfg) == ("c",)
    # one 576-value row a position a layer and no second tensor: the cell's
    # 33 rows x 9,344 positions x 6 layers are 2.13 GB
    small = kk.KimiK2Config.tiny()
    cache = jax.eval_shape(lambda: gen.init_cache(
        dataclasses.replace(cfg, n_layers=6), 33, 9344))
    assert set(cache) == {"c", "pos"}
    assert cache["c"].shape == (6, 33, 1, 576, 9344)
    assert cache["c"].dtype == jnp.bfloat16
    assert cache["c"].size * 2 == 2_131_329_024
    assert gen.init_cache(small, 2, 40)["c"].shape == (3, 2, 1, 24, 40)
    with pytest.raises(AssertionError):
        kk.KimiK2Config.tiny(experts_held=(12, 8))  # past the router


def test_yarn_frequencies_and_scale_at_the_published_sizes():
    """Hand-computed from the config.json: theta 50,000 over 64 rotary
    dimensions, factor 64 over 4,096 positions, beta_fast 32, beta_slow 1.
    The correction dimensions: 64 ln(4096 / (32 * 2 pi)) / (2 ln 50000) =
    8.914 -> 8 and 64 ln(4096 / (2 pi)) / (2 ln 50000) = 19.16 -> 20."""
    cfg = kk.KimiK2Config()
    inv = np.asarray(kk.yarn_inv_freq(cfg), np.float64)
    base = 50_000.0 ** (-np.arange(32) / 32.0)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:9], base[:9], rtol=1e-6)    # kept
    np.testing.assert_allclose(inv[20:], base[20:] / 64, rtol=1e-6)  # / factor
    # half way up the ramp, dimension 14: the mean of the two
    np.testing.assert_allclose(inv[14], base[14] * (0.5 + 0.5 / 64), rtol=1e-6)
    np.testing.assert_allclose(
        inv, ref.yarn_inv_freq(64, 50_000.0, sizes_of(cfg)["rope_scaling"]),
        rtol=1e-6)
    m = 0.1 * np.log(64.0) + 1.0
    assert abs(kk.yarn_mscale(64.0, 1.0) - 1.41589) < 1e-5 and abs(m - 1.41589) < 1e-5
    assert abs(cfg.attention_scale - 192 ** -0.5 * m * m) < 1e-9
    assert abs(cfg.attention_scale - 0.07217 * 2.00474) < 1e-5


@pytest.mark.parametrize("layer", [0, 1], ids=["dense", "sparse"])
def test_one_block_of_each_kind_against_the_reference(model, layer):
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(layer), (1, 21, cfg.d_model))
    p = params["layers"][layer]
    got, routed, _ = kk.block(x, served_layer(p), cfg)
    with jax.default_matmul_precision("highest"):
        want = ref._layer(x, p, **ref.layer_statics(sizes_of(cfg)))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < F32_TOL
    assert (routed is None) == (layer == 0)
    if routed is not None:  # 21 tokens x 4 choices, half of the experts held
        assert 0 < int(routed["tokens"].sum()) <= 21 * 4


@pytest.mark.parametrize("absorbed", [False, True], ids=["unabsorbed", "absorbed"])
def test_forward_against_the_reference(model, absorbed):
    """Both forms of the attention are the reference's forward: the absorbed
    one (64 heads against the latent rows themselves) is held to the
    un-absorbed one through it, and directly below."""
    cfg, params = model
    seq = list(np.random.RandomState(1).randint(0, cfg.vocab_size, 40))
    got = np.asarray(kk.apply(params, jnp.asarray([seq]), cfg, absorbed=absorbed)[0])
    assert np.abs(got - ref_logits(model, seq)).max() < F32_TOL


def test_absorbed_against_unabsorbed(model):
    """One layer's output in the decode form and in the prefill form, on the
    same input: equal up to float32 rounding.  The tolerance is what a
    float8 latent row (1e-2) or a scale without ``m * m`` (1e-1) breaks."""
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 33, cfg.d_model))
    for p in map(served_layer, params["layers"][:2]):
        plain, _, _ = kk.block(x, p, cfg)
        folded, _, _ = kk.block(x, p, cfg, absorbed=True)
        assert np.abs(np.asarray(plain) - np.asarray(folded)).max() < F32_TOL
        wrong = dataclasses.replace(cfg, rope_mscale=0.0, rope_mscale_all_dim=0.0)
        no_mm, _, _ = kk.block(x, p, wrong, absorbed=True)
        assert np.abs(np.asarray(plain) - np.asarray(no_mm)).max() > 1e-2


@pytest.mark.parametrize("broken", [None, "float8_cache", "no_mscale"])
def test_prefill_then_decode_through_the_latent_cache_against_the_reference(
        model, broken):
    """Prefill, then three decode chunks of 5 steps through the latent cache,
    one slot crossing position 128 (a tile boundary) in its second chunk, one
    short: each served token's LOGIT is the reference's best at its position
    in one full forward over prompt + served tokens, within float32 rounding.
    The same comparison FAILS, as it must, with the cache rounded through
    float8 and with the attention scale without YaRN's ``m * m``."""
    cfg, params = model
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, cfg.vocab_size, 121)),
               list(rng.randint(0, cfg.vocab_size, 9))]
    spoil = None
    if broken == "float8_cache":
        spoil = lambda cache: {**cache, "c": cache["c"].astype(  # noqa: E731
            jnp.float8_e4m3fn).astype(cache["c"].dtype)}
    elif broken == "no_mscale":
        cfg = dataclasses.replace(cfg, rope_mscale=0.0, rope_mscale_all_dim=0.0)
    # a cache of two 128-position tiles, a third slot idle
    served, cache, _ = serve(cfg, params, prompts, (None,) * 3, steps=5,
                             bucket=128, cache_len=256, spoil=spoil)
    assert set(cache) == {"c", "pos"}
    assert int(cache["pos"][2]) == len(prompts[0]) + 15
    gap = worst_gap(partial(ref_logits, model), prompts, served)
    assert gap < F32_TOL if broken is None else gap > 10 * F32_TOL


LIVE = {
    # n a slot: 0 (nothing), a position, a whole tile, a tile and one, all
    "edges": [0, 1, 127, 128, 129, 384],
    "all_dead": [0, 0, 0],
    "one_long": [0, 300],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("live", list(LIVE))
def test_latent_kernel_matches_the_slab(live, dtype):
    """``ragged_latent_decode_attention`` in the TPU interpreter against the
    masked einsums over the slab, ragged lengths including 0 and a whole
    tile.  float32: the same sums in another order (2e-5).  bfloat16: both
    round the weights to bf16 before the value product, at different maxima
    (2e-2, as the K/V kernel's test)."""
    dt = jnp.dtype(dtype)
    n = jnp.asarray(LIVE[live], jnp.int32)
    B, H, dk, dv, S, L, layer = len(LIVE[live]), 8, 72, 64, 384, 3, 1
    keys = jax.random.split(jax.random.PRNGKey(B), 2)
    q = jax.random.normal(keys[0], (B, H, dk), dt)
    c = jax.random.normal(keys[1], (L, B, 1, dk, S), dt)
    plan = attention.ragged_decode_plan(n, S // attention.DECODE_TILE)
    got = attention.ragged_latent_decode_attention(
        q, c, jnp.int32(layer), plan, scale=0.11, dv=dv,
        interpret=pltpu.InterpretParams())
    want = attention.latent_slab_attention(
        q, c, jnp.int32(layer), jnp.arange(S)[None, :] < n[:, None],
        scale=0.11, dv=dv)
    tol = 2e-5 if dtype == "float32" else 2e-2
    for name, g, w in zip(("acc", "m", "d"), got, want):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol, err_msg=name)
    dead = np.asarray(n) == 0
    assert (np.asarray(got[0])[dead] == 0).all()
    assert (np.asarray(got[2])[dead] == 0).all()
    assert (np.asarray(got[1])[dead] == -1e30).all()


def test_the_32_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """64 experts over 32 chips, 2 a chip: the parts the shares give, the
    shared expert counted once, are the uncut layer (the reference's sums,
    given every expert)."""
    whole = kk.KimiK2Config.tiny(dtype=jnp.float32, n_experts=64)
    p = kk.init_layer(whole, jax.random.PRNGKey(3), 1)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 9, whole.d_model))
    top_k, scale = whole.experts_per_token, whole.routed_scale
    worst, counted = shares_add_up(
        p, h, 32, moe.route_sigmoid_top_k(
            h.reshape(18, -1), p["router"], p["router_bias"], top_k, scale),
        sigmoid_top_k_by_hand(h, p, top_k, scale), ref._swiglu, whole.n_experts)
    assert counted == 18 * top_k  # every choice, once
    assert worst < F32_TOL


def test_engine_serves_a_mixed_batch_as_generate_does(model):
    """Prompts of three buckets through ``GenerationEngine`` (a never-started
    engine: the test is the engine thread): every answer is the one-shot
    path's, and the counters count latent layers as full ones, in the latent
    row's bytes."""
    cfg, params = model
    eng, _, _ = engine(
        "kimi_k2", n_slots=3, max_new_tokens=6, decode_chunk_steps=3,
        prefill_buckets=(8, 16, 32))
    assert set(eng.cache) == {"c", "pos"}
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(0, cfg.vocab_size, n)) for n in (5, 20, 12, 3)]
    futs = [eng.submit(p, 6) for p in prompts]
    run_engine(eng, futs)
    assert [f.result(timeout=1) for f in futs] == one_shot(
        params, cfg, prompts, 6)
    tiles = eng.perf_stats()["cache_tiles"]
    assert tiles["layers"] == {"full": 3, "window": 0}
    assert tiles["read_window"] == 0 and tiles["read_full"] > 0
    # one float32 row of 16 + 8 values a position, 128 positions a tile
    assert tiles["tile_bytes"] == {"full": 128 * 24 * 4, "window": 0}
    routed = eng.perf_stats()["moe"]
    assert np.asarray(routed["decode"]["tokens"]).shape == (2, 8)


@pytest.mark.parametrize("t", [256, 1024])
def test_attention_takes_values_narrower_than_keys(t):
    """The un-absorbed prefill form: 24-wide q and k, 16-wide v, a scale of
    its own; the long-context paths (blockwise, and the Pallas pair in the
    interpreter) against the materialised one."""
    q, k = (jax.random.normal(key, (1, 2, t, 24))
            for key in jax.random.split(jax.random.PRNGKey(t), 2))
    v = jax.random.normal(jax.random.PRNGKey(t + 1), (1, 2, t, 16))
    want = attention.mha_reference(q, k, v, causal=True, scale=0.3)
    got = attention.blockwise_attention(q, k, v, causal=True, scale=0.3,
                                        block_k=128)
    assert got.shape == (1, 2, t, 16)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    flash = attention.flash_attention_tpu(q, k, v, True, 0.3, 128, 128, True)
    assert np.abs(np.asarray(flash) - np.asarray(want)).max() < 1e-5
