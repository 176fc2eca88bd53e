"""The main path's programs, compiled for the v5e at their real widths.

No chip is needed: the TPU compiler is installed and compiles for a chip
that is described (``v5e:2x2``), not attached.  A compile that passes is
not a chip run — it says the chip's compiler accepts the program and that
it fits the device's memory, nothing about results or speed.  What it
catches is what interpret mode and the CPU backend cannot: a Pallas slice
the tiling refuses, too much VMEM, a step that does not fit 16 GB.

Shapes only — nothing is allocated or run (``jax.eval_shape`` for the
models, ``ShapeDtypeStruct`` pinned to the described chip for arguments).
Skipped only where the topology cannot be described.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import re
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """The described 2x2 of v5e chips.  The persistent compile cache is off
    meanwhile: a compile for a described chip is written to it but cannot
    be read back without a chip (the next one warns and recompiles)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    _FOUR_CHIPS[:] = topo.devices
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# the described 2x2's devices, for a lowering over all four (set by ``topo``)
_FOUR_CHIPS: list = []


@pytest.fixture(scope="module")
def chip(topo):
    """A sharding that pins arguments to one described v5e chip."""
    return SingleDeviceSharding(topo.devices[0])


def _on(chip, tree):
    """The shapes of ``tree`` as arguments living on the described chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < HBM_BYTES, f"program needs {need / 2**30:.1f} GiB"
    return need


def _lower_train_step(chip):
    """The train phase of chip_smoke.py and bench.py: GPT-2 small unchanged
    (12 layers, d_model 768, vocab 50304), B=6, T=1024, bf16, dots remat."""
    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2Config.gpt2_small()
    assert (cfg.remat, cfg.remat_policy, cfg.dtype) == (True, "dots", jnp.bfloat16)
    optimizer = gpt2.make_optimizer(lr=3e-4)
    state = jax.eval_shape(
        lambda k: gpt2.init_state(cfg, k, optimizer), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((6, cfg.max_seq_len), jnp.int32)
    step = jax.jit(gpt2.make_train_step(cfg, optimizer), donate_argnums=(0,))
    return [step.lower(
        _on(chip, state), _on(chip, {"inputs": tokens, "targets": tokens}))]


# GPT-2 XL's widths (huggingface.co/openai-community/gpt2-xl), the
# benchmark's gpt2-xl configuration, and GPT-2 medium's (.../gpt2-medium)
XL = dict(n_layers=48, n_heads=25, d_model=1600, d_ff=6400)
MEDIUM = dict(n_layers=24, n_heads=16, d_model=1024, d_ff=4096)


def _lower_medium_cell(chip):
    """The train-gpt2-medium-1k cell's step as benchmark/drivers/train.py
    builds it: GPT-2 medium unchanged, B=8, T=1024, bf16, dots remat."""
    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2Config.gpt2_small(**MEDIUM)
    assert (cfg.remat, cfg.remat_policy, cfg.max_seq_len) == (True, "dots", 1024)
    optimizer = gpt2.make_optimizer(lr=3e-4, warmup=20)
    state = jax.eval_shape(
        lambda k: gpt2.init_state(cfg, k, optimizer), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((8, cfg.max_seq_len), jnp.int32)
    step = jax.jit(gpt2.make_train_step(cfg, optimizer), donate_argnums=(0,))
    return [step.lower(
        _on(chip, state), _on(chip, {"inputs": tokens, "targets": tokens}))]


def _flash_in_the_layer_loops(compiled, *, forward_again: bool):
    """The Pallas pair where a train step's attention was: the forward kernel
    in the forward pass's layer loop, the backward kernel in the backward
    pass's (with the forward again under full remat; not where the remat
    policy kept its result and logsumexp), each once a layer body, and no
    float32 ``[.., 256, kv_len]`` block of scores left anywhere."""
    from ray_tpu.parallel.sharding import kernel_profile

    kernels = kernel_profile(compiled)
    fwd, bwd = kernels["flash_attention_fwd"], kernels["flash_attention_bwd"]
    assert bwd["count"] == 1 and len(bwd["loops"]) == 1, kernels
    assert fwd["count"] == 1 + forward_again, kernels
    if forward_again:
        assert len(fwd["loops"]) == 2 and fwd["loops"] > bwd["loops"], kernels
    else:
        assert len(fwd["loops"]) == 1 and not fwd["loops"] & bwd["loops"], kernels
    assert not re.search(r"f32\[\d+,\d+,256,\d+\]", compiled.as_text())


def _served_shapes(cfg):
    """The shapes of the parameters an engine holds: the family's ``init``
    tree laid out as it is served (``generate.serving_layout``: the held
    experts' gate and up matrices ONE leaf; as many bytes as ``init``'s)."""
    from ray_tpu.models import generate as gen
    from ray_tpu.serve import llm

    return jax.eval_shape(
        lambda: gen.serving_layout(cfg, llm._default_init(cfg, 0)))


def _lower_prefill(chip, prefill, params, cache, n_slots, bucket):
    """``llm_prefill`` of one bucket at the engine's width for it."""
    from ray_tpu.serve import llm

    n = llm.call_rows(bucket, n_slots)
    i32 = lambda *shape: _on(chip, jax.ShapeDtypeStruct(shape, jnp.int32))  # noqa: E731
    return prefill.lower(
        _on(chip, params), i32(n, bucket), i32(n), _on(chip, cache), i32(n))


def _lower_part(chip, part, params, cache):
    """``llm_prefill_part`` as the engine calls it: ONE row a part wide, its
    offset a sixth, runtime argument (one program whatever it is)."""
    from ray_tpu.serve import llm

    i32 = lambda *shape: _on(chip, jax.ShapeDtypeStruct(shape, jnp.int32))  # noqa: E731
    return part.lower(
        _on(chip, params), i32(1, llm.PREFILL_PART_TOKENS), i32(1),
        _on(chip, cache), i32(1), i32(1))


def _lower_decodes(chip, decode, cut, params, cache, n_slots):
    """The engine's two decode programs as it calls them: the whole chunk
    with five arguments, the cut chunk with its runtime bound as a sixth."""
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    args = (_on(chip, params), _on(chip, cache), _on(chip, i32(n_slots + 1)),
            _on(chip, jax.ShapeDtypeStruct((n_slots + 1,), jnp.bool_)),
            _on(chip, jax.eval_shape(lambda: jax.random.PRNGKey(0))))
    return [decode.lower(*args), cut.lower(*args, _on(chip, i32()))]


def _lower_serve_engine(chip, family, *, buckets=(128,), chunk=64, max_new=128,
                        **config_kwargs):
    """What a ``num_tpus=1`` LLM replica runs: the prefill of each bucket at
    the engine's width for it (``llm.call_rows``: the largest bucket first,
    the others after the two decode programs), and one decode chunk, whole
    and cut, over 16 slots plus the scratch slot.  The defaults are bench.run_decode_bench's shape;
    the cache's length is the engine's own rounding to whole 128-position
    tiles (128 + 128 + 64 = 320 -> 384), so this is what a replica really
    runs."""
    from ray_tpu.models import generate as gen
    from ray_tpu.serve import llm

    cfg = llm.make_config(family, "small", **config_kwargs)
    n_slots = 16
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(cfg.dtype) if x.dtype == jnp.float32 else x,
        llm._default_init(cfg, 0)))
    cache = jax.eval_shape(
        lambda: gen.init_cache(
            cfg, n_slots + 1, llm.cache_positions(max(buckets), max_new, chunk)))
    prefill, decode, cut, part = llm.engine_programs(cfg, decode_chunk_steps=chunk)
    assert part is None  # no bucket above a part: whole prompts only
    prefill_of = partial(_lower_prefill, chip, prefill, params, cache, n_slots)
    widest, *narrower = sorted(buckets, reverse=True)
    return [
        prefill_of(widest),
        *_lower_decodes(chip, decode, cut, params, cache, n_slots),
        *map(prefill_of, narrower),
    ]


# K-EXAONE-236B-A23B as the benchmark's k-exaone-236b-a23b-ep8 holds it: the
# published widths (the config's defaults), 5 layers, experts 0-15 of 128,
# an eighth of the vocabulary
K_EXAONE = dict(vocab_size=19_200, n_layers=5, experts_held=(0, 16))


def _lower_exaone_cell(chip):
    """The serve-k-exaone-236b-ep8-mixed cell's programs: window and full
    layers in one cache (33 rows: a 4,736-position slab for the full layer,
    256-position rings for the four window layers), 16 held experts a
    sparse layer through the grouped matmul, every prefill bucket at the
    engine's width for it (``llm.call_rows``: two rows of 128, one of each
    wider bucket) up to one part of 2,048 tokens, and the part program in
    the 4,096 bucket's place (a prompt above a part goes in parts: the full
    layer's cached k, v through the flash kernel with a runtime key length,
    the window layers' rings read ahead of the part)."""
    from ray_tpu.models import generate as gen
    from ray_tpu.serve import llm

    cfg = llm.make_config("exaone_moe", "236b", **K_EXAONE)
    n_slots, chunk = 32, 16
    params = _served_shapes(cfg)
    assert all(x.dtype == cfg.dtype for x in jax.tree.leaves(params))
    cache = jax.eval_shape(lambda: gen.init_cache(
        cfg, n_slots + 1, llm.cache_positions(4096, 512, chunk)))
    assert cache["k"].shape == (1, 33, 8, 128, 4736)
    assert cache["k_ring"].shape == (4, 33, 8, 128, 256)
    prefill, decode, cut, part = llm.engine_programs(
        cfg, decode_chunk_steps=chunk, part_bound=llm.part_bound(4096))
    prefill_of = partial(_lower_prefill, chip, prefill, params, cache, n_slots)
    assert [llm.call_rows(b, n_slots) for b in (128, 256, 2048)] == [2, 1, 1]
    return [
        _lower_part(chip, part, params, cache),
        *_lower_decodes(chip, decode, cut, params, cache, n_slots),
        *map(prefill_of, (2048, 1024, 512, 256, 128)),
    ]


# Kimi-K2.7-Code as the benchmark's kimi-k2.7-code-ep32 holds it: the
# published widths (the config's defaults), 6 layers, experts 0-11 of 384, an
# eighth of the vocabulary
KIMI_K2 = dict(vocab_size=20_480, n_layers=6, experts_held=(0, 12))


def _lower_kimi_cell(chip):
    """The serve-kimi-k2.7-code-ep32-code cell's programs: six latent layers
    over ONE cache tensor (33 rows x 9,344 positions of one 576-value row),
    12 held experts a sparse layer through the grouped matmul, every prefill
    bucket one row wide up to one part of 2,048 tokens, and the part program
    for every longer prompt (the cached rows up-projected by the layer's own
    weights, then the Pallas flash kernel with 192-wide keys, 128-wide values
    and a runtime key length)."""
    from ray_tpu.models import generate as gen
    from ray_tpu.serve import llm

    cfg = llm.make_config("kimi_k2", "k2.7-code", **KIMI_K2)
    n_slots, chunk = 32, 16
    params = _served_shapes(cfg)
    assert all(x.dtype == cfg.dtype for x in jax.tree.leaves(params))
    assert sum(x.size for x in jax.tree.leaves(params)) == 4_173_177_728
    cache = jax.eval_shape(lambda: gen.init_cache(
        cfg, n_slots + 1, llm.cache_positions(8192, 1024, chunk)))
    assert set(cache) == {"c", "pos"}
    assert cache["c"].shape == (6, 33, 1, 576, 9344)
    prefill, decode, cut, part = llm.engine_programs(
        cfg, decode_chunk_steps=chunk, part_bound=llm.part_bound(8192))
    prefill_of = partial(_lower_prefill, chip, prefill, params, cache, n_slots)
    assert [llm.call_rows(b, n_slots) for b in (256, 2048)] == [1, 1]
    return [
        _lower_part(chip, part, params, cache),
        *_lower_decodes(chip, decode, cut, params, cache, n_slots),
        *map(prefill_of, (2048, 1024, 512, 256)),
    ]


# Granite-4.0-H-Small as the benchmark's granite-4.0-h-small-ep8 holds it:
# the published widths (the config's defaults), layers 0-19, experts 0-8 of
# 72, an eighth of the tied vocabulary
GRANITE = dict(vocab_size=12_544, n_layers=20, experts_held=(0, 9))


def _lower_granite_cell(chip):
    """The serve-granite-4.0-h-small-ep8-chat cell's programs at 48 slots:
    18 Mamba-2 layers in three rolled runs over ONE stack of parameters, a
    float32 state of 4 MB a row a layer updated in place by the kernel that
    walks the chunk's active slots, two attention layers' K/V slabs of 2,688
    positions beside it, 9 held experts a layer through the grouped matmul
    over the whole stack, every prefill bucket at the engine's width for it
    (four rows of 64, two of 128, one of each wider bucket)."""
    from ray_tpu.models import generate as gen
    from ray_tpu.serve import llm

    cfg = llm.make_config("granite_hybrid", "4.0-h-small", **GRANITE)
    n_slots, chunk = 48, 16
    params = _served_shapes(cfg)
    assert all(x.dtype == cfg.dtype for x in jax.tree.leaves(params))
    assert sum(x.size for x in jax.tree.leaves(params)) == 4_058_678_528
    cache = jax.eval_shape(lambda: gen.init_cache(
        cfg, n_slots + 1, llm.cache_positions(2048, 512, chunk)))
    assert set(cache) == {"k", "v", "pos", "ssm", "conv"}
    assert cache["ssm"].shape == (18, 49, 64, 128, 128)
    assert cache["ssm"].dtype == jnp.float32
    assert cache["k"].shape == (2, 49, 8, 128, 2688)
    prefill, decode, cut, _ = llm.engine_programs(cfg, decode_chunk_steps=chunk)
    assert not gen.can_continue(cfg)  # a recurrent layer: whole prompts only
    prefill_of = partial(_lower_prefill, chip, prefill, params, cache, n_slots)
    assert [llm.call_rows(b, n_slots) for b in (64, 128, 256, 2048)] == [4, 2, 1, 1]
    return [
        prefill_of(2048),
        *_lower_decodes(chip, decode, cut, params, cache, n_slots),
        *map(prefill_of, (1024, 512, 256, 128, 64)),
    ]


# dots3-note-prev as the benchmark's dots3-note-prev-ep32 holds it: the
# published widths (the config's defaults), layers 0-4, experts 0-7 of 256,
# an eighth of the vocabulary
DOTS3_NOTE = dict(vocab_size=19_008, n_layers=5, experts_held=(0, 8))


def _lower_dots3_cell(chip):
    """The serve-dots3-note-prev-ep32-docs cell's programs: two full latent
    layers that SELECT (a 576-value row and a 128-value index key a position,
    33 rows x 17,536 positions), three sliding latent layers over rings of
    1,152 rows of 1,088 values (nine whole tiles), 8 held experts a sparse
    layer; the decode chunk hands the latent kernel the selection as a mask
    on a full layer and the step's window on a sliding one; a prompt of at
    most one part (2,048 tokens) through its bucket's program, every longer
    one through the part program: the full layers' cached rows up-projected,
    the selection over cached and own index keys, and the Pallas forward
    kernel with the selection as its fourth operand and a runtime key length
    over a static 16,384 positions."""
    from ray_tpu.models import generate as gen
    from ray_tpu.serve import llm

    cfg = llm.make_config("dots3_note", "note-prev", **DOTS3_NOTE)
    n_slots, chunk = 32, 16
    params = _served_shapes(cfg)
    assert all(x.dtype == cfg.dtype for x in jax.tree.leaves(params))
    cache = jax.eval_shape(lambda: gen.init_cache(
        cfg, n_slots + 1, llm.cache_positions(16384, 1024, chunk)))
    assert set(cache) == {"c", "idx_k", "c_ring", "pos"}
    assert cache["c"].shape == (2, 33, 1, 576, 17536)
    assert cache["idx_k"].shape == (2, 33, 1, 128, 17536)
    assert cache["c_ring"].shape == (3, 33, 1, 1088, 1152)
    prefill, decode, cut, part = llm.engine_programs(
        cfg, decode_chunk_steps=chunk, part_bound=llm.part_bound(16384))
    prefill_of = partial(_lower_prefill, chip, prefill, params, cache, n_slots)
    assert llm.call_rows(2048, n_slots) == 1
    # the engine's two prefill programs: the part program (the masked kernel
    # under a runtime key length) and the one bucket of at most a part (plain
    # causal attention, every position selected)
    return [
        _lower_part(chip, part, params, cache),
        *_lower_decodes(chip, decode, cut, params, cache, n_slots),
        prefill_of(2048),
    ]


def _lower_keye_cell(chip):
    """The serve-keye-vl-2.0-30b-a3b-pp8-video cell's programs: Keye-VL-2.0 at
    its published widths, 6 of 48 layers (the first of eight pipeline stages)
    with all 128 experts of each, the whole vocabulary, the tower and the
    merger; 12 slots and the scratch row over k, v and a 64-value index key a
    position (17,536 positions) and an offset a slot.  The decode chunk hands
    the ragged kernel the step's selection as its mask on every layer and
    flushes three tensors; every prompt (all are above one part) goes through
    the 2,048 bucket's program for its first part and the part program after
    it, both with the ``visual`` argument (four tower results of 16 frames x
    64 rows, an index, three-axis positions, the slots' offsets); the tower
    call takes 16 frames of 16 x 16 patches."""
    from ray_tpu.models import generate as gen
    from ray_tpu.serve import llm

    cfg = llm.make_config("keye_vl", "2.0-30b-a3b", n_layers=6)
    n_slots, chunk, part_tokens = 12, 16, llm.PREFILL_PART_TOKENS
    params = _served_shapes(cfg)
    assert all(x.dtype == cfg.dtype for x in jax.tree.leaves(params))
    cache = jax.eval_shape(lambda: gen.init_cache(
        cfg, n_slots + 1, llm.cache_positions(16384, 1024, chunk)))
    assert set(cache) == {"k", "v", "idx_k", "rope_delta", "pos"}
    assert cache["k"].shape == (6, 13, 4, 128, 17536)
    assert cache["idx_k"].shape == (6, 13, 1, 64, 17536)
    prefill, decode, cut, part = llm.engine_programs(
        cfg, decode_chunk_steps=chunk, part_bound=llm.part_bound(16384))
    i32 = lambda *shape: _on(chip, jax.ShapeDtypeStruct(shape, jnp.int32))  # noqa: E731
    # frames a tower call, rows a frame, tower results a call of one part
    per, rows = llm.VISION_CALL_PATCHES // 256, 64
    results = 1 + -(-(part_tokens // rows + 1) // per)
    assert (per, results) == (16, 4)
    visual = {"rows": tuple(_on(chip, jax.ShapeDtypeStruct(
                  (per, rows, cfg.d_model), cfg.dtype)) for _ in range(results)),
              "index": i32(1, part_tokens), "positions": i32(1, 3, part_tokens),
              "delta": i32(1)}
    return [
        part.lower(_on(chip, params), i32(1, part_tokens), i32(1),
                   _on(chip, cache), i32(1), i32(1), visual),
        *_lower_decodes(chip, decode, cut, params, cache, n_slots),
        prefill.lower(_on(chip, params), i32(1, part_tokens), i32(1),
                      _on(chip, cache), i32(1), visual),
        llm.vision_program(cfg).lower(
            _on(chip, params), _on(chip, jax.ShapeDtypeStruct(
                (per, 256, 588), jnp.uint8)), grid=(16, 16)),
    ]


def _lower_evabyte_cell(chip):
    """The serve-evabyte-6.5b-pp4-code cell's programs: EvaByte at its
    published widths, 8 of 32 layers (one of four pipeline stages), 16 slots
    and the scratch row over the cache that compacts itself: an exact window
    of 2,048 + 128 places and 1,152 summary rows (9 windows' worth: 16,384 +
    2,048 + 16 positions) a slot a layer.  The decode chunk reads both through
    the ragged kernel (twice a layer), flushes the window's two tensors at the
    slot's place in its window and pools the windows that filled; a prompt of
    at most one part (2,048 bytes: one window) takes its bucket's program
    (plain causal attention), every longer one the part program: the flash
    kernel over the cached summaries laid ahead of the part's own keys."""
    from ray_tpu.models import generate as gen
    from ray_tpu.serve import llm

    cfg = llm.make_config("evabyte", "6.5b", n_layers=8)
    n_slots, chunk = 16, 16
    params = _served_shapes(cfg)
    assert all(x.dtype == cfg.dtype for x in jax.tree.leaves(params))
    cache = jax.eval_shape(lambda: gen.init_cache(
        cfg, n_slots + 1, llm.cache_positions(16384, 2048, chunk)))
    assert set(cache) == {"k", "v", "ks", "vs", "pos"}
    assert cache["k"].shape == cache["v"].shape == (8, 17, 32, 128, 2176)
    assert cache["ks"].shape == cache["vs"].shape == (8, 17, 32, 128, 1152)
    prefill, decode, cut, part = llm.engine_programs(
        cfg, decode_chunk_steps=chunk, part_bound=llm.part_bound(16384))
    assert llm.call_rows(2048, n_slots) == 1
    return [
        _lower_part(chip, part, params, cache),
        *_lower_decodes(chip, decode, cut, params, cache, n_slots),
        _lower_prefill(chip, prefill, params, cache, n_slots, 2048),
    ]


def _lower_phi4_flash_cell(chip):
    """The serve-phi-4-mini-flash-reasoning-docs cell's programs:
    Phi-4-mini-flash at its published sizes, WHOLE (32 layers, 200,064
    rows), 32 slots and the scratch row: ONE slab of 18,560 positions of K and
    V pairs (10 heads of 128) that eight layers read, eight rings of 1,024,
    nine Mamba-1 states ``[16, 40, 128]`` float32.  A prefill program takes a
    further argument, the rows that END a prompt (the layers above the slab
    run for those rows' last positions alone, or not at all)."""
    from ray_tpu.models import generate as gen
    from ray_tpu.serve import llm

    cfg = llm.make_config("phi4_flash", "mini-flash")
    n_slots, chunk = 32, 16
    params = _served_shapes(cfg)
    assert all(x.dtype == cfg.dtype for x in jax.tree.leaves(params))
    assert sum(x.size for x in jax.tree.leaves(params)) == 3_852_562_944
    cache = jax.eval_shape(lambda: gen.init_cache(
        cfg, n_slots + 1, llm.cache_positions(16384, 2048, chunk)))
    assert cache["k"].shape == cache["v"].shape == (1, 33, 10, 128, 18560)
    assert cache["k_ring"].shape == (8, 33, 10, 128, 1024)
    assert cache["ssm"].shape == (9, 33, 16, 40, 128)
    assert cache["conv"].shape == (9, 3, 33, 5120)
    prefill, decode, cut, part = llm.engine_programs(
        cfg, decode_chunk_steps=chunk, part_bound=llm.part_bound(16384))
    assert llm.call_rows(2048, n_slots) == 1
    i32 = lambda *shape: _on(chip, jax.ShapeDtypeStruct(shape, jnp.int32))  # noqa: E731
    final = _on(chip, jax.ShapeDtypeStruct((1,), jnp.bool_))
    return [
        part.lower(_on(chip, params), i32(1, llm.PREFILL_PART_TOKENS), i32(1),
                   _on(chip, cache), i32(1), i32(1), final),
        *_lower_decodes(chip, decode, cut, params, cache, n_slots),
        prefill.lower(_on(chip, params), i32(1, 2048), i32(1), _on(chip, cache),
                      i32(1), final),
    ]


def _lower_bert(chip):
    """The classifier bench.run_serve_bench serves: BERT-base, one static
    batch of 16 x 128 tokens."""
    from ray_tpu.models import bert

    cfg = bert.BertConfig.base()
    params = jax.eval_shape(lambda: bert.init(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((16, 128), jnp.int32)
    fwd = jax.jit(lambda p, t: bert.apply(p, t, cfg))
    return [fwd.lower(_on(chip, params), _on(chip, tokens))]


def _lower_flash(chip, direction):
    """The Pallas kernels at a long sequence: T=8192, 12 heads of 64, blocks
    of 128, causal."""
    from ray_tpu.ops.attention import flash_attention_tpu

    qkv = _on(chip, jax.ShapeDtypeStruct((1, 12, 8192, 64), jnp.bfloat16))

    def attend(q, k, v):
        return flash_attention_tpu(q, k, v, True, None, 128, 128, False)

    if direction == "backward":
        attend = jax.grad(
            lambda q, k, v, f=attend: f(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))
    return [jax.jit(attend).lower(qkv, qkv, qkv)]


def _lower_sparse_train_parts(chip):
    """What the four-chip sparse train cell (SmallThinker-21B-A3B, 8 x 8,192
    tokens a step) gave the compiler that no program had: the dropless expert
    layer differentiated and spread over the 2x2 (64 ReGLU experts of 2,560 x
    768 top-6, 16 held a chip, all of them gathered to a chip's own 16,384
    tokens, whose 98,304 pairs go through ``lax.ragged_dot`` and its
    transposes with no loop; the matrices' gradients sent home a block at a
    time; value and gradients under the model's remat policy, so the program
    holds the forward pass, its replay and the backward pass as a step does),
    and ``attention()`` forward and backward under a 4,096 band at a chip's
    share ``[2, 28, 8192, 128]``.  (The WHOLE step's compile, ~60 s here,
    sized the cell's batch in the builder's scratch run and is too long for
    this file: PERF.md section 6, PR 57.)"""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models import smallthinker
    from ray_tpu.ops import attention, moe

    mesh = Mesh(np.array(_FOUR_CHIPS).reshape(4), ("fsdp",))
    over = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=NamedSharding(mesh, P("fsdp")))
    n, d, f, e, k = 65536, 2560, 768, 64, 6

    def experts(x, gates, w_gate_up, w_down, chosen):
        return moe.experts_ffn_train(
            x, chosen, gates, w_gate_up, w_down, activation="relu", mesh=mesh,
            axis="fsdp").astype(jnp.float32).sum()

    qkv = _on(chip, jax.ShapeDtypeStruct((2, 28, 8192, 128), jnp.bfloat16))
    return [
        jax.jit(jax.value_and_grad(
            jax.checkpoint(experts, policy=smallthinker.remat_policy()),
            argnums=(0, 1, 2, 3))).lower(
            over((n, d), jnp.bfloat16), over((n, k), jnp.float32),
            over((e, d, 2 * f), jnp.float32), over((e, f, d), jnp.float32),
            over((n, k), jnp.int32)),
        jax.jit(jax.grad(
            lambda q, k, v: attention(q, k, v, causal=True, window=4096)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))).lower(qkv, qkv, qkv)]


def _lower_attention_dispatch(chip):
    """``attention()`` itself, forward and backward, at the two train cells'
    shapes (``[8, 16, 1024, 64]``; a chip's share of fsdp4 ``[4, 25, 1024,
    64]``) and at the widest prefill shape below the 8,192 bucket (latent
    attention's 192 | 128 at 4,096): the blocks and heads a step that
    ``flash_plan`` picks have to fit the chip's VMEM."""
    from ray_tpu.ops import attention

    def grads(q, k, v):
        return jax.grad(
            lambda q, k, v: attention(q, k, v, causal=True)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    bf16 = lambda *shape: _on(chip, jax.ShapeDtypeStruct(shape, jnp.bfloat16))  # noqa: E731
    return [jax.jit(grads).lower(bf16(b, h, t, dk), bf16(b, h, t, dk), bf16(b, h, t, dv))
            for b, h, t, dk, dv in ((8, 16, 1024, 64, 64), (4, 25, 1024, 64, 64),
                                    (1, 64, 4096, 192, 128))]


PROGRAMS = {
    "gpt2_125m_train_step": _lower_train_step,
    "gpt2_medium_train_step_cell": _lower_medium_cell,
    "attention_dispatch_train_and_prefill": _lower_attention_dispatch,
    "serve_engine_gpt2": lambda chip: _lower_serve_engine(chip, "gpt2"),
    "serve_engine_llama": lambda chip: _lower_serve_engine(chip, "llama"),
    # GQA with four query heads a KV head, heads of 128: the decode kernel's
    # G > 1 path at another head size (the preset above has G = 3, dh = 64)
    "serve_engine_llama_gqa4": lambda chip: _lower_serve_engine(
        chip, "llama", n_heads=8, n_kv_heads=2, d_model=1024),
    # the serve-gpt2-xl-chat cell: 17 rows, 512 + 368 + 16 = 896 positions,
    # prefill calls of 1 x 512, 1 x 256, 2 x 128, 4 x 64
    "serve_engine_gpt2_xl_cell": lambda chip: _lower_serve_engine(
        chip, "gpt2", buckets=(64, 128, 256, 512), chunk=16, max_new=368, **XL),
    "serve_engine_exaone_cell": _lower_exaone_cell,
    "serve_engine_kimi_cell": _lower_kimi_cell,
    "serve_engine_granite_cell": _lower_granite_cell,
    "serve_engine_dots3_cell": _lower_dots3_cell,
    "serve_engine_evabyte_cell": _lower_evabyte_cell,
    "serve_engine_phi4_flash_cell": _lower_phi4_flash_cell,
    "serve_engine_keye_cell": _lower_keye_cell,
    "bert_base_forward": _lower_bert,
    "flash_attention_forward": lambda chip: _lower_flash(chip, "forward"),
    "flash_attention_backward": lambda chip: _lower_flash(chip, "backward"),
    "sparse_train_experts_and_band": _lower_sparse_train_parts,
}


# the three serve cells' decode programs: cached tensors a full layer (one
# flush kernel each), the slab's type, and the temporaries the PARENT of
# PR 38 planned for the program (the flush as slice updates)
FLUSHED_IN_PLACE = {
    "serve_engine_gpt2_xl_cell": (2, "bf16[48,17,25,64,896]", 1_408_124_416),
    "serve_engine_exaone_cell": (2, "bf16[1,33,8,128,4736]", 668_006_400),
    "serve_engine_kimi_cell": (1, "bf16[6,33,1,576,9344]", 258_276_352),
    # no parent: the decode chunk plans 0.08 GB of temporaries (PR 42); a
    # copy of the 3.7 GB of state or of a slab would show at once
    "serve_engine_granite_cell": (2, "bf16[2,49,8,128,2688]", 200_000_000),
}


# the four expert cells, every program in ``PROGRAMS``' order: the bodies that
# hold an expert layer (an unrolled family's sparse layers; Granite's three
# rolled runs and two attention layers), and the bytes of its arguments as the
# PARENT of PR 54 compiled them (gate and up two leaves): the served layout
# (one leaf, ``ew_gate_up``) holds the same bytes
HELD_EXPERTS = {
    "serve_engine_exaone_cell": (4, [
        8202638336, 8202630144, 8202630656, 8202637824, 8202633728,
        8202631680, 8202630656, 8202630656]),
    "serve_engine_kimi_cell": (5, [
        10477702144, 10477693952, 10477694464, 10477701632, 10477697536,
        10477695488, 10477694464]),
    "serve_engine_granite_cell": (5, [
        12947123712, 12947116032, 12947116544, 12947119616, 12947117568,
        12947116544, 12947116544, 12947117568]),
    "serve_engine_dots3_cell": (4, [
        5522225152, 5522216960, 5522217472, 5487359488]),
}


def _expert_weights_are_read_where_they_lie(program, bodies):
    """Two grouped matmuls an expert layer's body (gate and up as ONE call
    over the side-by-side leaf, then down; their group metadata once), and
    nothing in the program MAKES a tensor of an expert weight's shape in HBM:
    no concatenation, slice, copy or fusion of one (a layer's experts sliced
    out of a stack, or gate and up laid side by side in a step, would be a
    copy of the weights every call).  The compiler's own prefetch of a whole
    small leaf into its fast memory (``S(1)``) is not the program's doing."""
    text = program.as_text()
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 2 * bodies
    assert len(re.findall(r"%ragged-dot-metadata[.\d]* = ", text)) == bodies
    holds = lambda p: isinstance(p, dict) and "ew_down" in p  # noqa: E731
    held = list(filter(holds, jax.tree.leaves(
        program.args_info[0][0], is_leaf=holds)))
    assert len(held) >= 2 and not any(
        {"ew_gate", "ew_up"} & set(layer) for layer in held)
    shapes = set()
    for layer in held:
        for leaf in (layer["ew_gate_up"], layer["ew_down"]):
            *lead, n_held, rows, cols = leaf.shape
            assert leaf.dtype == jnp.bfloat16
            shapes |= {f"{n_held},{rows},{cols}", ",".join(map(str, leaf.shape)),
                       f"{n_held * (lead or [1])[0]},{rows},{cols}"}
        assert layer["ew_gate_up"].shape[-1] == 2 * layer["ew_down"].shape[-2]
    made = re.compile(
        r"^\s*(?:ROOT )?%[\w.\-]+ = bf16\[(?:" + "|".join(sorted(shapes))
        + r")\]\{[^ ]*\} ([\w\-]+)\(", re.M)
    makers = [(match.group(1), match.group(0)) for match in made.finditer(text)]
    assert [op for op, _ in makers].count("parameter") >= 2 * len(held)
    for op, line in makers:
        assert op in ("parameter", "get-tuple-element", "bitcast") or (
            "S(1)}" in line), line


@pytest.fixture(scope="module")
def compiled(chip):
    """Every program, lowered here and compiled side by side (the compiler
    runs outside the GIL; one after another they cost twice the wall)."""
    lowered = {name: lower(chip) for name, lower in PROGRAMS.items()}
    with ThreadPoolExecutor(max_workers=8) as pool:
        futures = {name: [pool.submit(low.compile) for low in lows]
                   for name, lows in lowered.items()}
    return futures  # .result() re-raises what the chip's compiler raised


def test_xl_fsdp4_step_gathers_bf16_weights(topo):
    """The four-chip cell's step (GPT-2 XL, mesh fsdp=4, B=16, full remat,
    compiled as benchmark/drivers/train.py does) for the described 2x2: in
    the layer loop bf16 weight shards are gathered, nothing with the 16
    sequences of the whole batch crosses chips, every gradient is summed
    across the chips in float32, and a chip's share fits."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.sharding import collective_profile, rules_for_mesh

    mesh = Mesh(np.array(topo.devices).reshape(4), ("fsdp",))
    cfg = gpt2.GPT2Config.gpt2_small(remat_policy="full", **XL)
    optimizer = gpt2.make_optimizer(lr=3e-4, warmup=20)
    rules = rules_for_mesh(mesh)
    replicated = NamedSharding(mesh, P())
    p_shard = gpt2.param_shardings(mesh, rules, cfg)
    shapes = jax.eval_shape(
        lambda k: gpt2.init_state(cfg, k, optimizer), jax.random.PRNGKey(0))
    o_shard = optax.tree_map_params(
        optimizer, lambda _, s: s, shapes["opt_state"], p_shard,
        transform_non_params=lambda _: replicated)
    s_shard = {"params": p_shard, "opt_state": o_shard, "step": replicated}
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        shapes, s_shard)
    tokens = jax.ShapeDtypeStruct(
        (16, cfg.max_seq_len), jnp.int32, sharding=NamedSharding(mesh, P("fsdp")))
    compiled = jax.jit(
        gpt2.make_train_step(cfg, optimizer, mesh), donate_argnums=(0,),
        out_shardings=(s_shard, None),
    ).lower(state, {"inputs": tokens, "targets": tokens}).compile()
    assert _fits(compiled) < 12 * 2**30  # 14.6 GB when 16 sequences lived on a chip
    # attention runs as the Pallas pair on each chip's own four sequences
    # (under shard_map: GSPMD would gather q, k and v to run it whole), so
    # the layer loops move between chips exactly what the parent of PR 43
    # moved (sandbox compile of that parent: 3 / 8 / 4 / 0 / 3)
    _flash_in_the_layer_loops(compiled, forward_again=True)
    in_loop = {k: v["in_loop"] for k, v in collective_profile(compiled).items()}
    assert {k: v["count"] for k, v in in_loop.items()} == {
        "all-reduce": 3, "all-gather": 8, "reduce-scatter": 4, "all-to-all": 0,
        "collective-permute": 3}, in_loop
    gathered = in_loop["all-gather"]["shapes"]
    assert gathered and all(s.startswith("bf16[") for s in gathered), gathered
    assert "bf16[1600,6400]" in gathered  # w1, whole, from its [400,6400] shard
    for kind, entry in in_loop.items():
        for label in entry["shapes"]:
            dims = label[label.index("[") + 1:-1].split(",")
            assert "16" not in dims and "1024" not in dims, (kind, label)
    # the sums fsdp introduces: the layers' matrices are reduce-scattered
    # as float32 ...
    scattered = in_loop["reduce-scatter"]["shapes"]
    assert len(scattered) >= 4 and all(s.startswith("f32[") for s in scattered)
    # ... and no collective of the BACKWARD pass (vectors' and the LM head's
    # all-reduces included) gives a bf16 sum.  (Forward, small bf16 vectors
    # are gathered as an all-reduce of zero-padded shards: no sum.)
    backward = [
        line for line in compiled.as_text().splitlines()
        if re.search(r"\b(all-reduce|reduce-scatter)(-start)?\(", line)
        and "transpose(jvp" in line]
    assert backward
    for line in backward:
        result = line.split("=", 1)[1].split("all-reduce")[0].split("reduce-scatter")[0]
        assert "bf16[" not in result and "f16[" not in result, line[:300]


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_program_compiles_for_v5e(compiled, name):
    programs = [f.result() for f in compiled[name]]
    needs = [_fits(c) for c in programs]
    if name == "gpt2_125m_train_step":
        assert needs[0] > 1 * 2**30  # params + adam moments alone are 1.5 GB
        _flash_in_the_layer_loops(programs[0], forward_again=False)
    if name == "gpt2_medium_train_step_cell":
        # 4.26 GB of state + 9.63 GB of temporaries (the parent of PR 43
        # planned 9.22: the kept attention results are 0.41 GB, lane-dense as
        # the kernel writes them) of the chip's 15.75 GB
        _flash_in_the_layer_loops(programs[0], forward_again=False)
        assert 13.5e9 < needs[0] < 14.5e9, needs
    if name == "attention_dispatch_train_and_prefill":
        for program in programs:
            assert program.as_text().count("tpu_custom_call") == 2
    if name == "sparse_train_experts_and_band":
        experts, band = (p.as_text() for p in programs)
        # the exchange: the experts gathered whole in bf16 (the compiler's
        # own all-gather), the gradients' blocks sent home in float32 by
        # three shifts a matrix, every one a start/done pair with the
        # backward's grouped matmuls scheduled between: under ``moe.exchange``
        # no sum across the chips is ONE instruction that holds the chip
        # (PR 58: the four reduce-scatters a layer were, 12 ms each)
        from ray_tpu.parallel.sharding import collective_profile

        moved = collective_profile(experts)
        shifted = moved["collective-permute"]["outside"]
        assert shifted["count"] == 2 * 3 and shifted["synchronous"] == 0
        assert len(shifted["start_to_done"]) == 6 and min(shifted["start_to_done"]) > 0
        assert {"f32[16,768,2560]", "f32[16,2560,1536]"} <= set(shifted["shapes"])
        assert shifted["max_operand_bytes"] == 16 * 2560 * 1536 * 4
        assert {"bf16[64,768,2560]", "bf16[64,2560,1536]"} <= set(
            moved["all-gather"]["outside"]["shapes"])
        alone = re.compile(r" (reduce-scatter|all-reduce|all-to-all"
                           r"|collective-permute)\(")
        assert not [line[:200] for line in experts.splitlines()
                    if "moe.exchange" in line and alone.search(line)]
        # the grouped matmuls (``lax.ragged_dot`` and its transposes in both
        # operands) over a chip's own 98,304 pairs, and nothing that follows
        # the routing: no loop, and no scatter but the sort's own
        assert "moe.expert_ffn" in experts
        assert not re.search(r"= \S+ while\(", experts)
        assert "scatter-add" not in experts
        # SEVEN grouped matmuls: two forward, gate-and-up again in the replay,
        # four backward.  The gate goes in before the down matmul, so the
        # backward pass needs nothing of that matmul's result: the replay
        # leaves it out (eight before PR 62), and of the float32 [pairs, D]
        # blocks gathered only the forward's way back to the tokens is left
        # (three: the replay's, and the weighted cotangent's).  The rows, the
        # replay's rows and the cotangent's rows are bf16 gathers; the two
        # ways back to the tokens (the result's, the rows' gradient's) gather
        # k blocks of [N, D] that are added with no layout change between
        assert len(re.findall(r"^\s*%ragged-dot[\w\-.]* = \S+ custom-call\(",
                              experts, flags=re.M)) == 7
        gathered = lambda shape: sorted(re.findall(  # noqa: E731
            r" = (\w+)\[%s\]\S* gather\(" % shape, experts))
        assert gathered("98304,2560") == ["bf16"] * 3
        assert gathered("6,16384,2560") == ["bf16", "f32"]
        assert not re.search(r"\[16384,6,2560\]", experts)
        # (4.2 GiB while one reduce-scatter took the gradients home: a
        # matrix's float32 blocks are in flight, three sent and three
        # received, while the backward's matmuls still hold their operands;
        # 5.95 GiB with the forward pass and the replay in the program, 6.57
        # for the backward pass alone while it held float32 [pairs, D] blocks)
        assert needs[0] < 6.5 * 2**30, needs
        # both kernels of the pair under the band, no masked scores in HBM
        assert band.count("tpu_custom_call") == 2
        # (q, k, v, their gradients and the result are 0.8 GB)
        assert "flash_attention_bwd" in band and needs[1] < 2 * 2**30, needs
    if name in ("serve_engine_exaone_cell", "serve_engine_kimi_cell",
                "serve_engine_dots3_cell", "serve_engine_evabyte_cell",
                "serve_engine_phi4_flash_cell", "serve_engine_keye_cell"):
        # ONE program for every part of every prompt above 2,048 tokens, under
        # the name a trace's readers sum the prefill programs by; the flash
        # kernel is in it on every layer that reads a slab
        assert programs[0].as_text().startswith("HloModule jit_llm_prefill_part")
        assert "flash_attention_fwd" in programs[0].as_text()
    if name.startswith("serve_engine"):
        # the decode chunk writes the big cache once, at its end, in place:
        # no scatter anywhere in it, and the cache's leaves (k, pos, v, and
        # a family's rings: the arguments after the parameters, outputs 1..)
        # come back in the buffers they arrived in.  The cut chunk (the
        # same steps under a runtime bound) likewise, under a name of its own
        assert programs[1].as_text().startswith("HloModule jit__unknown")
        assert programs[2].as_text().startswith("HloModule jit_llm_decode_cut")
        kernels = [p.as_text().count("tpu_custom_call") for p in programs[1:3]]
        assert kernels[0] == kernels[1], kernels
        for decode in programs[1:3]:
            text = decode.as_text()
            assert not re.search(r"\bscatter\(", text)
            # every engine's cache is whole 128-position tiles, so the cache
            # half of decode attention reaches the chip's compiler as the
            # ragged kernel, for G = 1 (GPT-2) and G > 1 (Llama) alike
            assert text.count("tpu_custom_call") >= 1
            n_params = len(jax.tree.leaves(decode.args_info[0][0]))
            if name == "serve_engine_keye_cell":
                # (the tower's leaves are no argument of a decode program:
                # the compiled program's numbering skips them)
                n_params -= len(jax.tree.leaves(decode.args_info[0][0]["vision"]))
            n_cache = len(jax.tree.leaves(decode.args_info[0][1]))
            aliased = re.search(
                r"input_output_alias=\{(.*?) \}, entry", text).group(1)
            for out_index, arg in enumerate(
                    range(n_params, n_params + n_cache), start=1):
                assert f"{{{out_index}}}: ({arg}, {{}}, may-alias)" in aliased, aliased
    if name in HELD_EXPERTS:
        bodies, parent_arguments = HELD_EXPERTS[name]
        assert [p.memory_analysis().argument_size_in_bytes
                for p in programs] == parent_arguments
        for program in programs:
            _expert_weights_are_read_where_they_lie(program, bodies)
    if name in FLUSHED_IN_PLACE:
        # the chunk's flush reaches the chip's compiler as the kernel, by
        # name, once a cached tensor; no update of slab size is left beside
        # it; and the slab is merged where it lies: a copy of it (2.34 GB,
        # 0.32 GB, 2.13 GB) would show in the temporaries, which stay at
        # what the slice updates planned (sandbox compiles of PR 38's parent)
        tensors, slab, parent_temp = FLUSHED_IN_PLACE[name]
        for decode in programs[1:3]:
            text = decode.as_text()
            flushes = [line for line in text.splitlines()
                       if re.search(r"%cache_flush[.\d]* = ", line)]
            assert len(flushes) == tensors, flushes
            for line in flushes:
                assert f"= {slab}{{" in line and "tpu_custom_call" in line
                assert "output_to_operand_aliasing={{}: (2, {})}" in line
            for line in text.splitlines():
                result = line.split("dynamic-update-slice(")[0]
                assert result == line or f" {slab}{{" not in result, line[:300]
            assert decode.memory_analysis().temp_size_in_bytes < parent_temp + 2**20
    if name == "serve_engine_gpt2_xl_cell":
        # the layout cliff (ISSUE 28; the cell's `assumed` has the same one
        # at 784 positions): a cache the compiler re-lays-out costs 8-12 GB
        # of temporaries in converted copies.  In place it needs 1.31 GiB
        # (a slab-sized copy feeding the kernel would show here too).
        for decode in programs[1:3]:
            assert decode.memory_analysis().temp_size_in_bytes < 1.5 * 2**30
        # a prefill call is at most 512 padded tokens wide: its temporaries
        # are a small fraction of the 16-row calls' (1.9 GiB at 16 x 512)
        for prefill in programs[:1] + programs[3:]:
            assert prefill.memory_analysis().temp_size_in_bytes < 0.5 * 2**30
    if name == "serve_engine_exaone_cell":
        # the ragged kernel on the full layer, the grouped matmuls of four
        # expert layers (two each and their metadata); 7.42 GB of weights
        # and 0.78 GB of cache resident, well over a quarter of the chip
        assert programs[1].as_text().count("tpu_custom_call") >= 1 + 4 * 3
        assert all(8.0e9 < need < 10.5e9 for need in needs), needs
    if name == "serve_engine_kimi_cell":
        # the latent kernel once a layer, the grouped matmuls of five expert
        # layers; a prompt's part attends through the flash kernel (six
        # layers); 8.35 GB of weights and 2.13 GB of cache resident
        assert programs[1].as_text().count("tpu_custom_call") >= 6 + 5 * 3
        assert "ragged_latent_decode_attention" in programs[1].as_text()
        assert programs[0].as_text().count("tpu_custom_call") >= 6 + 5 * 3
        assert all(10.4e9 < need < 15.5e9 for need in needs), needs
    if name == "serve_engine_granite_cell":
        # the state's update reaches the chip's compiler as the kernel, once
        # a rolled run of Mamba layers (three runs), the whole cache of
        # states its operand AND its result; the attention layers' ragged
        # kernel; the grouped matmuls over the WHOLE stack of experts (no
        # layer's 170 MB sliced out to feed them: ``HELD_EXPERTS`` above; two
        # calls and their metadata a body); 8.12 GB of weights and
        # 4.82 GB of cache resident: 13.0-13.8 GB a program
        for decode in programs[1:3]:
            text = decode.as_text()
            updates = [line for line in text.splitlines()
                       if re.search(r"%ssm_state_update[.\d]* = ", line)]
            assert len(updates) == 3, len(updates)
            for line in updates:
                assert "f32[18,49,64,128,128]" in line.split("custom-call(")[0]
                assert "output_to_operand_aliasing={{0}: (3, {})}" in line
            assert text.count("ragged_decode_attention") >= 2
            assert text.count("tpu_custom_call") >= 3 + 2 + 2 + 5 * 3
        assert all(12.9e9 < need < 14.0e9 for need in needs), needs
    if name == "serve_engine_dots3_cell":
        # the latent kernel FIVE times a decode step: once a full layer,
        # given the step's selection as a further operand, and once a sliding
        # layer (under a name of its own: a trace's rows of the full layers'
        # kernel stay theirs), over the tiles of the entries a live row's ring
        # holds and given the step's window (PR 52: no layer's ring is sliced out of
        # ``c_ring``, 82.7 MB a layer, for masked einsums over every row);
        # the flush kernel over ``c`` and ``idx_k``; the grouped matmuls of
        # four expert layers.  Every prompt above one part of 2,048 tokens
        # goes through the PART program (the first of the list), which runs
        # the Pallas forward kernel with the selection as its fourth operand
        # and a runtime key length on both full layers (a [128, 2048, 16384]
        # score tensor never exists: it plans 2.3 GB of temporaries where the
        # 16,384-token call, which is no longer built, planned 4.3); the
        # 2,048 bucket selects every position and takes the plain causal
        # kernel.  3.64 GB of weights and 1.85 GB of cache resident
        for decode in programs[1:3]:
            text = decode.as_text()
            kernels = [line for line in text.splitlines() if re.search(
                r"%ragged_latent_(decode|ring)_attention[.\d]* = ", line)]
            assert sum("%ragged_latent_ring" in line for line in kernels) == 3
            assert all(("attention.latent_window" in line)
                       == ("%ragged_latent_ring" in line) for line in kernels)
            assert len(kernels) == 5, len(kernels)
            for line in kernels:  # the mask: a slot's tiles, in groups of 8
                ring = "%ragged_latent_ring" in line
                assert ("f32[33,16,128]" if ring else "f32[33,144,128]") in line
            for line in text.splitlines():  # one layer's ring, sliced out
                made = line.split(" fusion(")[0].split(" copy(")[0]
                assert made == line or not re.search(
                    r" bf16\[33,(1,)?1088,\d+\]\{", made), line[:200]
            flushes = [line for line in text.splitlines()
                       if re.search(r"%cache_flush[.\d]* = ", line)]
            assert len(flushes) == 2, flushes
            assert text.count("tpu_custom_call") >= 5 + 2 + 4 * 3
            assert decode.memory_analysis().temp_size_in_bytes < 0.6e9
        for prefill in programs[:1] + programs[3:]:
            assert prefill.as_text().count("flash_attention_fwd") >= 2
            assert not re.search(r"f32\[1,128,\d{4,5},\d{4,5}\]", prefill.as_text())
        assert programs[0].memory_analysis().temp_size_in_bytes < 2.6e9
        assert all(5.5e9 < need < 8.5e9 for need in needs), needs
    if name == "serve_engine_keye_cell":
        # the evidence for 12 slots (ISSUE 59's arithmetic: 9.64 GB of weights,
        # 2.98 GB of cache, 12.6 GB resident): the decode chunk, whole and cut,
        # plans 11.73 GB of arguments (the tower's 0.89 GB are no argument of
        # it) and 0.18 GB of temporaries; a 2,048-token part over a static
        # 16,384 positions 0.99 GB of temporaries (12.74 GB in all: the
        # largest program); the 2,048 bucket 0.21; the tower call 0.89 GB of
        # arguments and 0.03 of temporaries.  The ragged kernel SIX times a
        # decode step, under a name of its own and handed the step's selection
        # (f32[13, 144, 128]: a slot's 137 tiles in groups of 8), the flush
        # kernel over k, v AND idx_k, the grouped matmuls over 128 experts a
        # layer read where they lie; the flash kernel on every layer of both
        # prefill programs (the part's with the selection as its operand; no
        # [32, 2048, 16384] score tensor exists)
        for decode in programs[1:3]:
            text = decode.as_text()
            kernels = [line for line in text.splitlines() if re.search(
                r"%ragged_sparse_gqa_attention[.\d]* = ", line)]
            assert len(kernels) == 6 and all(
                "f32[13,144,128]" in line and "attention.gqa_sparse" in line
                for line in kernels), len(kernels)
            assert not re.search(r"%ragged_decode_attention[.\d]* = ", text)
            flushes = [line for line in text.splitlines()
                       if re.search(r"%cache_flush[.\d]* = ", line)]
            assert len(flushes) == 3, flushes
            assert sum("bf16[6,13,4,128,17536]" in line for line in flushes) == 2
            assert sum("bf16[6,13,1,64,17536]" in line for line in flushes) == 1
            assert text.count("tpu_custom_call") >= 6 + 3 + 6 * 3
            _expert_weights_are_read_where_they_lie(decode, 6)
            assert decode.memory_analysis().temp_size_in_bytes < 0.3e9
            assert 11.6e9 < decode.memory_analysis().argument_size_in_bytes < 11.9e9
        for prefill in (programs[0], programs[3]):
            assert prefill.as_text().count("flash_attention_fwd") >= 6
            assert not re.search(r"f32\[1,32,2048,\d{4,5}\]", prefill.as_text())
            _expert_weights_are_read_where_they_lie(prefill, 6)
            # every expert is held: a part's 16,384 pairs go through the
            # grouped matmuls as ONE block (``held_experts_ffn(all_held=)``)
            # and a token's rows are gathered back, nothing is added into
            # the result trip by trip (the parent's six scatter-adds)
            assert "bf16[16384,2048]" in prefill.as_text()
            assert not re.search(r"\bscatter\(", prefill.as_text())
        # (the block's temporaries: 0.21 -> 0.46 GB in the 2,048 bucket's
        # program; the part's largest are elsewhere: 0.99 -> 0.97 GB)
        assert programs[0].memory_analysis().temp_size_in_bytes < 1.2e9
        assert programs[3].memory_analysis().temp_size_in_bytes < 0.6e9
        tower = programs[4]
        assert tower.as_text().startswith("HloModule jit_llm_vision_encode")
        assert all(scope in tower.as_text() for scope in (
            "vision.patch_embed", "vision.attention", "vision.mlp", "vision.merge"))
        assert tower.memory_analysis().temp_size_in_bytes < 0.2e9
        assert 0.85e9 < tower.memory_analysis().argument_size_in_bytes < 0.95e9
        assert all(11.7e9 < need < 13.0e9 for need in needs[:4]), needs
    if name == "serve_engine_phi4_flash_cell":
        # ONE slab read by eight layers: the ragged kernel under a name of its
        # own, once for the owner and once in the rolled (GMU, cross) loop;
        # the rings read a live row's tiles at a time, under their own name,
        # with the step's window as the mask (f32[33, 8, 128]: a slot's eight
        # tiles); the Mamba-1 update over the plan's rows, in the rolled pairs
        # and in the memory layer; the flush over the slab's k and v.  7.71 GB
        # of weights and 4.63 GB of cache resident: 12.33 GB of arguments
        for decode in programs[1:3]:
            text = decode.as_text()
            called = lambda kernel: [  # noqa: E731
                line for line in text.splitlines()
                if re.search(rf"%{kernel}[.\d]* = ", line)]
            assert len(called("ragged_shared_kv_attention")) == 2
            assert all("attention.shared_kv" in line
                       for line in called("ragged_shared_kv_attention"))
            rings = called("ragged_ring_attention")
            assert len(rings) == 1 and "f32[33,8,128]" in rings[0]
            assert "attention.diff_window" in rings[0]
            assert len(called("ssm_selective_state_update")) == 2
            assert not called("ragged_decode_attention")
            flushes = called("cache_flush")
            assert len(flushes) == 2, flushes
            assert all("bf16[1,33,10,128,18560]" in line for line in flushes)
            assert decode.memory_analysis().temp_size_in_bytes < 0.9e9
            assert decode.memory_analysis().argument_size_in_bytes > 12.3e9
        # the part program and the 2,048 bucket's: the scan as the kernel that
        # walks time (rolled pairs + the memory layer), and the layers above
        # the slab under a conditional (no row may end a prompt)
        for prefill in (programs[0], programs[3]):
            text = prefill.as_text()
            assert len(re.findall(r"%ssm_selective_scan[.\d]* = ", text)) == 2
            assert re.search(r"\bconditional\(", text)
            assert prefill.memory_analysis().temp_size_in_bytes < 0.7e9
        assert "flash_attention_fwd" in programs[0].as_text()
        assert all(12.5e9 < need < 13.5e9 for need in needs), needs
    if name == "serve_engine_evabyte_cell":
        # the ragged kernel twice a layer (the window, then the summaries:
        # each under its own named scope, which the traced run's ``scope:*``
        # rows are summed by), the flush kernel over the window's k and v,
        # and the roll-over's pooling in a loop over the slots that rolled,
        # writing the summaries where they lie (no copy of a 2.6 GB slab:
        # the chunk plans 0.85 GB of temporaries).  3.26 GB of weights and
        # 7.42 GB of cache resident: 62 % of the chip
        for decode in programs[1:3]:
            text = decode.as_text()
            reads = [line for line in text.splitlines()
                     if re.search(r"%ragged_decode_attention[.\d]* = ", line)]
            assert len(reads) == 2, len(reads)
            assert sum("attention.eva_window" in line for line in reads) == 1
            assert sum("attention.eva_summary" in line for line in reads) == 1
            flushes = [line for line in text.splitlines()
                       if re.search(r"%cache_flush[.\d]* = ", line)]
            assert len(flushes) == 2, flushes
            assert all("bf16[8,17,32,128,2176]" in line for line in flushes)
            assert "attention.eva_pool" in text and "attention.eva_merge" in text
            assert decode.memory_analysis().temp_size_in_bytes < 1.0e9
        assert "attention.eva_summary" in programs[0].as_text()
        assert "attention.eva_pool" in programs[0].as_text()
        assert "flash_attention_fwd" in programs[3].as_text()
        for prefill in (programs[0], programs[3]):
            assert not re.search(r"f32\[1,32,2048,\d{4}\]", prefill.as_text())
            assert prefill.memory_analysis().temp_size_in_bytes < 1.0e9
        assert all(11.0e9 < need < 12.0e9 for need in needs), needs
    if name.startswith("flash_attention"):
        # must reach the chip's compiler as kernels, not as an XLA fallback:
        # one forward; the backward kernel + the forward it differentiates
        want = 1 if name.endswith("forward") else 2
        assert programs[0].as_text().count("tpu_custom_call") >= want
