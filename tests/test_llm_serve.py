"""LLM serving: continuous-batching engine + Serve deployment.

Pins that iteration-level batching (requests admitted/freed mid-stream)
reproduces one-shot generation exactly under greedy decoding, and that the
engine works behind a Serve replica (the decode analog of the reference's
``serve/_private/replica.py:250`` request path).
"""

import jax.numpy as jnp
import pytest
from family_harness import engine, one_shot, run_engine, shared_engine

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.llm import llm_deployment

pytestmark = pytest.mark.usefixtures("kept_engine_programs")
# the cache-tile tests' engine: two slots of two tiles, chunks of 3
TILES = dict(seed=2, n_slots=2, max_new_tokens=8, decode_chunk_steps=3,
             prefill_buckets=(8, 160))


@pytest.fixture
def serve_instance():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    client = serve.start(serve.HTTPOptions(host="127.0.0.1", port=0))
    yield client
    serve.shutdown()
    ray_tpu.shutdown()


def test_engine_matches_one_shot_under_continuous_batching():
    eng, cfg, params = engine(
        "gpt2", n_slots=2, max_new_tokens=8, decode_chunk_steps=3,
        prefill_buckets=(8, 16))
    eng.start()
    try:
        prompts = [[3, 17, 5], [9, 2], [11, 4, 7, 1], [6], [8, 8, 3, 2, 1]]
        futs = [eng.submit(p, 8) for p in prompts]  # 5 requests, 2 slots
        got = [f.result(timeout=120) for f in futs]
    finally:
        eng.stop()
    assert got == one_shot(params, cfg, prompts, 8)
    assert eng.stats()["total_requests"] == 5


def test_engine_eos_and_max_new():
    kw = dict(seed=1, n_slots=1, decode_chunk_steps=5, prefill_buckets=(8,))
    eng2, cfg, params = engine("gpt2", max_new_tokens=3, **kw)
    ref, = one_shot(params, cfg, [[5, 9, 2, 4]], 12)
    # EOS semantics: the stream stops at the FIRST occurrence of the eos
    # value (tiny random models cycle quickly, so derive the expectation
    # from wherever the chosen value first appears)
    eos = ref[-1]
    idx = ref.index(eos)
    eng, _, _ = engine("gpt2", max_new_tokens=12, eos_id=eos, **kw)
    eng.start()
    try:
        out = eng.generate([5, 9, 2, 4], timeout=120)
    finally:
        eng.stop()
    assert out == ref[:idx + 1]  # stops AT the eos token
    # max_new cutoff
    eng2.start()
    try:
        out2 = eng2.generate([5, 9, 2, 4], timeout=120)
    finally:
        eng2.stop()
    assert out2 == ref[:3]


def test_cache_tiles_counts_what_the_decode_chunks_read():
    """``perf_stats()["cache_tiles"]``: at every decode dispatch a slot
    standing at ``n`` positions adds ``ceil(n / 128)`` tiles to ``read_full``
    and the dispatch adds the whole padded slab to ``padded`` — counted on
    the engine thread from prompt lengths and scheduled tokens, no device
    read.  The cache itself is whole tiles."""
    eng, cfg, params = engine("gpt2", **TILES)  # never started
    assert eng.cache["k"].shape[-1] == 256  # 160 + 8 + 3 = 171 -> two tiles
    assert eng.perf_stats()["cache_tiles"] == {
        "read_full": 0, "read_window": 0, "held_window": 0, "padded": 0,
        "flushed": 0,
        "layers": {"full": cfg.n_layers, "window": 0},
        # k and v of every head, 128 positions, float32 here
        "tile_bytes": {"full": 2 * cfg.d_model * 128 * 4, "window": 0}}
    prompts = [[1 + i % 50 for i in range(127)], [3, 17, 5],
               [1 + i % 40 for i in range(130)]]
    futs = [eng.submit(p, 8) for p in prompts]  # 3 requests, 2 slots
    seen = [eng.perf_stats()["cache_tiles"]]
    while not all(f.done() for f in futs):
        eng.step()
        seen.append(eng.perf_stats()["cache_tiles"])
    # a request of 8 tokens is dispatched in three chunks of 3 steps, its
    # slot standing at len, len + 3 and len + 6 positions when they begin
    want = sum(-(-(len(p) + 3 * chunk) // 128)
               for p in prompts for chunk in range(3))
    assert want == (1 + 2 + 2) + 3 + 6
    last = seen[-1]
    assert last["read_full"] == want and 0 < want <= last["padded"]
    assert last["read_window"] == 0  # no window layer in this family
    dispatches = last["padded"] // (3 * 2)  # 3 rows (one scratch) x 2 tiles
    assert last["padded"] == dispatches * 6 and dispatches >= 6
    for a, b in zip(seen, seen[1:]):
        assert a["read_full"] <= b["read_full"] and a["padded"] <= b["padded"]
    assert [f.result() for f in futs] == one_shot(params, cfg, prompts, 8)


@pytest.mark.parametrize("length,flushed", [
    # chunks of 3 steps; the slot stands at length, length + 3, length + 6
    pytest.param(3, [1, 1, 1], id="one_tile_a_dispatch"),
    pytest.param(126, [2, 1, 1], id="first_chunk_crosses_128"),  # 126..128
    pytest.param(125, [1, 1, 1], id="chunk_ends_on_the_boundary"),
    pytest.param(123, [1, 2, 1], id="second_chunk_crosses_128"),
    pytest.param(0, [], id="nothing_dispatched"),
])
def test_cache_tiles_counts_what_the_flushes_write(length, flushed):
    """``perf_stats()["cache_tiles"]["flushed"]``: a dispatched row adds the
    tile its chunk's columns fall in and, where they cross a 128-position
    boundary, the next (what the flush kernel writes a full layer a tensor,
    against ``padded``); a slot that sits the chunk out and a tick that
    dispatches nothing add none.  (One engine for the five cases: its
    counters are differenced.)"""
    eng, cfg, params = shared_engine("gpt2", **TILES)
    had = eng.perf_stats()["cache_tiles"]["flushed"]
    seen, prompt = [], [1 + i % 50 for i in range(length)]
    if not prompt:
        assert eng.step() is False
    else:
        fut = eng.submit(prompt, 8)
        seen = [s["flushed"] for s in run_engine(eng, [fut]) if s["padded"]]
        assert [fut.result()] == one_shot(params, cfg, [prompt], 8)
    assert seen == flushed
    assert eng.perf_stats()["cache_tiles"]["flushed"] - had == sum(flushed)


def test_a_queue_longer_than_the_slots_is_admitted_in_narrow_calls(monkeypatch):
    """16 queued prompts, 4 slots, the default budget (every slot at the
    largest bucket): each tick takes the queue's head into as many calls as
    it has free slots for, consecutive prompts of one bucket sharing a call
    up to its rows (2 / 1 at CALL_TOKENS 16), no prompt overtaking another or
    padded past its own bucket; the tallies are exact and every answer is
    the one-shot path's."""
    from ray_tpu.serve import llm

    monkeypatch.setattr(llm, "CALL_TOKENS", 16)
    eng, cfg, params = engine(  # never started: the test is the engine thread
        "gpt2", seed=3, n_slots=4, max_new_tokens=6, decode_chunk_steps=3,
        prefill_buckets=(8, 16))
    assert eng._rows == {8: 2, 16: 1} and eng._tick_tokens == 4 * 16
    lens = (3, 5, 12, 4, 9, 10, 2, 6, 7, 1, 11, 13, 8, 8, 8, 8)
    prompts = [[1 + (7 * i + j) % 90 for j in range(n)]
               for i, n in enumerate(lens)]
    futs = [eng.submit(p, 6) for p in prompts]
    ticks = [s["admitted"] for s in run_engine(eng, futs) if s["admitted"]]
    assert ticks == [[[3, 5], [12], [4]], [[9], [10], [2, 6]],
                     [[7, 1], [11], [13]], [[8, 8], [8, 8]]]
    assert eng.perf_stats()["prefill"] == {
        "8": {"calls": 6, "rows": 12, "padded_tokens": 96, "prompts": 11,
              "live_tokens": 60},
        "16": {"calls": 5, "rows": 5, "padded_tokens": 80, "prompts": 5,
               "live_tokens": 55}}
    # a dense family routes nothing: no routing counts, no ``rows_computed``
    assert eng.perf_stats()["moe"]["prefill"] is None
    assert [f.result() for f in futs] == one_shot(params, cfg, prompts, 6)


def test_llm_deployment_behind_serve(serve_instance):
    dep = llm_deployment(
        "gpt2", "tiny",
        engine_kwargs=dict(n_slots=2, max_new_tokens=6,
                           decode_chunk_steps=3, prefill_buckets=(8,)),
        config_kwargs=dict(dtype=jnp.float32),
    )
    handle = serve.run(dep.bind(), port=0)
    refs = [handle.remote({"tokens": [3, 5, 7], "max_new_tokens": 6})
            for _ in range(4)]
    outs = ray_tpu.get(refs, timeout=300)
    assert all(o == outs[0] for o in outs)  # greedy: identical prompts agree
    assert len(outs[0]["tokens"]) == 6
    stats = ray_tpu.get(handle.stats.remote(), timeout=60)
    assert stats["total_requests"] == 4
