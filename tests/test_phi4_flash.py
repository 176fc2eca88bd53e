"""The Phi-4-mini-flash family (``ray_tpu.models.phi4_flash``: SambaY with
differential attention) at a tiny size on the CPU, against its plain reference
(``benchmark/reference/phi4_flash_ref.py``: float32, the recurrence a position
at a time, the four softmax-value products of a head pair written out, the
FULL stack at every position): the whole forward, prefill then decode through
the cache, a prompt in parts, the prefill's early exit, the Mamba-1 ops and
their kernels, the differential read through padded queries, and the engine.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_harness import (
    decode_chunk,
    engine,
    full_forward,
    prefill_at,
    run_engine,
    tiny_model,
)

from benchmark.reference import phi4_flash_ref as ref
from ray_tpu.models import generate as gen
from ray_tpu.models import phi4_flash as pf
from ray_tpu.ops import ssm

attention = sys.modules["ray_tpu.ops.attention"]
pytestmark = pytest.mark.usefixtures("kept_engine_programs")

FAMILY = "phi4_flash"


def sizes(cfg):
    return dict(n_layers=cfg.n_layers, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, sliding_window=cfg.sliding_window,
                norm_eps=cfg.norm_eps)


@pytest.fixture(scope="module")
def model():
    return tiny_model(FAMILY, seed=3)


def ref_logits(params, cfg, seq, pad_to=64):
    """The reference's logits of ``seq``, computed at a padded width."""
    row = list(seq) + [0] * (-len(seq) % pad_to)
    return ref.logits(params, np.asarray([row]), sizes(cfg))[0, :len(seq)]


def test_layer_pattern_and_cache_kinds(model):
    cfg, params = model
    assert cfg.layer_types == ("mamba", "window", "mamba", "window", "mamba",
                               "full", "gmu", "cross")
    assert gen.layer_windows(cfg) == (-1, 8, -1, 8, -1, 0, gen.UNCACHED, -8)
    assert gen.shared_cache(cfg) == 5 and gen.reads_layer(-8) == 5
    assert gen.can_continue(cfg)
    cache = gen.init_cache(cfg, 3, 128)
    # ONE slab whatever the readers, a ring a window layer, a state a Mamba
    # layer; the pair layout: KV / 2 heads of 2 dh
    assert cache["k"].shape == (1, 3, 1, 16, 128)
    assert cache["k_ring"].shape == (2, 3, 1, 16, 16)
    assert cache["ssm"].shape == (3, 3, 4, 1, 64) and cache["ssm"].dtype == jnp.float32
    assert cache["conv"].shape == (3, 3, 3, 64)
    published = pf.Phi4FlashConfig()
    assert published.d_inner == 5120 and published.mamba_dt_rank == 160
    assert published.layer_types.count("cross") == 7
    assert published.state_cache["ssm"][0] == (16, 40, 128)


def test_apply_is_the_reference(model):
    cfg, params = model
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 64))
    with jax.default_matmul_precision("highest"):
        got = full_forward(FAMILY, params, cfg, [list(t) for t in toks], pad_to=64)
    want = ref.logits(params, toks, sizes(cfg))
    assert np.abs(got - want).max() < 2e-4
    assert want.std() > 0.02  # the logits say something


def _served(params, cfg, prompts, slots, chunks, steps=8, cache_len=128):
    """Prefill ``prompts`` in one call, decode ``chunks`` (None: whole, else
    cut to n) -> per prompt the served tokens and the LOGIT gap of each under
    the reference's best."""
    n_slots = max(slots) + 2
    cache = gen.init_cache(cfg, n_slots, cache_len)
    width = -(-max(map(len, prompts)) // 8) * 8
    toks = np.zeros((len(prompts), width), np.int32)
    for r, p in enumerate(prompts):
        toks[r, :len(p)] = p
    with jax.default_matmul_precision("highest"):
        logits, cache, _ = prefill_at(
            params, cfg, jnp.asarray(toks), jnp.asarray([len(p) for p in prompts]),
            cache, jnp.asarray(slots))
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        tok = jnp.zeros((n_slots,), jnp.int32).at[jnp.asarray(slots)].set(first)
        active = jnp.zeros((n_slots,), bool).at[jnp.asarray(slots)].set(True)
        served = [[int(t)] for t in first]
        for n in chunks:
            emitted, cache, active, _, _ = decode_chunk(
                params, cfg, cache, tok, active, steps=steps, n=n)
            tok = emitted[:, -1]
            for out, slot in zip(served, slots):
                out += [int(t) for t in np.asarray(emitted)[slot, :n]]
    gaps = []
    for p, out in zip(prompts, served):
        at = ref_logits(params, cfg, p + out)[len(p) - 1:len(p) - 1 + len(out)]
        gaps.append(float((at.max(-1) - at[np.arange(len(out)), out]).max()))
    return served, gaps, cache


def test_prefill_then_decode_through_the_cache_is_the_reference(model, positions):
    """Rings that wrap (window 8, ring 16), contexts past the window, the
    shared slab read by the owner and the cross layer, a cut chunk: every
    served token's logit is the reference's best of ONE forward over prompt +
    served tokens (logits, not tokens: within 2e-4)."""
    cfg, params = model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (37, 5, 20)]
    served, gaps, cache = _served(
        params, cfg, prompts, [0, 3, 2], [None, 5, None, None],
        cache_len=positions(128))
    assert [len(s) for s in served] == [30] * 3
    assert max(gaps) < 2e-4, gaps
    assert [int(p) for p in cache["pos"]] == [37 + 29, 0, 20 + 29, 5 + 29, 0]


def _whole_and_parts(params, cfg, prompt, part, dirty=None):
    n = len(prompt)
    whole = np.zeros((1, 64), np.int32)
    whole[0, :n] = prompt
    want_logits, want, _ = prefill_at(
        params, cfg, jnp.asarray(whole), jnp.asarray([n]),
        gen.init_cache(cfg, 2, 128), jnp.asarray([1]))
    cache = gen.init_cache(cfg, 2, 128)
    if dirty is not None:  # a longer request sat in the slot before
        row = np.zeros((1, 64), np.int32)
        row[0, :len(dirty)] = dirty
        _, cache, _ = prefill_at(params, cfg, jnp.asarray(row),
                                 jnp.asarray([len(dirty)]), cache, jnp.asarray([1]))
    calls = []
    for at in range(0, n, part):
        row = np.zeros((1, part), np.int32)
        own = prompt[at:at + part]
        row[0, :len(own)] = own
        logits, cache, _ = prefill_at(
            params, cfg, jnp.asarray(row), jnp.asarray([len(own)]), cache,
            jnp.asarray([1]), offsets=jnp.asarray([at]), bound=64,
            final=jnp.asarray([at + part >= n]))
        calls.append(np.asarray(logits))
    return want_logits, want, calls, cache


@pytest.mark.parametrize("part,n", [(4, 23), (8, 23), (32, 37), (16, 33)])
def test_a_prompt_in_parts_equals_the_prompt_whole(model, part, n):
    """State, conv tail, rings (the entries that hold a position), slab,
    ``pos`` and the first token's logits, whatever the part; a part that ends
    no prompt returns zeros (the upper stack did not run); a last part of ONE
    token still sees the inputs before it."""
    cfg, params = model
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, cfg.vocab_size, n).tolist()
    dirty = rng.integers(1, cfg.vocab_size, 60).tolist()
    with jax.default_matmul_precision("highest"):
        want_logits, want, calls, got = _whole_and_parts(
            params, cfg, prompt, part, dirty)
    assert np.abs(calls[-1] - np.asarray(want_logits)).max() < 2e-4
    assert all(not c.any() for c in calls[:-1])
    for name in ("ssm", "conv"):
        assert np.abs(np.asarray(got[name], np.float32)
                      - np.asarray(want[name], np.float32)).max() < 2e-4, name
    assert int(got["pos"][1]) == int(want["pos"][1]) == n
    for name in ("k", "v"):
        assert np.abs(np.asarray(got[name][0, 1, :, :, :n]
                                 - want[name][0, 1, :, :, :n])).max() < 2e-4
    holds = np.asarray(gen._ring_holds(jnp.asarray([n]), 16))[0] >= 0
    for name in ("k_ring", "v_ring"):
        assert np.abs(np.asarray(got[name][:, 1] - want[name][:, 1])
                      )[..., holds].max() < 2e-4


def test_the_early_exit_is_the_full_stack_at_the_last_position(model):
    """The prefill runs layers above the slab for the last position alone:
    its logits are the reference's, which runs every layer at every
    position."""
    cfg, params = model
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (29, 8)]
    toks = np.zeros((2, 32), np.int32)
    for r, p in enumerate(prompts):
        toks[r, :len(p)] = p
    with jax.default_matmul_precision("highest"):
        logits, _, _ = prefill_at(
            params, cfg, jnp.asarray(toks), jnp.asarray([29, 8]),
            gen.init_cache(cfg, 3, 128), jnp.asarray([0, 1]))
    for r, p in enumerate(prompts):
        want = ref_logits(params, cfg, p)[-1]
        assert np.abs(np.asarray(logits[r]) - want).max() < 2e-4


# -- the Mamba-1 ops ----------------------------------------------------------

def _scan_inputs(B, T, C, N, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (B, T, C)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (B, T, C)) - 3),
        a=-jnp.exp(0.5 * jax.random.normal(k[2], (N, C))),
        b=jax.random.normal(k[3], (B, T, N)), c=jax.random.normal(k[4], (B, T, N)),
        d=jax.random.normal(k[5], (C,)),
        state=jax.random.normal(k[6], (B, N, *ssm.channel_tiles(C))))


def _recurrence(x, dt, a, b, c, d, state, lengths):
    """The recurrence by hand, a row and a position at a time (numpy)."""
    x, dt, a, b, c, d = (np.asarray(t, np.float64) for t in (x, dt, a, b, c, d))
    B, T, C = x.shape
    h = np.asarray(state, np.float64).reshape(B, a.shape[0], C).copy()
    y = np.zeros((B, T, C))
    for r in range(B):
        for t in range(T):
            if t < lengths[r]:
                h[r] = np.exp(dt[r, t][None] * a) * h[r] + np.outer(
                    b[r, t], dt[r, t] * x[r, t])
            y[r, t] = c[r, t] @ h[r] + d * x[r, t]
    return y, h


@pytest.mark.parametrize("kernel", [False, True], ids=["steps", "kernel"])
def test_selective_scan_is_the_recurrence(kernel):
    """A carried state, right-padded rows (``dt = 0`` changes nothing), two
    parts = the whole; the Pallas kernel (TPU interpreter) = the scan."""
    B, T, C, N = 2, 128, 256, 8
    ins = _scan_inputs(B, T, C, N)
    lengths = np.array([128, 70])
    want_y, want_h = _recurrence(**ins, lengths=lengths)
    if kernel:
        dt = jnp.where((jnp.arange(T)[None] < lengths[:, None])[..., None],
                       ins["dt"], 0.0)
        y, h = ssm.selective_scan_kernel(
            dt * ins["x"], dt, ins["a"], ins["b"], ins["c"], ins["state"],
            interpret=True)
        y = y + ins["d"] * ins["x"]
    else:
        y, h = ssm.selective_scan(
            ins["x"], ins["dt"], ins["a"], ins["b"], ins["c"], ins["d"],
            ins["state"], jnp.asarray(lengths))
    real = np.arange(T)[None, :, None] < lengths[:, None, None]
    assert np.abs(np.where(real, np.asarray(y) - want_y, 0)).max() < 1e-4
    assert np.abs(np.asarray(h).reshape(B, N, C) - want_h).max() < 1e-4
    if not kernel:
        cut = lambda t, s: t[:, s] if t.ndim == 3 and t.shape[1] == T else t  # noqa: E731
        first = {k: cut(v, slice(0, 64)) for k, v in ins.items()}
        y1, h1 = ssm.selective_scan(*(first[k] for k in "x dt a b c d state".split()))
        rest = {k: cut(v, slice(64, T)) for k, v in ins.items()}
        rest["state"] = h1
        y2, h2 = ssm.selective_scan(*(rest[k] for k in "x dt a b c d state".split()))
        yw, hw = ssm.selective_scan(*(ins[k] for k in "x dt a b c d state".split()))
        assert np.abs(np.asarray(jnp.concatenate([y1, y2], 1) - yw)).max() < 1e-5
        assert np.abs(np.asarray(h2 - hw)).max() < 1e-5


def test_selective_state_update_kernel_is_the_masked_form():
    """The kernel over the plan's rows (TPU interpreter) = the masked form;
    a row that is not listed moves no byte and reads ``y = 0``; a listed row
    that stopped is frozen bit for bit."""
    L, B, N, C = 3, 5, 8, 1024
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    state = jax.random.normal(k[0], (L, B, N, *ssm.channel_tiles(C)))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, C)) - 3)
    dtx = jax.random.normal(k[2], (B, C))
    a = -jnp.exp(0.5 * jax.random.normal(k[3], (N, C)))
    b, c = jax.random.normal(k[4], (B, N)), jax.random.normal(k[5], (B, N))
    began = jnp.asarray([True, False, True, True, False])
    now = jnp.asarray([True, False, False, True, False])  # row 2 stopped
    want, want_y = ssm.selective_state_update(state, 1, dt, dtx, a, b, c, now)
    frozen = lambda t: jnp.where(now[:, None], t, 0.0)  # noqa: E731
    got, y = ssm.selective_state_update_kernel(
        state, 1, frozen(dt), frozen(dtx), a, b, c,
        ssm.state_update_plan(began), interpret=True)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert np.abs(np.asarray(y - want_y))[np.asarray(now)].max() < 1e-4
    for row in (1, 2, 4):
        assert bool((got[1, row] == state[1, row]).all())
    assert not np.asarray(y[1]).any() and not np.asarray(y[4]).any()
    assert bool((got[0] == state[0]).all()) and bool((got[2] == state[2]).all())
    # by hand: H = exp(dt A) H + dtx (outer) b
    h0 = np.asarray(state[1, 0]).reshape(N, C)
    h1 = np.exp(np.asarray(dt[0])[None] * np.asarray(a)) * h0 + np.outer(
        np.asarray(b[0]), np.asarray(dtx[0]))
    assert np.abs(np.asarray(got[1, 0]).reshape(N, C) - h1).max() < 1e-4


# -- the differential read ----------------------------------------------------

def _four_softmaxes(q, k, v, lam, gamma, lam_init, window, eps=1e-5):
    """``q [T, H, dh]``, ``k, v [T, KV, dh]`` -> ``[T, H dh]`` by the layer
    equations, the four products of a pair written out (numpy float64)."""
    T, H, dh = q.shape
    out = np.zeros((T, H // 2, 2 * dh))
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    mask = (j <= i) & ((j > i - window) if window else True)

    def soft(s):
        s = np.where(mask, s / np.sqrt(dh), -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    for p in range(H // 2):
        kv = p // 2
        w1 = soft(q[:, 2 * p] @ k[:, 2 * kv].T)
        w2 = soft(q[:, 2 * p + 1] @ k[:, 2 * kv + 1].T)
        a1 = np.concatenate([w1 @ v[:, 2 * kv], w1 @ v[:, 2 * kv + 1]], -1)
        a2 = np.concatenate([w2 @ v[:, 2 * kv], w2 @ v[:, 2 * kv + 1]], -1)
        o = a1 - lam * a2
        out[:, p] = (o / np.sqrt((o * o).mean(-1, keepdims=True) + eps)
                     * gamma * (1 - lam_init))
    return out.reshape(T, -1)


@pytest.mark.parametrize("back", [511, 512, 513])
def test_padded_queries_through_grouped_attention_are_the_four_softmaxes(back):
    """The pair map (query pair p -> K/V pair p // 2) and the window's edge:
    a key ``back`` positions behind the query is attended at 511, not at 512
    or 513 (window 512 counts the query's own position)."""
    from ray_tpu.models.transformer import _attend

    T, H, KV, dh, window = 640, 8, 4, 8, 512
    rng = np.random.default_rng(back)
    q, k, v = (rng.normal(size=(T, n, dh)) for n in (H, KV, KV))
    # ONE key far behind that every query would love: it moves the last
    # query's output iff it is inside that query's window
    k[T - 1 - back] *= 6.0
    cfg = pf.Phi4FlashConfig.tiny(dtype=jnp.float32, d_model=H * dh, n_heads=H,
                                  n_kv_heads=KV, sliding_window=window)
    p = {"lq1": rng.normal(size=dh) * 0.1, "lk1": rng.normal(size=dh) * 0.1,
         "lq2": rng.normal(size=dh) * 0.1, "lk2": rng.normal(size=dh) * 0.1,
         "subln": 1 + 0.1 * rng.normal(size=2 * dh)}
    p = {n: jnp.asarray(t, jnp.float32) for n, t in p.items()}
    layer = 3
    lam_init = 0.8 - 0.6 * np.exp(-0.3 * layer)
    lam = (np.exp(float(p["lq1"] @ p["lk1"])) - np.exp(float(p["lq2"] @ p["lk2"]))
           + lam_init)
    want = _four_softmaxes(q, k, v, lam, np.asarray(p["subln"], np.float64),
                           lam_init, window)
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    pair = lambda t: f32(t).reshape(1, T, KV // 2, 2 * dh).transpose(0, 2, 1, 3)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        a = _attend(pf.pad_queries(f32(q)[None]).transpose(0, 2, 1, 3), pair(k),
                    pair(v), causal=True, mesh=None, window=window,
                    scale=dh ** -0.5)[0]
        got = np.asarray(pf.diff_combine(a, p, cfg, layer))[0]
    assert np.abs(got - want).max() < 2e-4
    # the edge itself: with the loud key muted the last row changes iff the
    # key was inside the window
    k[T - 1 - back] /= 6.0
    muted = _four_softmaxes(q, k, v, lam, np.asarray(p["subln"], np.float64),
                            lam_init, window)
    moved = np.abs(muted[-1] - want[-1]).max() > 1e-3
    assert moved == (back < window)


@pytest.mark.parametrize("with_keep", [False, True], ids=["plain", "ring"])
def test_ragged_decode_kernel_takes_a_mask_and_a_name(with_keep):
    """The ragged decode kernel (TPU interpreter) with ``keep``: the ring
    read's window over the entries a ring holds = the masked einsums; a slot
    that attends nothing gives ``d = 0``."""
    B, KV, G, dh, S = 3, 2, 4, 16, 256
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (B, KV, G, dh))
    ks, vs = (jax.random.normal(t, (2, B, KV, dh, S)) for t in k[1:])
    live = jnp.asarray([200, 0, 77])
    plan = attention.ragged_decode_plan(live, S // 128)
    below = jnp.arange(S)[None] < live[:, None]
    keep = None
    if with_keep:
        keep = gen._ring_mask(live, jnp.asarray([203, 0, 77]), 96, S)
        keep = keep.at[2].set(False)  # a slot whose window holds nothing
    acc, m, d = attention.ragged_decode_attention(
        q, ks, vs, jnp.int32(1), plan, scale=0.3, keep=keep,
        name="ragged_ring_attention", interpret=True)
    want = gen._cache_scores_slab(
        q, ks, vs, 1, below if keep is None else below & keep, 0.3)
    norm = lambda acc, m, d: np.asarray(  # noqa: E731
        acc / jnp.maximum(d, 1e-30)[..., None])
    rows = [0] if with_keep else [0, 2]
    assert np.abs(norm(acc, m, d) - norm(*want))[rows].max() < 1e-4
    assert not np.asarray(d[1]).any()
    if with_keep:
        assert not np.asarray(d[2]).any()


# -- the engine ---------------------------------------------------------------

def test_engine_serves_mixed_lengths_parts_a_cut_and_a_reused_slot(monkeypatch):
    """Prompts under and over a part (parts of 16 with a carried state), a
    chunk cut where an answer ends, a slot reused after a longer request: the
    served tokens are the reference's greedy ones (logit gap under 2e-4), the
    upper-stack counter is ONE a prompt, and the prefill positions are the
    prompts' tokens."""
    from ray_tpu.serve import llm

    monkeypatch.setattr(llm, "PREFILL_PART_TOKENS", 16)
    with jax.default_matmul_precision("highest"):
        eng, cfg, params = engine(
            FAMILY, seed=3, n_slots=2, max_new_tokens=12, decode_chunk_steps=4,
            prefill_buckets=(16, 64))
        assert eng._part == 16
        rng = np.random.default_rng(5)
        lens = [50, 7, 33, 16, 21]
        news = [9, 12, 6, 5, 10]
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]
        futs = [eng.submit(p, m) for p, m in zip(prompts, news)]
        seen = run_engine(eng, futs)
    served = [f.result() for f in futs]
    assert [len(s) for s in served] == news
    assert any(t["steps"] not in (None, 4) for t in seen)  # a cut chunk ran
    for p, out in zip(prompts, served):
        at = ref_logits(params, cfg, p + out)[len(p) - 1:len(p) - 1 + len(out)]
        gap = float((at.max(-1) - at[np.arange(len(out)), out]).max())
        assert gap < 2e-4, (len(p), gap)
    tiles = eng.perf_stats()["cache_tiles"]
    assert tiles["yoco_upper_positions"] == len(prompts)
    assert tiles["yoco_prefill_positions"] == sum(lens)
    assert tiles["yoco_steps"] > 0 and tiles["yoco_row_steps"] >= sum(news) - 5
    assert tiles["yoco_slab_tile_steps"] == 2 * tiles["yoco_row_steps"]  # 1 tile x 2 readers
    parts = eng.perf_stats()["prefill"]["parts"]
    assert parts["prompts"] == 3 and parts["calls"] == 4 + 3 + 2
