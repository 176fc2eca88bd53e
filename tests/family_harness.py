"""One harness for the served-model tests: the tiny model of every family in
``generate.FAMILIES``, the jitted programs a case drives, and the host-side
walks (``Slots``, ``serve``, ``one_shot``, ``run_engine``) the family files,
``test_generate``, ``test_decode_cut``, ``test_decode_attention``,
``test_prefill_parts`` and ``test_llm_serve`` share.  The fixtures that go
with it (``lowered_for_tpu``, ``positions``, ``kept_engine_programs``) live in
``conftest.py``.

Why it exists: a test's time is building programs, not running them.  Called
un-jitted, ``gen.prefill_at`` dispatches an unrolled family's layers an
operation at a time and every ``gen.decode_chunk`` traces and compiles its
scan anew; so every program here is jitted ONCE a process and kept, keyed by
what it was traced under (see ``programs``), and a case pays for a program
only where no earlier case of its worker built it.

A new family's tests are a row in ``TINY`` plus what is NEW about its cache.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import generate as gen

# The tiny float32 model of each family (f32: an argmax never flips on
# accumulation-order noise): its preset and these keywords, nothing else,
# unless a test says what a different size shows.  256 positions for the two
# dense families (their tiny preset's position table holds 128; the chunk
# tests stand slots on both sides of position 128); the MoE presets hold 512.
# ``experts_held``: 16 experts of which this chip holds 4..11.
TINY = {
    "gpt2": {"max_seq_len": 256},
    "llama": {"max_seq_len": 256},
    "exaone_moe": {"experts_held": (4, 8)},       # window 8: rings of 16
    "kimi_k2": {"experts_held": (4, 8)},
    "granite_hybrid": {"experts_held": (4, 8)},
    "dots3_note": {"experts_held": (4, 8)},       # window 5, top-8 positions
    "evabyte": {},                                # window 32, chunks of 4
    "phi4_flash": {},                             # window 8, one shared slab
    # top-8 positions over k, v slabs, 8 experts top 2 (all held), a tower of
    # 2 blocks over frames of 4 x 4 patches
    "keye_vl": {},
}
# exaone_moe where a chunk is longer than 9 steps (the one-shot path decodes a
# whole answer in ONE chunk): a ring's flush needs ``steps <= window + 1``
# (``gen.ring_positions``), so the window is 16 and the ring 32
LONG_CHUNK = {"exaone_moe": {"sliding_window": 16}}

# the chunk tests' one set of shapes: five slots (the last the scratch slot),
# prompts padded to 8 or 128, a short cache and one that crosses position 128
SLOTS, SHORT, LONG = 5, 8 + 3 * 4, 128 + 1 + 32


@functools.lru_cache(maxsize=None)
def _tiny(family, seed, block_scale, changed):
    mod = gen.FAMILIES[family]
    cfg = mod.Config.tiny(**{"dtype": jnp.float32, **TINY.get(family, {}),
                             **dict(changed)})
    params = mod.init(cfg, jax.random.PRNGKey(seed))
    if block_scale != 1 and "blocks" in params:
        # (a family that lists its layers makes them large enough itself)
        params["blocks"] = jax.tree.map(
            lambda w: w * block_scale if w.ndim >= 3 else w, params["blocks"])
    return cfg, params


def tiny_model(family, seed=0, block_scale=1, **changed):
    """``(cfg, params)`` of the family's tiny model, built once a process.
    As initialised a dense family repeats its last token whatever it attends
    (tied embeddings, small blocks); ``block_scale=8`` makes the layers'
    matrices large enough that the answer depends on the context, so that a
    wrong or stale cached column changes a token."""
    return _tiny(family, seed, block_scale, tuple(sorted(changed.items())))


# -- the programs -------------------------------------------------------------

# Which lowering the programs are traced for: "cpu", or "lowered_for_tpu" while
# that fixture is active (``lax.platform_dependent`` takes its ``tpu`` branch
# and Pallas runs in the TPU interpreter).  The choice is made at TRACE time
# and is no argument of any program, so a jitted program traced under one
# path must never serve the other: ``programs`` keeps one set of jitted
# functions a path, each a closure of its own (two ``jax.jit`` of one function
# share its traces), and a program is only ever called, and so traced, inside
# the path it is kept under.
PATH = "cpu"


@functools.lru_cache(maxsize=None)
def programs(path):
    """The jitted model programs of ``path``: ``cfg`` (a hashable frozen
    dataclass) and the sizes are static, so there is one compiled program a
    (path, config, shapes), whatever case asks; ``n``, the bound of a cut
    chunk, is a runtime argument by design (one program serves every bound)."""
    def prefill_at(*args, **kw):
        return gen.prefill_at(*args, **kw)

    def decode_chunk(*args, **kw):
        return gen.decode_chunk(*args, **kw)

    def generate(*args, **kw):
        return gen.generate(*args, **kw)

    return types.SimpleNamespace(
        prefill_at=jax.jit(prefill_at, static_argnums=1,
                           static_argnames=("bound",)),
        decode_chunk=jax.jit(decode_chunk, static_argnums=1, static_argnames=(
            "steps", "eos_id", "temperature", "top_k")),
        generate=jax.jit(generate, static_argnums=1, static_argnames=(
            "max_new_tokens", "eos_id", "temperature", "top_k")))


_SERVED = {}


def served(params, cfg):
    """``params`` as the programs take them (``gen.serving_layout``: what an
    engine lays out once at load), made once a tree: the cases hold ``init``'s
    tree (``tiny_model``: what a reference reads and an engine is handed) and
    every walk into ``gen`` below goes through here.  A tree laid out already
    (an engine's) comes back as it is."""
    if id(params) not in _SERVED:  # (the tree is kept, so its id is not reused)
        _SERVED[id(params)] = params, gen.serving_layout(
            cfg, jax.tree.map(lambda a: a, params))
    return _SERVED[id(params)][1]


def served_layer(p):
    """One layer of ``init``'s tree (or a stack) as a family's ``block`` reads
    it (``moe.gate_up_side_by_side``, on a copy of the dict)."""
    from ray_tpu.ops import moe

    return moe.gate_up_side_by_side(dict(p))


def prefill_at(params, cfg, *args, **kw):
    """``gen.prefill_at`` through the kept program -> the last logits, the
    cache WITHOUT its routing counts, and those counts (None: none)."""
    logits, cache = programs(PATH).prefill_at(
        served(params, cfg), cfg, *args, **kw)
    return logits, cache, cache.pop("routed", None)


def decode_chunk(params, cfg, cache, tokens, active, key=None, *, n=None, **kw):
    """``gen.decode_chunk`` through the kept program (``n``: cut after ``n``
    steps) -> emitted, the cache without its routing counts, active, the key,
    and those counts."""
    key = jax.random.PRNGKey(0) if key is None else key
    emitted, cache, active, key = programs(PATH).decode_chunk(
        served(params, cfg), cfg, cache, tokens, active, key,
        n=None if n is None else jnp.int32(n), **kw)
    return emitted, cache, active, key, cache.pop("routed", None)


def one_shot(params, cfg, prompts, n, pad_to=8, eos_id=None):
    """The one-shot path's answers to ``prompts``, ``n`` tokens each (a list:
    its own count a prompt): ONE ``gen.generate`` over all of them, right-
    padded to a multiple of ``pad_to`` (padding and the rows beside a prompt
    change nothing: ``test_batched_slots_with_different_lengths``)."""
    counts = [n] * len(prompts) if isinstance(n, int) else list(n)
    width = -(-max(map(len, prompts)) // pad_to) * pad_to
    batch = np.zeros((len(prompts), width), np.int32)
    for row, prompt in enumerate(prompts):
        batch[row, :len(prompt)] = prompt
    out = np.asarray(programs(PATH).generate(
        served(params, cfg), cfg, jnp.asarray(batch),
        jnp.asarray([len(p) for p in prompts]), max_new_tokens=max(counts),
        eos_id=eos_id))
    return [[int(t) for t in row[:m]] for row, m in zip(out, counts)]


def full_forward(family, params, cfg, seqs, pad_to=32):
    """The family's full forward (``apply``: the plain reference of the cache
    tests, never traced for the chip whatever the path) over ``seqs`` in ONE
    jitted call, right-padded to a multiple of ``pad_to`` (every layer is
    causal: what follows a position does not reach it) -> logits
    ``[len(seqs), width, vocab]``."""
    width = -(-max(map(len, seqs)) // pad_to) * pad_to
    batch = np.zeros((len(seqs), width), np.int32)
    for row, seq in enumerate(seqs):
        batch[row, :len(seq)] = seq
    return np.asarray(_apply(gen.FAMILIES[family].apply)(
        params, jnp.asarray(batch), cfg))


@functools.lru_cache(maxsize=None)
def _apply(apply_fn):
    return jax.jit(apply_fn, static_argnums=2)


def greedy_reference(family, params, cfg, prompt, n_new):
    """Teacher-forcing loop: full forward each step, argmax last logit."""
    toks = list(prompt)
    for _ in range(n_new):
        logits = full_forward(family, params, cfg, [toks])
        toks.append(int(np.argmax(logits[0, len(toks) - 1])))
    return toks[len(prompt):]


# -- the engine's use of the programs, on the host ----------------------------

class Slots:
    """A cache of ``n`` slots (the last one the scratch slot), prompts
    admitted into any of them, all decoded together ``steps`` tokens a
    chunk: what the engine does with the programs, a call at a time."""

    def __init__(self, family, max_len, n=SLOTS, *, eos_id=None, **changed):
        self.cfg, self.params = tiny_model(
            family, seed=4, block_scale=8, **changed)
        self.family, self.n, self.eos_id = family, n, eos_id
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
        last, self.cache, _ = prefill_at(
            self.params, self.cfg, jnp.asarray(toks),
            jnp.asarray([len(prompt), bucket]), self.cache,
            jnp.asarray([slot, self.n - 1]))
        first = int(jnp.argmax(last[0]))
        self.tok = self.tok.at[slot].set(first)
        self.active[slot] = first != self.eos_id
        self.out[slot], self._prompts[slot] = [first], prompt

    def decode(self, steps, n=None):
        """One chunk of ``steps``; ``n``: CUT to ``n`` steps, by the program
        whose bound is an argument, as the engine's is."""
        was = self.active.copy()
        emitted, self.cache, active, self.key, _ = decode_chunk(
            self.params, self.cfg, self.cache, self.tok,
            jnp.asarray(self.active), self.key, steps=steps, n=n,
            eos_id=self.eos_id)
        emitted = np.asarray(emitted)
        self.tok = jnp.asarray(emitted[:, -1])
        self.active = np.array(active)
        for slot in np.flatnonzero(was):
            row = [int(t) for t in emitted[slot, :n]]
            if self.eos_id in row:  # what follows an EOS repeats it
                row = row[:row.index(self.eos_id) + 1]
            self.out[slot] += row
        return emitted

    def assert_greedy(self, answers):
        """The answers of the slots in ``answers`` (slot -> its length) are
        the full forward's greedy ones, by teacher forcing: ONE forward over
        prompt + answer; by induction the answer is greedy iff every token is
        the argmax after the tokens before."""
        slots = list(answers)
        logits = full_forward(
            self.family, self.params, self.cfg,
            [self._prompts[s] + self.out[s][:-1] for s in slots])
        for row, slot in zip(logits, slots):
            prompt, out = self._prompts[slot], self.out[slot]
            assert len(out) == answers[slot]
            at = len(prompt) - 1
            assert out == [
                int(t) for t in row[at:at + len(out)].argmax(-1)], slot


def serve(cfg, params, prompts, chunks, *, steps, bucket, cache_len,
          slots=(2, 0), n_slots=3, rows=None, cache=None, spoil=None):
    """Prefill ``prompts`` in ONE call into ``slots`` of a fresh cache of
    ``n_slots`` (the others sit idle; ``cache``: this one instead), then
    decode chunks of ``steps``, each whole (None) or CUT to ``n``;
    ``spoil(cache)`` stands between any two calls -> the served tokens of
    each prompt, the cache, and each chunk's routing counts."""
    first, cache, tokens, active = prefill(
        cfg, params, prompts, slots,
        gen.init_cache(cfg, n_slots, cache_len) if cache is None else cache,
        bucket=bucket, rows=rows)
    served, counted = [[int(t)] for t in first], []
    for n in chunks:
        if spoil:
            cache = spoil(cache)
        emitted, cache, active, _, routed = decode_chunk(
            params, cfg, cache, tokens, active, steps=steps, n=n)
        counted.append(routed)
        tokens = emitted[:, -1]
        for out, slot in zip(served, slots):
            out += [int(t) for t in emitted[slot, :n]]
    return served, cache, counted


def prefill(cfg, params, prompts, slots, cache, *, bucket, rows=None):
    """``prompts`` right-padded to ``bucket`` into ``slots`` by ONE call of
    ``rows`` rows (those no prompt fills: a 1-token dummy aimed at the last
    slot, as the engine aims them at its scratch row) -> their first tokens,
    the cache, and the last tokens and the active flags of every slot."""
    rows, n_slots = rows or len(prompts), cache["pos"].shape[0]
    toks = np.zeros((rows, bucket), np.int32)
    lengths = np.ones((rows,), np.int32)
    into = np.full((rows,), n_slots - 1, np.int32)
    for r, (p, slot) in enumerate(zip(prompts, slots)):
        toks[r, :len(p)], lengths[r], into[r] = p, len(p), slot
    last, cache, _ = prefill_at(params, cfg, jnp.asarray(toks),
                                jnp.asarray(lengths), cache, jnp.asarray(into))
    first = jnp.argmax(last, -1).astype(jnp.int32)[:len(prompts)]
    at = jnp.asarray(list(slots))
    tokens = jnp.zeros((n_slots,), jnp.int32).at[at].set(first)
    active = jnp.zeros((n_slots,), bool).at[at].set(True)
    return first, cache, tokens, active


def padded(seq, pad_to=64):
    """``seq`` right-padded to a multiple of ``pad_to``: a reference pass of
    a causal model over it gives the logits of ``seq`` in its first
    ``len(seq)`` rows, and the reference compiles once a width."""
    return list(seq) + [0] * (-len(seq) % pad_to)


def worst_gap(ref_logits, prompts, served):
    """How far any served token's LOGIT lies under the reference's best at
    its position, in one full forward of the reference (``ref_logits(seq)``)
    over prompt + served tokens."""
    worst = 0.0
    for p, out in zip(prompts, served):
        logits = np.asarray(ref_logits(p + out))[
            len(p) - 1:len(p) - 1 + len(out)]
        worst = max(worst, float(
            (logits.max(-1) - logits[np.arange(len(out)), out]).max()))
    return worst


def sigmoid_top_k_by_hand(h, p, top_k, scale):
    """The sigmoid router written out: the ``top_k`` experts by score plus
    bias, gated by their scores' share of ``scale`` -> (experts, gates)."""
    s = jax.nn.sigmoid(h @ p["router"])
    _, sel = jax.lax.top_k(s + p["router_bias"], top_k)
    chosen = jnp.take_along_axis(s, sel, -1)
    return sel, scale * chosen / chosen.sum(-1, keepdims=True)


def shares_add_up(p, h, shares, routed, by_hand, ref_swiglu, n_experts):
    """An expert layer ``p`` cut over ``shares`` chips, two experts a chip:
    the parts the shares give on ``h`` (``routed``: the program's router's
    ``(experts, gates)`` of the flattened tokens), the shared expert counted
    once, against the uncut layer: the reference's sums over every expert,
    routed ``by_hand`` -> the largest difference, and the (token, expert)
    pairs the shares counted."""
    from ray_tpu.ops import moe

    flat = h.reshape(-1, h.shape[-1])
    experts, gates = routed
    # (jitted: op by op a share costs more than all of them compiled once;
    # the first held expert is an operand of comparisons only)
    share = jax.jit(moe.held_experts_ffn)
    parts, counted = 0.0, 0
    for chip in range(shares):
        held = {k: p[k][2 * chip:2 * chip + 2]
                for k in ("ew_gate", "ew_up", "ew_down")}
        y, tokens = share(
            flat, experts, gates, served_layer(held)["ew_gate_up"],
            held["ew_down"], first_expert=2 * chip)
        parts, counted = parts + y, counted + int(tokens.sum())
    f = lambda a: a  # noqa: E731
    shared = ref_swiglu(h, p["sw_gate"], p["sw_up"], p["sw_down"], f)
    sel, g_all = by_hand
    want = shared
    for e in range(n_experts):
        g = jnp.where(sel == e, g_all, 0.0).sum(-1)
        want = want + g[..., None] * ref_swiglu(
            h, p["ew_gate"][e], p["ew_up"][e], p["ew_down"][e], f)
    got = parts.reshape(h.shape) + shared
    return float(jnp.abs(got - want).max()), counted


# -- the serve engine, never started: the test is its thread ------------------

def engine(family, *, seed=0, changed=(), **kw):
    """A never-started ``GenerationEngine`` (``kw``: its own keywords) on the
    family's tiny model (``changed``: config keywords, as ``LONG_CHUNK``'s);
    its programs are the kept ones where ``kept_engine_programs`` is active
    -> the engine, its config, its parameters."""
    from ray_tpu.serve.llm import GenerationEngine

    cfg, params = tiny_model(family, seed=seed, **dict(changed))
    return GenerationEngine(cfg, params, **kw), cfg, params


@functools.lru_cache(maxsize=None)
def _shared_engine(family, kw):
    return engine(family, **dict(kw))


def shared_engine(family, **kw):
    """One never-started engine a (family, arguments) for the cases of a
    process that differ only in the prompts they submit, brought back to
    empty: nothing queued, no slot held, no chunk undrained.  Its counters
    run on from case to case: difference them."""
    eng, cfg, params = _shared_engine(family, tuple(sorted(kw.items())))
    for _ in range(8):
        if not eng.step():
            break
    stats = eng.stats()
    assert not eng.step() and stats["queued"] == stats["active_slots"] == 0
    return eng, cfg, params


def run_engine(eng, futs, limit=400):
    """Step the engine until ``futs`` are done -> a record per tick: the
    prompt lengths of each prefill call it admitted (``admitted``, None: it
    admitted nobody) and, where it dispatched a chunk, the steps that chunk
    ran (``steps``, else None), what the counters and the live requests'
    ``scheduled`` moved by, and which futures were done after it."""
    seen = []
    for _ in range(limit):
        if all(f.done() for f in futs):
            return seen
        before, was, queued = eng.perf_stats(), eng._pending, eng.stats()["queued"]
        live = {id(r): (r, r.scheduled) for r in eng._slots if r is not None}
        eng.step()
        after, now = eng.perf_stats(), eng._pending
        new = now is not None and now is not was
        seen.append({
            "admitted": [[len(req.tokens) for _, _, req in admissions]
                         for admissions, *_ in now.prefills]
            if queued - eng.stats()["queued"] else None,
            "steps": now.steps if new and now.chunk_dev is not None else None,
            "flushed": (after["cache_tiles"]["flushed"]
                        - before["cache_tiles"]["flushed"]),
            "padded": (after["cache_tiles"]["padded"]
                       - before["cache_tiles"]["padded"]),
            # of the rows that were live before the tick (one admitted in
            # it starts at its prefill's 1)
            "scheduled": sorted(r.scheduled - had for r, had in live.values()),
            "done": [f.done() for f in futs]})
    raise AssertionError("the engine did not finish")


@functools.lru_cache(maxsize=None)
def kept_programs():
    """The memo the ``kept_engine_programs`` fixture puts over
    ``llm.engine_programs`` (a function of the config and the bound alone):
    engines of one path, config and arguments get the jitted programs the
    first of them built, and the cut chunk, which an engine compiles ahead
    for its shapes, is compiled once a set of shapes."""
    from ray_tpu.serve import llm

    build, kept = llm.engine_programs, {}  # (called first outside the patch)

    def engine_programs(cfg, **kw):
        key = (PATH, cfg, tuple(sorted(kw.items())))
        if key not in kept:
            prefill, decode, cut, part = build(cfg, **kw)
            kept[key] = prefill, decode, _BuiltOnce(cut), part
        return kept[key]

    return engine_programs


class _BuiltOnce:
    """``jitted.lower(*shapes).compile()``, once a set of shapes."""

    def __init__(self, jitted):
        self._jitted, self._built = jitted, {}

    def lower(self, *args):
        key = str(jax.tree.map(lambda a: (a.shape, str(a.dtype)), args))
        if key not in self._built:
            self._built[key] = self._jitted.lower(*args).compile()
        return types.SimpleNamespace(compile=lambda: self._built[key])
