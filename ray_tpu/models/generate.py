"""KV-cache autoregressive generation for the decoder LMs (GPT-2, Llama).

The reference snapshot has no inference engine at all — serving wraps a
plain forward (``python/ray/serve/_private/replica.py:250`` calls the user
callable); generation/KV-cache is delegated to user code.  Here decode is a
first-class TPU path, designed for XLA:

- **Static shapes everywhere**: the cache is a fixed ``[L, B, KV, dh, S]``
  buffer (positions last: the decode step's scores come out with S on the
  lanes, and the chip stores the cache unpadded); positions are dynamic
  *values*, never dynamic shapes, so the decode chunk compiles once and
  runs for every token.
- **Per-slot positions**: each batch slot sits at its own offset (``pos``
  vector), which is what iteration-level continuous batching needs
  (Orca-style; see :mod:`ray_tpu.serve.llm`).
- **Chunked decode**: ``decode_chunk`` runs N decode+sample steps inside
  one device computation (``lax.scan``) so the host syncs once per chunk,
  not per token.
- **No step writes the cache**: a chunk's new K/V columns live in a
  chunk-local buffer ``[L, steps, B, KV, dh]``.  Layer ``l`` of step ``i``
  writes there with one ``dynamic_update_slice`` at ``(l, i)`` — the same
  index for every slot, a contiguous block — and attends the cache below
  ``pos0`` (the slot's position when the chunk began) together with the
  buffer up to ``i``, under one softmax.  After the last step each slot's
  columns go into the cache at ``pos0[b]``, once, in place (XLA aliases
  the donated cache through the flush loop).  A scatter of every slot's
  column at its own position per layer per step cost 7.5 ms of the 19.7 ms
  GPT-2 XL decode step on the v5e (PERF.md, PR 28).

The flush invariant: after a chunk, every position ``j < pos[b]`` of slot
``b`` holds a column that prefill or an ACTIVE step wrote.  The flush
writes all ``steps`` columns at ``pos0[b] ..``, so those of a slot that was
idle, or that met EOS mid-chunk, land at or beyond its frozen ``pos`` —
harmless: a slot never attends an index its own ``pos`` hasn't covered, the
next flush starts at ``pos`` again, and prefill overwrites ``[0, Tp)`` and
resets ``pos`` when the slot is reused.  A slot that decodes needs
``pos0 + steps <= S`` (the engine sizes the cache ``bucket + max_new +
chunk``); where an IDLE slot's frozen ``pos`` is nearer the end than that,
the slice update clamps and lands on the slot's own dead columns.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.layers import layernorm, rmsnorm, rope


def family_of(cfg) -> str:
    if isinstance(cfg, LlamaConfig):
        return "llama"
    if isinstance(cfg, GPT2Config):
        return "gpt2"
    raise TypeError(f"no generation support for config {type(cfg).__name__}")


def kv_heads(cfg) -> int:
    return cfg.n_kv_heads if isinstance(cfg, LlamaConfig) else cfg.n_heads


def init_cache(cfg, n_slots: int, max_len: int) -> Dict[str, jax.Array]:
    """Fixed-size KV cache: k/v ``[L, B, KV, dh, S]`` (positions LAST, so the
    scores of a decode step come out with S on the lanes and the chip
    stores the cache unpadded) plus per-slot ``pos``."""
    shape = (cfg.n_layers, n_slots, kv_heads(cfg), cfg.head_dim, max_len)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((n_slots,), jnp.int32),
    }


def _decode_attend(q, k_cache, v_cache, k_new, v_new, pos0, i) -> jax.Array:
    """q ``[B, H, 1, dh]`` of chunk step ``i`` against the keys a slot has:
    the cache ``[B, KV, dh, S]`` at positions ``j < pos0`` (where the slot
    stood when the chunk began) and the chunk's own columns ``[steps, B,
    KV, dh]`` at ``t <= i``.  ONE softmax over both score sets (shared max
    and denominator).  GQA folds the query heads onto their KV head by
    reshape (no materialized repeat)."""
    B, H, _, dh = q.shape
    KV, S, steps = k_cache.shape[1], k_cache.shape[3], k_new.shape[0]
    q = q.reshape(B, KV, H // KV, dh)

    def scores(spec, k, mask):
        # keep the cache reads in bf16 (f32 accumulation via
        # preferred_element_type) — upcasting the whole cache each step
        # would double the dominant HBM traffic of decode
        s = jnp.einsum(spec, q, k.astype(q.dtype),
                       preferred_element_type=jnp.float32) / (dh ** 0.5)
        return jnp.where(mask, s, -1e30)

    s_old = scores("bkgd,bkds->bkgs", k_cache,
                   jnp.arange(S)[None, None, None, :] < pos0[:, None, None, None])
    s_new = scores("bkgd,tbkd->bkgt", k_new, jnp.arange(steps) <= i)
    # column t = 0 is never masked, so the max is a real score
    m = jnp.maximum(s_old.max(-1, keepdims=True), s_new.max(-1, keepdims=True))
    e_old, e_new = jnp.exp(s_old - m), jnp.exp(s_new - m)
    denom = e_old.sum(-1, keepdims=True) + e_new.sum(-1, keepdims=True)

    def weighted(spec, e, v):
        return jnp.einsum(spec, (e / denom).astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    out = (weighted("bkgs,bkds->bkgd", e_old, v_cache)
           + weighted("bkgt,tbkd->bkgd", e_new, v_new))
    return out.reshape(B, H, 1, dh)


# ---------------------------------------------------------------------------
# per-family block math — ONE implementation serves prefill and decode:
# _qkv projects (post-rope, [B, heads, T, dh]), _post_attn applies the
# output projection + FFN residuals; only the attention middle differs
# (full causal for prefill, cache-masked for decode)
# ---------------------------------------------------------------------------

def _gpt2_qkv(x, p, cfg: GPT2Config):
    """x [B, T, D] -> q, k, v [B, H, T, dh]."""
    B, T, _ = x.shape
    H, dh = cfg.n_heads, cfg.head_dim
    c = lambda w: w.astype(cfg.dtype)
    h = layernorm(x, c(p["ln1_w"]), c(p["ln1_b"]))
    qkv = h @ c(p["wqkv"]) + c(p["bqkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    to_heads = lambda t: t.reshape(B, T, H, dh).transpose(0, 2, 1, 3)
    return to_heads(q), to_heads(k), to_heads(v)


def _gpt2_post_attn(x, out, p, cfg: GPT2Config):
    """out [B, T, D] (attention result, head-merged) -> next x."""
    c = lambda w: w.astype(cfg.dtype)
    x = x + out @ c(p["wo"]) + c(p["bo"])
    h = layernorm(x, c(p["ln2_w"]), c(p["ln2_b"]))
    h = jax.nn.gelu(h @ c(p["w1"]) + c(p["b1"]), approximate=True)
    return x + h @ c(p["w2"]) + c(p["b2"])


def _llama_qkv(x, p, cfg: LlamaConfig, positions):
    """x [B, T, D] -> post-rope q [B, H, T, dh], k/v [B, KV, T, dh] (the
    GQA KV-head layout the cache stores)."""
    B, T, _ = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype
    h = rmsnorm(x, p["attn_norm"].astype(dt), eps=cfg.rms_eps)
    q = (h @ p["wq"].astype(dt)).reshape(B, T, H, dh).transpose(0, 2, 1, 3)
    k = (h @ p["wk"].astype(dt)).reshape(B, T, KV, dh).transpose(0, 2, 1, 3)
    v = (h @ p["wv"].astype(dt)).reshape(B, T, KV, dh).transpose(0, 2, 1, 3)
    return (rope(q, positions, base=cfg.rope_base),
            rope(k, positions, base=cfg.rope_base), v)


def _llama_post_attn(x, out, p, cfg: LlamaConfig):
    dt = cfg.dtype
    x = x + out @ p["wo"].astype(dt)
    h = rmsnorm(x, p["ffn_norm"].astype(dt), eps=cfg.rms_eps)
    gated = jax.nn.silu(h @ p["w_gate"].astype(dt)) * (h @ p["w_up"].astype(dt))
    return x + gated @ p["w_down"].astype(dt)


def _gpt2_block(x, p, cfg: GPT2Config):
    """One GPT-2 prefill block: full causal self-attention over
    ``x [B, T, D]``; returns ``(x, (k, v))`` for the cache."""
    B, T, D = x.shape
    q, k, v = _gpt2_qkv(x, p, cfg)
    from ray_tpu.ops.attention import attention

    out = attention(q, k, v, causal=True)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, D).astype(cfg.dtype)
    return _gpt2_post_attn(x, out, p, cfg), (k, v)


def _llama_block(x, p, cfg: LlamaConfig, positions):
    """One Llama prefill block (RMSNorm/RoPE/GQA/SwiGLU); the cache stores
    post-RoPE keys in the KV-head layout (the GQA memory saving)."""
    B, T, _ = x.shape
    H, dh = cfg.n_heads, cfg.head_dim
    q, k, v = _llama_qkv(x, p, cfg, positions)
    kr = jnp.repeat(k, cfg.q_per_kv, axis=1)
    vr = jnp.repeat(v, cfg.q_per_kv, axis=1)
    from ray_tpu.ops.attention import attention

    out = attention(q, kr, vr, causal=True)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, H * dh).astype(cfg.dtype)
    return _llama_post_attn(x, out, p, cfg), (k, v)


# ---------------------------------------------------------------------------
# prefill / decode over the stacked layers
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg, positions):
    if family_of(cfg) == "gpt2":
        x = params["wte"][tokens] + jnp.take(params["wpe"], positions, axis=0)
    else:
        x = params["tok_emb"][tokens]
    return x.astype(cfg.dtype)


def _unembed(params, x, cfg):
    if family_of(cfg) == "gpt2":
        x = layernorm(x, params["lnf_w"].astype(cfg.dtype),
                      params["lnf_b"].astype(cfg.dtype))
        w = params["wte"]
    else:
        x = rmsnorm(x, params["final_norm"].astype(cfg.dtype), eps=cfg.rms_eps)
        w = params["tok_emb"]
    return (x @ w.T.astype(cfg.dtype)).astype(jnp.float32)


def prefill_at(params, cfg, tokens: jax.Array, lengths: jax.Array,
               cache: Dict[str, jax.Array], slots: jax.Array) -> Tuple[jax.Array, Dict]:
    """Run the prompts ``tokens [B, Tp]`` (right-padded; true lengths
    ``lengths [B]``) and write K/V into cache slots ``slots [B]`` (any
    subset — one compiled program admits a whole batch of requests).  Returns
    ``(last_logits [B, V], cache)``.  Positions are 0..Tp-1, so a slot must
    be prefilled from scratch (pos resets to ``lengths``)."""
    fam = family_of(cfg)
    B, Tp = tokens.shape
    positions = jnp.arange(Tp)
    x = _embed(params, tokens, cfg, positions)

    if fam == "gpt2":
        def body(h, p):
            h, kv = _gpt2_block(h, p, cfg)
            return h, kv
    else:
        def body(h, p):
            h, kv = _llama_block(h, p, cfg, positions)
            return h, kv

    x, (ks, vs) = lax.scan(body, x, params["blocks"])  # ks [L, B, KV, Tp, dh]
    # single advanced index keeps its axis position: one scatter per tensor
    # (over whole slots, once a prompt; decode never scatters)
    to_cache = lambda t, c: c.at[:, slots, :, :, :Tp].set(
        jnp.swapaxes(t, 3, 4).astype(c.dtype))
    cache_k, cache_v = to_cache(ks, cache["k"]), to_cache(vs, cache["v"])
    pos = cache["pos"].at[slots].set(lengths.astype(jnp.int32))
    last = _unembed(params, jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1), cfg)
    return last[:, 0, :], {"k": cache_k, "v": cache_v, "pos": pos}


def prefill(params, cfg, tokens: jax.Array, lengths: jax.Array,
            cache: Dict[str, jax.Array], slot: jax.Array) -> Tuple[jax.Array, Dict]:
    """:func:`prefill_at` with contiguous slots ``slot + [0..B)``."""
    B = tokens.shape[0]
    return prefill_at(params, cfg, tokens, lengths, cache,
                      slot + jnp.arange(B, dtype=jnp.int32))


def sample_logits(logits: jax.Array, key: jax.Array, *, temperature: float = 0.0,
                  top_k: int = 0) -> jax.Array:
    """Greedy (temperature 0) or temperature/top-k categorical sampling."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def decode_chunk(params, cfg, cache, tokens, active, key, *, steps: int,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_id: Optional[int] = None):
    """Run ``steps`` decode+sample iterations in one device computation.
    ``tokens [B]`` are each slot's last emitted token, ``active [B]`` bool
    gates the position advance.  Returns ``(emitted [B, steps], cache,
    active, key)``.  A slot that emits ``eos_id`` flips inactive mid-chunk
    (its pos freezes).

    No step writes into the cache (module docstring): the new K/V columns
    go to a chunk-local buffer ``[L, steps, B, KV, dh]``, attention is over
    the cache below ``pos0`` plus that buffer (:func:`_decode_attend`), and
    after the last step each slot's columns are flushed to ``pos0[b]`` with
    one ``dynamic_update_slice`` per tensor, in place.  (On the v5e the
    GPT-2 XL step, 17 rows over 896 positions, fell from 19.74 to 13.16 ms:
    ``serve-gpt2-xl-chat`` ``model.decode_step_ms``, ledger, PRs 25 and
    28.)"""
    fam = family_of(cfg)
    B = tokens.shape[0]
    H, dh, KV = cfg.n_heads, cfg.head_dim, kv_heads(cfg)
    S = cache["k"].shape[-1]
    # a dynamic_update_slice clamps silently: the flush of a slot at pos0
    # needs pos0 + steps <= S (the engine's bucket + max_new + chunk)
    assert steps <= S, (steps, S)
    if steps == 0:
        return jnp.zeros((B, 0), jnp.int32), cache, active, key
    blocks = params["blocks"]
    k_old, v_old, pos0 = cache["k"], cache["v"], cache["pos"]
    local = jnp.zeros((cfg.n_layers, steps, B, KV, dh), k_old.dtype)

    def step(carry, i):
        k_loc, v_loc, pos, toks, act, rng = carry
        rng, sub = jax.random.split(rng)
        positions = pos[:, None]  # [B, 1] per-slot offsets (wpe / rope)
        x = _embed(params, toks[:, None], cfg, positions)  # [B, 1, D]

        def layer(l, carry):
            x, k_loc, v_loc = carry
            at_l = lambda a: lax.dynamic_index_in_dim(a, l, 0, keepdims=False)
            p = jax.tree.map(at_l, blocks)
            if fam == "gpt2":
                q, k, v = _gpt2_qkv(x, p, cfg)  # [B, heads, 1, dh]
            else:
                q, k, v = _llama_qkv(x, p, cfg, positions)
            put = lambda buf, t: lax.dynamic_update_slice(
                buf, t[None, None, :, :, 0, :].astype(buf.dtype),
                (l, i, 0, 0, 0))
            k_loc, v_loc = put(k_loc, k), put(v_loc, v)
            out = _decode_attend(q, at_l(k_old), at_l(v_old),
                                 at_l(k_loc), at_l(v_loc), pos0, i)
            out = out.transpose(0, 2, 1, 3).reshape(B, 1, H * dh).astype(cfg.dtype)
            post = _gpt2_post_attn if fam == "gpt2" else _llama_post_attn
            return post(x, out, p, cfg), k_loc, v_loc

        x, k_loc, v_loc = lax.fori_loop(
            0, cfg.n_layers, layer, (x, k_loc, v_loc))
        logits = _unembed(params, x, cfg)[:, 0, :]
        nxt = sample_logits(logits, sub, temperature=temperature, top_k=top_k)
        nxt = jnp.where(act, nxt, toks)
        pos = pos + act.astype(jnp.int32)
        if eos_id is not None:
            act = act & (nxt != eos_id)
        return (k_loc, v_loc, pos, nxt, act, rng), nxt

    (k_loc, v_loc, pos, _, active, key), emitted = lax.scan(
        step, (local, local, pos0, tokens, active, key), jnp.arange(steps))

    def flush(b, kv):
        # slot b's columns of the chunk, as [L, 1, KV, dh, steps], to pos0[b]
        col = lambda loc: jnp.transpose(
            lax.dynamic_index_in_dim(loc, b, 2, keepdims=False),
            (0, 2, 3, 1))[:, None]
        return tuple(
            lax.dynamic_update_slice(big, col(loc), (0, b, 0, 0, pos0[b]))
            for big, loc in zip(kv, (k_loc, v_loc)))

    k_all, v_all = lax.fori_loop(0, B, flush, (k_old, v_old))
    return emitted.T, {"k": k_all, "v": v_all, "pos": pos}, active, key


def generate(params, cfg, prompts: jax.Array, lengths: jax.Array, *,
             max_new_tokens: int, key: Optional[jax.Array] = None,
             temperature: float = 0.0, top_k: int = 0,
             eos_id: Optional[int] = None) -> jax.Array:
    """One-shot batched generation (prefill + ONE decode chunk of
    ``max_new_tokens - 1`` steps, so the chunk-local K/V buffer is as wide
    as the answer).  Returns ``[B, max_new_tokens]`` generated tokens
    (post-EOS positions repeat the EOS token).  For the serving path use
    :mod:`ray_tpu.serve.llm`, which runs the same kernels in fixed chunks
    under iteration-level continuous batching."""
    B, Tp = prompts.shape
    if key is None:
        key = jax.random.PRNGKey(0)
    cache = init_cache(cfg, B, Tp + max_new_tokens)
    last_logits, cache = prefill(
        params, cfg, prompts, lengths, cache, jnp.int32(0))
    key, sub = jax.random.split(key)
    first = sample_logits(last_logits, sub, temperature=temperature, top_k=top_k)
    active = jnp.ones((B,), bool)
    if eos_id is not None:
        active = active & (first != eos_id)
    rest, _, _, _ = decode_chunk(
        params, cfg, cache, first, active, key,
        steps=max_new_tokens - 1, temperature=temperature, top_k=top_k,
        eos_id=eos_id)
    return jnp.concatenate([first[:, None], rest], axis=1)
