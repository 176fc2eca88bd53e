"""KV-cache autoregressive generation for the decoder LMs (GPT-2, Llama).

The reference snapshot has no inference engine at all — serving wraps a
plain forward (``python/ray/serve/_private/replica.py:250`` calls the user
callable); generation/KV-cache is delegated to user code.  Here decode is a
first-class TPU path, designed for XLA:

- **Static shapes everywhere**: the cache is a fixed ``[L, B, KV, S, dh]``
  buffer; positions are dynamic *values*, never dynamic shapes, so the
  decode step compiles once and runs for every token.
- **In-place cache**: the decode layer loop is a ``fori_loop`` carrying
  the full cache; each layer writes only its new K/V column with one
  scatter, and XLA's while-loop buffer aliasing keeps the cache in place
  (a scan that re-emits the cache per step measured ~1.3 ms/step of pure
  rewrite traffic at GPT-2 125M on v5e).
- **Per-slot positions**: each batch slot sits at its own offset (``pos``
  vector), which is what iteration-level continuous batching needs
  (Orca-style; see :mod:`ray_tpu.serve.llm`).
- **Chunked decode**: ``decode_chunk`` runs N decode+sample steps inside
  one device computation (``lax.scan``) so the host syncs once per chunk,
  not per token.

Cache columns of finished/idle slots keep being written at their frozen
position, which is harmless: a slot's attention mask never reaches an
index its own ``pos`` hasn't covered, and prefill overwrites ``[0, len)``
when a slot is reused.
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
    """Fixed-size KV cache: k/v ``[L, B, KV, S, dh]`` plus per-slot ``pos``."""
    shape = (cfg.n_layers, n_slots, kv_heads(cfg), max_len, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((n_slots,), jnp.int32),
    }


def _decode_attend(q, k_cache, v_cache, pos) -> jax.Array:
    """q ``[B, H, 1, dh]`` against the full cache ``[B, KV, S, dh]`` with a
    per-slot length mask ``j <= pos``.  GQA folds the query heads onto
    their KV head by reshape (no materialized repeat)."""
    B, H, _, dh = q.shape
    KV = k_cache.shape[1]
    S = k_cache.shape[2]
    q = q.reshape(B, KV, H // KV, dh)
    # keep the cache reads in bf16 (f32 accumulation via
    # preferred_element_type) — upcasting the whole cache each step would
    # double the dominant HBM traffic of decode
    scores = jnp.einsum(
        "bkgd,bksd->bkgs", q, k_cache.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) / (dh ** 0.5)
    mask = jnp.arange(S)[None, None, None, :] <= pos[:, None, None, None]
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgs,bksd->bkgd", w.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
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
    cache_k = cache["k"].at[:, slots, :, :Tp, :].set(ks.astype(cache["k"].dtype))
    cache_v = cache["v"].at[:, slots, :, :Tp, :].set(vs.astype(cache["v"].dtype))
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


def decode_step(params, cfg, cache: Dict[str, jax.Array], tokens: jax.Array,
                active: jax.Array) -> Tuple[jax.Array, Dict]:
    """One token for every slot.  ``tokens [B]`` are each slot's last
    emitted token, written at ``pos`` then attended; ``active [B]`` bool
    gates the position advance.  Returns ``(logits [B, V], cache)``.

    The layer loop is a ``fori_loop`` carrying the FULL cache and writing
    each layer's new K/V column with one scatter — XLA's while-loop buffer
    aliasing keeps the cache in place.  (The earlier scan-with-outputs
    version rebuilt the whole cache every step: measured ~1.3 ms/step of
    pure rewrite traffic on v5e at GPT-2 125M, on top of the ~1.2 ms
    weight-streaming floor.)"""
    fam = family_of(cfg)
    pos = cache["pos"]
    B = tokens.shape[0]
    H, dh = cfg.n_heads, cfg.head_dim
    KV = kv_heads(cfg)
    x = _embed(params, tokens[:, None], cfg, pos[:, None])  # [B, 1, D]
    blocks = params["blocks"]
    iota_b = jnp.arange(B)[:, None]
    iota_kv = jnp.arange(KV)[None, :]
    positions = pos[:, None]  # [B, 1] per-slot offsets (rope)

    def layer(l, carry):
        x, k_all, v_all = carry  # x [B, 1, D]
        p = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
            blocks)
        if fam == "gpt2":
            q, k, v = _gpt2_qkv(x, p, cfg)  # [B, heads, 1, dh]
        else:
            q, k, v = _llama_qkv(x, p, cfg, positions)
        # ONE scatter per tensor writes only the new column (l, b, :, pos_b)
        k_all = k_all.at[l, iota_b, iota_kv, positions, :].set(
            k[:, :, 0, :].astype(k_all.dtype))
        v_all = v_all.at[l, iota_b, iota_kv, positions, :].set(
            v[:, :, 0, :].astype(v_all.dtype))
        k_c = lax.dynamic_index_in_dim(k_all, l, 0, keepdims=False)
        v_c = lax.dynamic_index_in_dim(v_all, l, 0, keepdims=False)
        out = _decode_attend(q, k_c, v_c, pos)  # [B, H, 1, dh]
        out = out.transpose(0, 2, 1, 3).reshape(B, 1, H * dh).astype(cfg.dtype)
        if fam == "gpt2":
            x = _gpt2_post_attn(x, out, p, cfg)
        else:
            x = _llama_post_attn(x, out, p, cfg)
        return x, k_all, v_all

    x, k_all, v_all = lax.fori_loop(
        0, cfg.n_layers, layer, (x, cache["k"], cache["v"]))
    logits = _unembed(params, x, cfg)[:, 0, :]
    return logits, {
        "k": k_all, "v": v_all,
        "pos": pos + active.astype(jnp.int32),
    }


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
    Returns ``(emitted [B, steps], cache, active, key)``.  A slot that
    emits ``eos_id`` flips inactive mid-chunk (its pos freezes)."""

    def step(carry, _):
        cache, toks, act, k = carry
        k, sub = jax.random.split(k)
        logits, cache = decode_step(params, cfg, cache, toks, act)
        nxt = sample_logits(logits, sub, temperature=temperature, top_k=top_k)
        nxt = jnp.where(act, nxt, toks)
        if eos_id is not None:
            act = act & (nxt != eos_id)
        return (cache, nxt, act, k), nxt

    (cache, _, active, key), emitted = lax.scan(
        step, (cache, tokens, active, key), None, length=steps)
    return emitted.T, cache, active, key  # [B, steps]


def generate(params, cfg, prompts: jax.Array, lengths: jax.Array, *,
             max_new_tokens: int, key: Optional[jax.Array] = None,
             temperature: float = 0.0, top_k: int = 0,
             eos_id: Optional[int] = None) -> jax.Array:
    """One-shot batched generation (prefill + fused decode loop).  Returns
    ``[B, max_new_tokens]`` generated tokens (post-EOS positions repeat the
    EOS token).  For the serving path use :mod:`ray_tpu.serve.llm`, which
    runs the same kernels under iteration-level continuous batching."""
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
