"""EvaByte (huggingface.co/EvaByte/EvaByte, ``model_type: evabyte``,
``attention_class: eva``): a tokenizer-free decoder over raw BYTES (vocabulary
320: the bytes plus specials) whose attention keeps an EXACT window and a
compressed memory of everything before it.

A layer, as this repo reads the published implementation (the points the
``config.json`` does not settle are listed as ``assumed`` in
``benchmark/configs/evabyte-6.5b-pp4.json``; ``benchmark/reference/
evabyte_ref.py`` is the same reading written plainly):

- the residual stream is FLOAT32 beside ``cfg.dtype`` matmuls
  (``fp32_skip_add``); RMSNorm multiplies by ``1 + g`` (``norm_add_unit_offset``);
- ``q, k, v`` are 32 heads of 128 with 32 KV heads (no sharing), no bias,
  rotary over all 128 values at absolute positions (rotate-half pairing);
- position ``i`` attends the positions of its own WINDOW at or below it
  (window ``i // 2048``: block-aligned, not sliding) and ONE pooled key and
  value a 16-position chunk of every earlier window, under one softmax
  (:mod:`ray_tpu.ops.eva`); the pooling's vector ``eva_phi`` and key offset
  ``eva_mu`` are learned, a head a layer;
- SwiGLU of 11,008; after the last layer a final norm and a float32 head of
  ``n_pred_heads x vocab`` outputs (``fp32_logits``): head ``p`` predicts byte
  ``i + 1 + p``.  The served path samples head 0 (plain next-byte generation;
  multi-byte self-speculative decoding over the eight heads is not built:
  ROADMAP R18); :func:`apply` gives every head's logits on request.

What it asks of :mod:`ray_tpu.models.generate`: a cache that is COMPACTED
while a request is live (``cfg.summary_cache``: WINDOW and SUMMARY rows of
``cache_layout`` there).  The
parameters are stacked (``params["blocks"]``, leaves ``[L, ...]``) and made in
``cfg.dtype``, a leaf at a time: the served model never exists in float32.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.exaone_moe import rope_half
from ray_tpu.ops import eva

__all__ = ["EvaByteConfig", "init", "apply", "block", "embed", "unembed",
           "kv_heads", "pooling", "num_params"]


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_model: int = 4096
    d_ff: int = 11_008
    window_size: int = 2048
    chunk_size: int = 16
    n_pred_heads: int = 8
    max_seq_len: int = 32_768
    rope_base: float = 100_000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def summary_cache(self) -> Tuple[int, int]:
        """``(window, chunk)``: what ``generate.init_cache`` sizes the exact
        window and the slab of summaries from."""
        assert self.window_size % self.chunk_size == 0
        return self.window_size, self.chunk_size

    @staticmethod
    def evabyte_6_5b(**kw) -> "EvaByteConfig":
        return EvaByteConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "EvaByteConfig":
        base = dict(n_layers=2, n_heads=4, n_kv_heads=4, d_model=64, d_ff=128,
                    window_size=32, chunk_size=4, max_seq_len=512)
        base.update(kw)
        return EvaByteConfig(**base)


Config = EvaByteConfig
SIZES = {"6.5b": EvaByteConfig.evabyte_6_5b, "tiny": EvaByteConfig.tiny}


def init(cfg: EvaByteConfig, key: jax.Array) -> Dict[str, Any]:
    """Every leaf in ``cfg.dtype``: fan-in normals, the two projections that
    write the residual stream scaled by ``(2 L) ** -0.5`` (the stream keeps
    unit size at any depth, so attention stays a real share of a logit), norm
    offsets near zero, the pooling vectors as published (``randn.clamp(-1, 1)
    * head_dim ** -0.5``)."""
    L, D, H, KV, hd, F = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                          cfg.n_kv_heads, cfg.head_dim, cfg.d_ff)
    keys = iter(jax.random.split(key, 16))

    def w(*shape, scale=1.0):
        return (jax.random.normal(next(keys), shape, cfg.dtype)
                * jnp.asarray(scale * shape[-2] ** -0.5, cfg.dtype))

    small = lambda *shape: (  # noqa: E731
        0.1 * jax.random.normal(next(keys), shape)).astype(cfg.dtype)
    clamped = lambda: (jnp.clip(  # noqa: E731
        jax.random.normal(next(keys), (L, KV, hd)), -1, 1) * hd ** -0.5
    ).astype(cfg.dtype)
    out = (2 * L) ** -0.5
    return {
        "tok_emb": jax.random.normal(next(keys), (cfg.vocab_size, D), cfg.dtype),
        "blocks": {
            "attn_norm": small(L, D), "ffn_norm": small(L, D),
            "wq": w(L, D, H * hd), "wk": w(L, D, KV * hd), "wv": w(L, D, KV * hd),
            "wo": w(L, H * hd, D, scale=out),
            "eva_phi": clamped(), "eva_mu": clamped(),
            "w_gate": w(L, D, F), "w_up": w(L, D, F),
            "w_down": w(L, F, D, scale=out),
        },
        "final_norm": small(D),
        # head p's outputs are columns [p V, (p + 1) V)
        "head": w(D, cfg.n_pred_heads * cfg.vocab_size),
    }


def kv_heads(cfg: EvaByteConfig) -> int:
    return cfg.n_kv_heads


def pooling(p: Dict[str, Any]) -> Tuple[jax.Array, jax.Array]:
    """A layer's (or the stacked layers') pooling vector and key offset."""
    return p["eva_phi"], p["eva_mu"]


def _norm(x, g, cfg: EvaByteConfig):
    """RMSNorm of the float32 stream times ``1 + g``, handed on in
    ``cfg.dtype``."""
    xf = x.astype(jnp.float32)
    xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.rms_eps)
    return (xf * (1.0 + g.astype(jnp.float32))).astype(cfg.dtype)


def _eva_attend(q, k, v, *, p, cfg):
    out, _ = eva.windowed_attention(
        q, k, v, *pooling(p), window=cfg.window_size, chunk=cfg.chunk_size)
    return out, None


def block(x, p, cfg: EvaByteConfig, attend=None, positions=None, mesh=None):
    """One layer.  ``x [B, T, D]`` FLOAT32 (the residual stream), ``positions``
    ``[T]`` or ``[B, T]`` absolute (None: ``0..T-1``).  ``attend(q, k, v)``:
    the attention middle, given rotated ``q [B, H, T, dh]`` and ``k, v [B, KV,
    T, dh]`` as a cache stores them (None: the whole sequence from position 0,
    :func:`ray_tpu.ops.eva.windowed_attention`).  Returns ``(x, 0, carried)``."""
    B, T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    attend = attend or partial(_eva_attend, p=p, cfg=cfg)
    positions = jnp.arange(T) if positions is None else positions
    lin = lambda h, w: h @ w.astype(h.dtype)  # noqa: E731
    heads = lambda t, n: t.reshape(B, T, n, hd).transpose(0, 2, 1, 3)  # noqa: E731

    h = _norm(x, p["attn_norm"], cfg)
    q = rope_half(heads(lin(h, p["wq"]), H), positions, cfg.rope_base)
    k = rope_half(heads(lin(h, p["wk"]), KV), positions, cfg.rope_base)
    o, carried = attend(q, k, heads(lin(h, p["wv"]), KV))  # [B, H, T, hd]
    o = o.astype(cfg.dtype).transpose(0, 2, 1, 3).reshape(B, T, H * hd)
    x = x + lin(o, p["wo"]).astype(jnp.float32)

    h = _norm(x, p["ffn_norm"], cfg)
    gated = jax.nn.silu(lin(h, p["w_gate"])) * lin(h, p["w_up"])
    return (x + lin(gated, p["w_down"]).astype(jnp.float32),
            jnp.zeros((), jnp.float32), carried)


def embed(params, tokens, cfg: EvaByteConfig, positions=None, mesh=None,
          rules=None) -> jax.Array:
    """bytes ``[B, T]`` -> the float32 stream ``[B, T, D]`` (positions are
    the blocks' rotary embedding)."""
    return params["tok_emb"][tokens].astype(jnp.float32)


def unembed(params, x, cfg: EvaByteConfig, mesh=None, rules=None,
            all_heads: bool = False) -> jax.Array:
    """Final norm and the float32 head: ``x [B, T, D]`` -> logits ``[B, T,
    V]`` of head 0, the next byte's (``all_heads``: ``[B, T, n_pred_heads,
    V]``, head ``p`` for byte ``i + 1 + p``)."""
    h = _norm(x, params["final_norm"], cfg).astype(jnp.float32)
    head = params["head"] if all_heads else params["head"][:, :cfg.vocab_size]
    logits = jnp.dot(h, head.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if all_heads:
        return logits.reshape(*logits.shape[:-1], cfg.n_pred_heads,
                              cfg.vocab_size)
    return logits


def apply(params, tokens, cfg: EvaByteConfig, all_heads: bool = False):
    """The full forward from position 0: ``tokens [B, T]`` (``T`` at most one
    window, or whole windows) -> float32 logits (:func:`unembed`)."""
    x = embed(params, tokens, cfg)
    x, _ = lax.scan(lambda h, p: (block(h, p, cfg)[0], None), x,
                    params["blocks"])
    return unembed(params, x, cfg, all_heads=all_heads)


def num_params(params: Dict[str, Any]) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
