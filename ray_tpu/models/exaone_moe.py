"""EXAONE-MoE-family decoder LM (K-EXAONE): RMSNorm, QK-norm, grouped-query
attention with an explicit head size, window and full attention layers mixed,
a SwiGLU dense layer first and sigmoid-routed experts beside a shared one on
every other layer.  Pure jax, serving path (``generate.FAMILIES``).

What differs from :mod:`ray_tpu.models.llama`, and why this is a module of
its own: ``n_heads * head_dim != d_model``; the layers are NOT alike
(``layer_types``: a ``sliding_attention`` layer rotates q, k and attends the
last ``sliding_window`` positions, a ``full_attention`` layer has no rotary
and attends everything; ``mlp_layer_types``: ``dense`` or ``sparse``), so the
parameters are a LIST of layers (``params["layers"]``), not a stack, and the
layer loops of :mod:`ray_tpu.models.generate` run them unrolled, each kind of
layer against its own kind of cache; and a sparse layer is told which experts
this chip holds (``experts_held``), routes over all of them and computes its
own experts' part (:func:`ray_tpu.ops.moe.held_experts_ffn`).

Layer equations (pre-norm residual blocks, ``n`` the RMSNorm):

- attention: ``q, k, v = W_q n(x), W_k n(x), W_v n(x)`` without biases; ``q,
  k`` RMS-normalised over the head dimension with a learned scale; on a
  window layer rotary (``rope_base``, the rotate-half convention) and
  position ``i`` attends ``i - window < j <= i``; on a full layer ``j <= i``;
  scale ``head_dim ** -0.5``; ``x += W_o o``.
- dense FFN: ``x += W_down(silu(W_gate h) * W_up h)``.
- sparse FFN: ``s = sigmoid(h W_r)`` in float32, ``sel = top_k(s + b)``,
  ``g_i = routed_scale * s_i / sum_{j in sel} s_j``, ``y = E_shared(h) +
  sum_{i in sel, held here} g_i E_i(h)``; what the absent experts would add is
  left out.  A sparse layer's leaves as :func:`init` makes them (and a
  reference reads them): ``router router_bias ew_gate ew_up [held, D, F]
  ew_down [held, F, D] sw_gate sw_up sw_down``; as an engine serves them
  (:func:`serving_layout`, once at load; what :func:`block` reads): ``ew_gate``
  and ``ew_up`` as ONE leaf ``ew_gate_up [held, D, 2F]``, so that the experts'
  SwiGLU is two grouped matmuls.
- head: final RMSNorm, an output matrix of its own (untied).

Multi-token prediction (the published model's one MTP module) is not built:
the engine yields one token a slot a step.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models.transformer import _attend
from ray_tpu.ops.layers import dense, rmsnorm
from ray_tpu.ops.moe import (
    gate_up_side_by_side, held_experts_ffn, route_sigmoid_top_k)

__all__ = [
    "ExaoneMoeConfig", "init", "init_layer", "serving_layout", "apply",
    "block", "embed", "unembed", "kv_heads", "num_params",
]

WINDOW, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    vocab_size: int = 153_600
    n_layers: int = 48
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    d_model: int = 6144
    d_ff: int = 18_432            # the dense layers' SwiGLU width
    d_expert: int = 2048          # an expert's, routed or shared
    n_experts: int = 128          # the router's width, whatever is held here
    experts_per_token: int = 8
    n_shared_experts: int = 1
    routed_scale: float = 2.5
    # (first, count): the block of experts this chip holds of each sparse
    # layer; None: all of them
    experts_held: Optional[tuple] = None
    # per layer, as published; longer lists are read up to n_layers.  Left
    # empty: three window layers then a full one, repeated; the first layer
    # dense and every other one sparse
    layer_types: tuple = ()
    mlp_layer_types: tuple = ()
    sliding_window: int = 128
    rope_base: float = 1_000_000.0
    rms_eps: float = 1e-5
    max_seq_len: int = 262_144
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L = self.n_layers
        kinds = tuple(self.layer_types)[:L] or tuple(
            FULL if l % 4 == 3 else WINDOW for l in range(L))
        mlps = tuple(self.mlp_layer_types)[:L] or tuple(
            DENSE if l == 0 else SPARSE for l in range(L))
        held = tuple(self.experts_held or (0, self.n_experts))
        assert len(kinds) == L and len(mlps) == L, (kinds, mlps)
        assert set(kinds) <= {WINDOW, FULL} and set(mlps) <= {DENSE, SPARSE}
        assert 0 <= held[0] and held[0] + held[1] <= self.n_experts, held
        # a frozen dataclass that jit closes over has to hash: tuples
        for name, value in (("layer_types", kinds), ("mlp_layer_types", mlps),
                            ("experts_held", held)):
            object.__setattr__(self, name, value)

    @property
    def sliding_windows(self) -> tuple:
        """Per layer, the positions it attends: 0 is every one (what
        :func:`ray_tpu.models.generate.layer_windows` reads)."""
        return tuple(self.sliding_window if t == WINDOW else 0
                     for t in self.layer_types)

    @staticmethod
    def k_exaone_236b(**kw) -> "ExaoneMoeConfig":
        return ExaoneMoeConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "ExaoneMoeConfig":
        base = dict(vocab_size=256, n_layers=5, n_heads=4, n_kv_heads=2,
                    head_dim=16, d_model=32, d_ff=64, d_expert=24,
                    n_experts=16, experts_per_token=4, sliding_window=8,
                    max_seq_len=512)
        base.update(kw)
        return ExaoneMoeConfig(**base)


# the family table (ray_tpu.models.generate.FAMILIES) reads these two: the
# config class, and the presets ``size`` names
Config = ExaoneMoeConfig
SIZES = {"236b": ExaoneMoeConfig.k_exaone_236b, "tiny": ExaoneMoeConfig.tiny}


def init_layer(cfg: ExaoneMoeConfig, key: jax.Array, layer: int) -> Dict[str, Any]:
    """Layer ``layer``'s parameters in ``cfg.dtype``, from ``fold_in(key,
    layer)`` alone: a served model is made a layer at a time and never exists
    in float32 (3.7 B parameters would be 14.8 GB), and a reference can
    remake any one layer."""
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(jax.random.fold_in(key, layer), 16))

    def w(*shape, scale=1.0):  # fan-in scaled normal, made in cfg.dtype
        return (jax.random.normal(next(keys), shape, cfg.dtype)
                * jnp.asarray(scale * shape[-2] ** -0.5, cfg.dtype))

    def scale_near_one(n):  # learned norm scales: not all ones, so they count
        return (1.0 + 0.1 * jax.random.normal(next(keys), (n,))).astype(cfg.dtype)

    p = {
        "attn_norm": scale_near_one(D), "ffn_norm": scale_near_one(D),
        "q_norm": scale_near_one(hd), "k_norm": scale_near_one(hd),
        "wq": w(D, H * hd), "wk": w(D, KV * hd), "wv": w(D, KV * hd),
        "wo": w(H * hd, D, scale=0.5),
    }
    if cfg.mlp_layer_types[layer] == DENSE:
        p.update(w_gate=w(D, cfg.d_ff), w_up=w(D, cfg.d_ff),
                 w_down=w(cfg.d_ff, D, scale=0.5))
        return p
    E, F, Fs = cfg.experts_held[1], cfg.d_expert, cfg.d_expert * cfg.n_shared_experts
    p.update(
        # over ALL experts.  The selection bias makes choosing and weighting
        # differ; it has zero mean over every eight experts in a row, as a
        # bias trained to balance the load leaves no chip's block favoured
        router=w(D, cfg.n_experts),
        router_bias=_selection_bias(next(keys), cfg.n_experts).astype(cfg.dtype),
        ew_gate=w(E, D, F), ew_up=w(E, D, F), ew_down=w(E, F, D, scale=0.5),
        sw_gate=w(D, Fs), sw_up=w(D, Fs), sw_down=w(Fs, D, scale=0.5),
    )
    return p


def _selection_bias(key, n_experts: int, std: float = 0.02) -> jax.Array:
    b = std * jax.random.normal(key, (n_experts,))
    if n_experts % 8 == 0:
        b = (b.reshape(-1, 8) - b.reshape(-1, 8).mean(1, keepdims=True)).reshape(-1)
    return b - b.mean()


def init(cfg: ExaoneMoeConfig, key: jax.Array) -> Dict[str, Any]:
    """``{"tok_emb", "head", "final_norm", "layers": [one dict a layer]}``,
    every leaf in ``cfg.dtype`` (:func:`init_layer`)."""
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    D, V = cfg.d_model, cfg.vocab_size
    return {
        "tok_emb": jax.random.normal(k_emb, (V, D), cfg.dtype),
        "head": (jax.random.normal(k_head, (D, V), cfg.dtype)
                 * jnp.asarray(D ** -0.5, cfg.dtype)),
        "final_norm": jnp.ones((D,), cfg.dtype),
        "layers": [init_layer(cfg, k_layers, l) for l in range(cfg.n_layers)],
    }


def serving_layout(params: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`init`'s tree as it is SERVED, what :func:`block` reads: every
    sparse layer's ``ew_gate`` and ``ew_up`` as ONE leaf ``ew_gate_up``
    (:func:`ray_tpu.ops.moe.gate_up_side_by_side`), a layer at a time and IN
    PLACE on the layers' dicts, which the caller owns
    (:func:`ray_tpu.models.generate.serving_layout`).  Kimi-K2's and dots3's
    trees are laid out as this family's, so this is their hook too."""
    for p in params.get("layers", ()):
        gate_up_side_by_side(p)
    return params


def kv_heads(cfg: ExaoneMoeConfig) -> int:
    """K/V heads a cache holds for a position (the GQA saving)."""
    return cfg.n_kv_heads


def rope_half(x: jax.Array, positions: jax.Array, base: float) -> jax.Array:
    """Rotary embedding, the rotate-half convention of the published family
    (dimension ``i`` pairs with ``i + d/2``).  ``x [B, heads, T, d]``;
    ``positions`` ``[T]`` or ``[B, T]``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [.., T, d/2]
    if positions.ndim == 2:
        angles = angles[:, None]                                  # [B, 1, T, d/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _swiglu(h, w_gate, w_up, w_down):
    return dense(jax.nn.silu(dense(h, w_gate)) * dense(h, w_up), w_down)


def _sparse_ffn(h, p, cfg: ExaoneMoeConfig, valid):
    """The expert layer as this chip holds it -> ``(y, routed)``; ``routed``
    counts, of the valid tokens, those each held expert got and how many of
    the held experts got any."""
    B, T, D = h.shape
    flat = h.reshape(B * T, D)
    if valid is not None:
        valid = jnp.broadcast_to(valid, (B, T)).reshape(B * T)
    with jax.named_scope("moe.router"):
        experts, gates = route_sigmoid_top_k(
            flat, p["router"], p["router_bias"], cfg.experts_per_token,
            cfg.routed_scale)
    with jax.named_scope("moe.expert_ffn"):
        y, tokens = held_experts_ffn(
            flat, experts, gates, p["ew_gate_up"], p["ew_down"],
            first_expert=cfg.experts_held[0], valid=valid)
    with jax.named_scope("moe.shared_ffn"):
        shared = _swiglu(h, p["sw_gate"], p["sw_up"], p["sw_down"])
    y = (y.reshape(B, T, D) + shared.astype(jnp.float32)).astype(h.dtype)
    return y, {"tokens": tokens, "touched": (tokens > 0).sum().astype(jnp.int32)}


def block(x, p, cfg: ExaoneMoeConfig, attend=None, positions=None,
          mesh: Optional[Mesh] = None, *, window: int = 0, valid=None):
    """One layer.  x: [B, T, D] in cfg.dtype.  ``window``: the layer's kind
    (0: a full layer, no rotary; else a window layer, rotary at
    ``positions`` [T] or [B, T], None: 0..T-1); whether its FFN is dense or
    sparse shows in its parameters.  ``attend``: the attention middle
    (:mod:`ray_tpu.models.transformer`), given q and k, v in the KV-head
    layout a cache stores.  ``valid`` ([B, T] or [B, 1] bool; None: all):
    the real tokens, the only ones an expert sees.  Returns ``(x, routed,
    carried)``; ``routed`` is None for a dense layer."""
    B, T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    attend = attend or partial(_attend, causal=True, mesh=mesh, window=window)
    positions = jnp.arange(T) if positions is None else positions
    norm = partial(rmsnorm, eps=cfg.rms_eps)

    h = norm(x, p["attn_norm"])
    q = norm(dense(h, p["wq"]).reshape(B, T, H, hd), p["q_norm"])
    k = norm(dense(h, p["wk"]).reshape(B, T, KV, hd), p["k_norm"])
    v = dense(h, p["wv"]).reshape(B, T, KV, hd)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [B, heads, T, hd]
    if window:
        q = rope_half(q, positions, cfg.rope_base)
        k = rope_half(k, positions, cfg.rope_base)
    with jax.named_scope("attention.window" if window else "attention.full"):
        o, carried = attend(q, k, v)
    x = x + dense(o.transpose(0, 2, 1, 3).reshape(B, T, H * hd), p["wo"])

    h = norm(x, p["ffn_norm"])
    if "router" in p:
        y, routed = _sparse_ffn(h, p, cfg, valid)
    else:
        with jax.named_scope("dense_ffn"):
            y, routed = _swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), None
    return x + y, routed, carried


def embed(params: Dict[str, Any], tokens: jax.Array, cfg: ExaoneMoeConfig,
          positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, T] -> x [B, T, D] in cfg.dtype (``positions`` is not used:
    this family's positions are the window layers' rotary embedding)."""
    return params["tok_emb"][tokens].astype(cfg.dtype)


def unembed(params: Dict[str, Any], x: jax.Array, cfg: ExaoneMoeConfig) -> jax.Array:
    """Final norm and the output matrix (its own, untied, over the slice of
    the vocabulary held): x [B, T, D] -> logits [B, T, V] f32."""
    x = rmsnorm(x, params["final_norm"], eps=cfg.rms_eps)
    return dense(x, params["head"]).astype(jnp.float32)


def apply(params: Dict[str, Any], tokens: jax.Array, cfg: ExaoneMoeConfig) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, V] f32: the whole forward, no
    cache (the tests hold prefill and decode to it), over :func:`init`'s tree
    or the served one."""
    params = serving_layout(jax.tree.map(lambda a: a, params))
    x = embed(params, tokens, cfg)
    for p, window in zip(params["layers"], cfg.sliding_windows):
        x, _, _ = block(x, p, cfg, window=window)
    return unembed(params, x, cfg)


def num_params(params: Dict[str, Any]) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
