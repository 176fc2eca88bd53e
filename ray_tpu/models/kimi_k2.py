"""Kimi-K2-family decoder LM (``model_type: kimi_k2``; the DeepSeek-V3 layer):
multi-head latent attention, YaRN rotary on a key all heads share, a SwiGLU
dense layer first and sigmoid-routed experts beside a shared one on every
other layer.  Pure jax, serving path (``generate.FAMILIES``).

Why this is a module of its own: a position's cache row is not K and V per
head.  The layer projects the hidden state DOWN to one latent row a position
(``kv_lora_rank`` normed values, then ``qk_rope_head_dim`` rotated key values
every head shares: 512 + 64 = 576) and the heads' keys and values are
up-projections of it, so the row is all a cache has to hold
(``cfg.latent_cache``; :func:`ray_tpu.models.generate.init_cache`), and the
attention has two forms that agree up to rounding:

- un-absorbed (training, ``apply``, prefill): the call holds every position's
  ``k_nope`` and ``v`` once, 192-wide keys and 128-wide values a head;
- absorbed (decode): the up-projections are folded into the query and the
  output, and 64 heads attend the latent rows themselves: one 576-wide key a
  position whose first 512 values are its value vector.

Layer equations (pre-norm residual blocks, ``n`` RMSNorm with ``rms_eps``
1e-5 and a learned scale, no biases):

- q: ``c_q = n(W_dq h)`` (1536); ``[q_nope | q_pe] = W_uq c_q``, 64 heads x
  (128 | 64); ``q_pe = rope(q_pe)``.
- kv: ``[c | k_pe] = W_dkv h`` (512 | 64); ``c = n(c)``; ``k_pe = rope(k_pe)``,
  ONE rotated key shared by the 64 heads.  **What is cached for a position:
  ``[c ; k_pe]``, 576 values.**  ``k_nope = W_uk c``, ``v = W_uv c``, 64 heads
  x 128 each.
- scores ``s_ij = scale (q_nope_i . k_nope_j + q_pe_i . k_pe_j)``, causal,
  softmax in float32, ``o = sum_j p_ij v_j``, ``x += W_o o`` (8192 -> 7168).
  ``scale = 192 ** -0.5 * m * m``, ``m = 0.1 * mscale_all_dim * ln(factor) +
  1`` (:func:`yarn_mscale`); the rotary's cos/sin carry ``yarn_mscale(factor,
  mscale) / yarn_mscale(factor, mscale_all_dim)``, 1 as published.
- YaRN rotary over the 64 rope dimensions (:func:`yarn_inv_freq`): ``inv_freq
  = (1 - ramp) * base_freq / factor + ramp * base_freq``, ``ramp`` 1 below and
  0 above the correction dimensions of ``beta_fast`` and ``beta_slow``,
  linear between.
- decode, absorbed: ``q_lat = q_nope W_uk`` (64 x 512); ``s = scale ([q_lat |
  q_pe] . [c ; k_pe])``; ``o_lat = sum_j p_j c_j`` (64 x 512); ``o = o_lat
  W_uv`` per head.
- layer 0: dense SwiGLU.  Layers 1..: ``s = sigmoid(h W_r)`` in float32,
  ``sel = top_8(s + b)``, ``g_i = routed_scale s_i / sum_{sel} s_j``, ``y =
  E_shared(h) + sum_{i in sel, held here} g_i E_i(h)``
  (:mod:`ray_tpu.ops.moe`, the expert layer K-EXAONE runs); what the absent
  experts would add is left out.  :func:`init` makes a sparse layer's
  ``ew_gate ew_up [held, D, F] ew_down [held, F, D]`` (what a reference
  reads); an engine serves from ``ew_gate_up [held, D, 2F]`` and ``ew_down``
  (:func:`ray_tpu.models.exaone_moe.serving_layout`, once at load).
- head: final RMSNorm, an output matrix of its own (untied).

Departures from the published model: ``kv_b_proj`` is stored as its two
halves, a head at a time (``w_uk [H, 128, 512]``, ``w_uv [H, 512, 128]``: the
same numbers, laid out for both forms); the rotary pairs dimensions ``(2i, 2i
+ 1)`` in place (the published code moves each pair to ``(i, i + 32)`` first:
the same dot products); ``n_group = topk_group = 1``, so no group limit is
built; the vision tower of the family's multimodal siblings is not: the text
path is what is served.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models.exaone_moe import (
    _selection_bias, _sparse_ffn, _swiglu, serving_layout)
from ray_tpu.models.transformer import _attend
from ray_tpu.ops.layers import dense, rmsnorm

__all__ = [
    "KimiK2Config", "init", "init_layer", "apply", "block", "embed",
    "unembed", "kv_heads", "num_params", "yarn_inv_freq", "yarn_mscale",
    "latent_projections",
]


@dataclasses.dataclass(frozen=True)
class KimiK2Config:
    vocab_size: int = 163_840
    n_layers: int = 61
    n_heads: int = 64
    d_model: int = 7168
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 18_432            # the dense layers' SwiGLU width
    d_expert: int = 2048          # an expert's, routed or shared
    n_experts: int = 384          # the router's width, whatever is held here
    experts_per_token: int = 8
    n_shared_experts: int = 1
    routed_scale: float = 2.827
    # (first, count): the block of experts this chip holds of each sparse
    # layer; None: all of them
    experts_held: Optional[tuple] = None
    first_dense_layers: int = 1   # first_k_dense_replace
    rope_base: float = 50_000.0
    # rope_scaling, type yarn
    rope_factor: float = 64.0
    rope_original_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_eps: float = 1e-5
    max_seq_len: int = 262_144
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        held = tuple(self.experts_held or (0, self.n_experts))
        assert 0 <= held[0] and held[0] + held[1] <= self.n_experts, held
        # a frozen dataclass that jit closes over has to hash: a tuple
        object.__setattr__(self, "experts_held", held)

    @property
    def latent_cache(self) -> tuple:
        """``(values a cached position holds, of which the first are its
        value vector)``: what :mod:`ray_tpu.models.generate` reads to give
        this family's layers the latent kind of cache."""
        return (self.kv_lora_rank + self.qk_rope_head_dim, self.kv_lora_rank)

    @property
    def attention_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @staticmethod
    def k2_7_code(**kw) -> "KimiK2Config":
        return KimiK2Config(**kw)

    @staticmethod
    def tiny(**kw) -> "KimiK2Config":
        base = dict(vocab_size=256, n_layers=3, n_heads=4, d_model=32,
                    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
                    qk_rope_head_dim=8, v_head_dim=8, d_ff=64, d_expert=24,
                    n_experts=16, experts_per_token=4, max_seq_len=512,
                    rope_original_positions=32)
        base.update(kw)
        return KimiK2Config(**base)


# the family table (ray_tpu.models.generate.FAMILIES) reads these two: the
# config class, and the presets ``size`` names
Config = KimiK2Config
SIZES = {"k2.7-code": KimiK2Config.k2_7_code, "tiny": KimiK2Config.tiny}


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: KimiK2Config) -> jax.Array:
    """The rotary's ``qk_rope_head_dim / 2`` frequencies, the DeepSeek-V3 form
    of YaRN: a dimension that turns more than ``beta_fast`` times over the
    original positions keeps its frequency, one that turns less than
    ``beta_slow`` times has it divided by ``factor``, linear between."""
    d, base = cfg.qk_rope_head_dim, cfg.rope_base

    def correction_dim(rotations):
        return (d * math.log(cfg.rope_original_positions
                             / (rotations * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), d - 1)
    base_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ramp = 1.0 - jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3),
        0.0, 1.0)
    return (1.0 - ramp) * base_freq / cfg.rope_factor + ramp * base_freq


def rope_yarn(x: jax.Array, positions: jax.Array, cfg: KimiK2Config) -> jax.Array:
    """``x [B, heads, T, qk_rope_head_dim]`` rotated at ``positions`` (``[T]``
    or ``[B, T]``), dimension ``2i`` paired with ``2i + 1``."""
    angles = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
    if positions.ndim == 2:
        angles = angles[:, None]                              # [B, 1, T, d/2]
    mscale = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
              / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    cos, sin = jnp.cos(angles) * mscale, jnp.sin(angles) * mscale
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def init_layer(cfg: KimiK2Config, key: jax.Array, layer: int) -> Dict[str, Any]:
    """Layer ``layer``'s parameters in ``cfg.dtype``, from ``fold_in(key,
    layer)`` alone (as :func:`ray_tpu.models.exaone_moe.init_layer`: a served
    model is made a layer at a time and never exists in float32)."""
    D, H = cfg.d_model, cfg.n_heads
    nope, pe, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    keys = iter(jax.random.split(jax.random.fold_in(key, layer), 24))

    def w(*shape, fan_in, scale=1.0):  # fan-in scaled normal, made in cfg.dtype
        return (jax.random.normal(next(keys), shape, cfg.dtype)
                * jnp.asarray(scale * fan_in ** -0.5, cfg.dtype))

    def scale_near_one(n):  # learned norm scales: not all ones, so they count
        return (1.0 + 0.1 * jax.random.normal(next(keys), (n,))).astype(cfg.dtype)

    p = {
        "attn_norm": scale_near_one(D), "ffn_norm": scale_near_one(D),
        "w_dq": w(D, rq, fan_in=D), "q_norm": scale_near_one(rq),
        "w_uq": w(rq, H * (nope + pe), fan_in=rq),
        "w_dkv": w(D, rkv + pe, fan_in=D), "kv_norm": scale_near_one(rkv),
        # kv_b_proj's two halves, a head at a time
        "w_uk": w(H, nope, rkv, fan_in=rkv), "w_uv": w(H, rkv, dv, fan_in=rkv),
        "wo": w(H * dv, D, fan_in=H * dv, scale=0.5),
    }
    if layer < cfg.first_dense_layers:
        p.update(w_gate=w(D, cfg.d_ff, fan_in=D), w_up=w(D, cfg.d_ff, fan_in=D),
                 w_down=w(cfg.d_ff, D, fan_in=cfg.d_ff, scale=0.5))
        return p
    E, F, Fs = cfg.experts_held[1], cfg.d_expert, cfg.d_expert * cfg.n_shared_experts
    p.update(
        # over ALL experts; the selection bias as K-EXAONE's (zero mean over
        # every eight experts in a row), so that choosing and weighting differ
        router=w(D, cfg.n_experts, fan_in=D),
        router_bias=_selection_bias(next(keys), cfg.n_experts).astype(cfg.dtype),
        ew_gate=w(E, D, F, fan_in=D), ew_up=w(E, D, F, fan_in=D),
        ew_down=w(E, F, D, fan_in=F, scale=0.5),
        sw_gate=w(D, Fs, fan_in=D), sw_up=w(D, Fs, fan_in=D),
        sw_down=w(Fs, D, fan_in=Fs, scale=0.5),
    )
    return p


def init(cfg: KimiK2Config, key: jax.Array) -> Dict[str, Any]:
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


def kv_heads(cfg: KimiK2Config) -> int:
    """Heads a cache holds for a position: the one latent row."""
    return 1


def latent_projections(h, p, *, heads: int, nope: int, norm, rope,
                       absorbed: bool, rescale=(1.0, 1.0), context=None):
    """The projections of one latent-attention layer, ``h [B, T, D]`` (normed)
    -> ``(q, k, v, row, c_q)``: ``row [B, 1, T, rkv + pe]`` is what a cache
    holds for the positions (the normed latent, then the rotated shared key),
    ``c_q [B, T, rq]`` the query's normed latent.  Un-absorbed: ``q, k [B, H,
    T, nope + pe]``, ``v [B, H, T, dv]``.  ``absorbed``: ``q [B, H, T, rkv +
    pe]`` against ONE key head, ``k`` the rows themselves and ``v`` their first
    ``rkv`` values.  ``rope(t)`` rotates ``t [B, heads, T, pe]``; ``rescale``:
    factors on the two normed latents (a family that has them).
    ``context(row, up) -> (k, v)`` (un-absorbed; None: ``up(row)``): a
    prompt's PART, whose keys and values cover what the cache holds before it
    with its own rows among them; ``up(rows [B, 1, Tk, rkv + pe]) -> (k, v)``
    is this layer's up-projection of any rows, and the caller decides which
    positions it is run over
    (:func:`ray_tpu.models.generate.prefill_at`)."""
    B, T, _ = h.shape
    rkv = p["kv_norm"].shape[0]
    c_q = norm(dense(h, p["w_dq"]), p["q_norm"])
    ckv = dense(h, p["w_dkv"])[:, None]                        # [B, 1, T, row]
    c = norm(ckv[..., :rkv], p["kv_norm"])
    if rescale != (1.0, 1.0):
        c_q = c_q * jnp.asarray(rescale[0], c_q.dtype)
        c = c * jnp.asarray(rescale[1], c.dtype)
    q = dense(c_q, p["w_uq"])
    q = q.reshape(B, T, heads, -1).transpose(0, 2, 1, 3)
    pe = q.shape[-1] - nope
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:])
    row = jnp.concatenate([c, rope(ckv[..., rkv:])], axis=-1)
    w_uk, w_uv = p["w_uk"].astype(h.dtype), p["w_uv"].astype(h.dtype)
    if absorbed:
        q = jnp.concatenate(
            [jnp.einsum("bhtd,hdc->bhtc", q_nope, w_uk), q_pe], axis=-1)
        return q, row, row[..., :rkv], row, c_q
    split = lambda held: (held[:, 0, :, :rkv], held[..., rkv:])  # noqa: E731

    def up(c, k_pe):  # rows' latents and shared keys -> a key and value a head
        k = jnp.concatenate([
            jnp.einsum("btc,hdc->bhtd", c, w_uk),
            jnp.broadcast_to(k_pe, (B, heads, c.shape[1], pe))], axis=-1)
        return k, jnp.einsum("btc,hcv->bhtv", c, w_uv)

    # (a whole call's operations in the order they always had: its lowered
    # text is what a change to the part's path is checked against)
    own = None if context else split(row)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k, v = context(row, lambda held: up(*split(held))) if context else up(*own)
    return q, k, v, row, c_q


def block(x, p, cfg: KimiK2Config, attend=None, positions=None,
          mesh: Optional[Mesh] = None, *, window: int = 0, valid=None,
          absorbed: bool = False, context=None):
    """One layer.  x: [B, T, D] in cfg.dtype; rotary at ``positions`` ([T] or
    [B, T]; None: 0..T-1); whether its FFN is dense or sparse shows in its
    parameters; ``window`` is always 0 (the listed-layers loops of
    :mod:`ray_tpu.models.generate` pass every family its layer's).
    ``attend(q, k, v, row)``: the attention middle
    (:mod:`ray_tpu.models.transformer`), in one of two forms.  Un-absorbed:
    ``q, k [B, H, T, 192]``, ``v [B, H, T, 128]``.  ``absorbed``: ``q [B, H,
    T, 576]`` against ONE key head, ``k [B, 1, T, 576]`` the latent rows
    themselves and ``v`` their first 512 values.  ``row [B, 1, T, 576]`` is
    what a cache holds for the positions, either way.  ``valid`` ([B, T] or
    [B, 1] bool; None: all): the real tokens, the only ones an expert sees.
    ``context``: :func:`latent_projections`' (a prompt's part: the keys and
    values cover what the cache holds before it).
    Returns ``(x, routed, carried)``; ``routed`` is None for a dense layer."""
    B, T, D = x.shape
    H, nope, scale = cfg.n_heads, cfg.qk_nope_head_dim, cfg.attention_scale
    assert not window, window
    attend = attend or (lambda q, k, v, row: _attend(
        q, k, v, causal=True, mesh=mesh, scale=scale))
    positions = jnp.arange(T) if positions is None else positions
    norm = partial(rmsnorm, eps=cfg.rms_eps)

    h = norm(x, p["attn_norm"])
    with jax.named_scope("attention.mla_proj"):
        q, k, v, row, _ = latent_projections(
            h, p, heads=H, nope=nope, norm=norm, absorbed=absorbed,
            rope=lambda t: rope_yarn(t, positions, cfg), context=context)
        w_uv = p["w_uv"].astype(x.dtype)
    with jax.named_scope("attention.latent"):
        o, carried = attend(q, k, v, row)
    with jax.named_scope("attention.mla_proj"):
        if absorbed:
            o = jnp.einsum("bhtc,hcv->bhtv", o, w_uv)
        x = x + dense(o.transpose(0, 2, 1, 3).reshape(B, T, -1), p["wo"])

    h = norm(x, p["ffn_norm"])
    if "router" in p:
        y, routed = _sparse_ffn(h, p, cfg, valid)
    else:
        with jax.named_scope("dense_ffn"):
            y, routed = _swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), None
    return x + y, routed, carried


def embed(params: Dict[str, Any], tokens: jax.Array, cfg: KimiK2Config,
          positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, T] -> x [B, T, D] in cfg.dtype (``positions`` is not used:
    this family's positions are the rotary on the shared key)."""
    return params["tok_emb"][tokens].astype(cfg.dtype)


def unembed(params: Dict[str, Any], x: jax.Array, cfg: KimiK2Config) -> jax.Array:
    """Final norm and the output matrix (its own, untied, over the slice of
    the vocabulary held): x [B, T, D] -> logits [B, T, V] f32."""
    x = rmsnorm(x, params["final_norm"], eps=cfg.rms_eps)
    return dense(x, params["head"]).astype(jnp.float32)


def apply(params: Dict[str, Any], tokens: jax.Array, cfg: KimiK2Config,
          *, absorbed: bool = False) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, V] f32: the whole forward, no
    cache (the tests hold prefill and decode to it, and the two forms of the
    attention to each other), over :func:`init`'s tree or the served one
    (:func:`ray_tpu.models.exaone_moe.serving_layout`)."""
    params = serving_layout(jax.tree.map(lambda a: a, params))
    x = embed(params, tokens, cfg)
    for p in params["layers"]:
        x, _, _ = block(x, p, cfg, absorbed=absorbed)
    return unembed(params, x, cfg)


def num_params(params: Dict[str, Any]) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
