"""dots3-note-family decoder LM (``model_type: dots3_note``): two kinds of
multi-head LATENT attention in one model, a learned index that picks which
cached positions a full layer's queries read, a headwise output gate, a SwiGLU
dense layer first and sigmoid-routed experts beside a shared one on every
other layer.  Pure jax, serving path (``generate.FAMILIES``).

Why this is a module of its own: every layer caches one latent row a position
(as :mod:`ray_tpu.models.kimi_k2`), but the two kinds of layer have sizes of
their own (``layer_types``): a FULL layer keeps a 576-value row for every
position AND a 128-value index key beside it, and its queries attend only the
``index_topk`` positions their index scores put first; a SLIDING layer keeps a
1,088-value row and attends the last ``sliding_window`` positions, so a ring
holds all it needs.  What a config of this family tells
:mod:`ray_tpu.models.generate`: ``latent_cache`` and ``attention_scale`` (the
full layers' row), ``window_latent_cache`` and ``window_attention_scale`` (the
sliding layers'), ``index_cache`` (the index key's width and the top-k),
``sliding_windows``.

Layer equations (pre-norm residual blocks, ``n`` RMSNorm with ``rms_eps``
1e-5 and a learned scale, no biases on a projection):

Full layer (``layer_types[l] == "full_attention"``; 128 heads as published):

- ``c_q = r_q n(W_dq h)`` (1024); ``[q_nope | q_pe] = W_uq c_q``, 128 heads x
  (128 | 64); ``q_pe = rope(q_pe)``, base 8e7, plain rotary (``rope_scaling``
  null).
- ``[c | k_pe] = W_dkv h`` (512 | 64); ``c = r_kv n(c)``; ``k_pe =
  rope(k_pe)``.  **Cached for a position: ``[c ; k_pe]``, 576 values.**
  ``k_nope = W_uk c``, ``v = W_uv c``, 128 heads x 128.  ``scale = 192 **
  -0.5``.
- ``apply_mla_qkv_lora_rescale``: ``r_q = sqrt(d_model / q_lora_rank)``,
  ``r_kv = sqrt(d_model / kv_lora_rank)`` on the normed latents (ASSUMED: the
  reading LongCat-Flash's ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` give
  the same words).
- indexer (DeepSeek-V3.2's): ``qI = W_qI c_q`` -> 64 heads x 128; ``kI =
  LayerNorm(W_kI h)`` (128: ONE key a position, **cached**); rotary on the
  first 64 values of both; ``w = W_w h`` (64); ``I[t, s] = sum_h w[t, h]
  relu(qI[t, h] . kI[s]) 128 ** -0.5 64 ** -0.5`` in float32 for ``s <= t``;
  ``S_t`` = the 2,048 positions ``s <= t`` of largest ``I[t, s]`` (all of them
  while ``t < 2048``; ties: the lower position first).  **The softmax runs
  over ``S_t`` alone** (:mod:`ray_tpu.ops.dsa`).
- gate: ``g = sigmoid(W_g h)``, one value a head; ``o_head *= g_head`` before
  ``W_o`` (16,384 -> 5120).  ``attention_gate_type: headwise`` read as the
  per-head form of gated attention (ASSUMED).
- decode, absorbed, as Kimi-K2's: ``q_lat = q_nope W_uk``, scores against the
  576-value rows of ``S_t``, ``o = (sum_j p_j c_j) W_uv``.

Sliding layer: the same latent attention with the ``swa_*`` sizes: 64 heads,
q rank 1024, kv rank 1024 (``r_kv = sqrt(5)``), 192 | 64 | 128 a head, cached
row 1,088 values, rotary base 50,000, ``scale = 256 ** -0.5``, a gate of 64;
no indexer; position ``i`` attends ``i - window < j <= i`` (the tree's window
convention; ASSUMED).

FFN: layer 0 dense SwiGLU; layers 1..: ``s = sigmoid(h W_r)`` float32, ``sel =
top_8(s + b)``, ``g_i = s_i / sum_sel s_j``, ``y = E_shared(h) + sum_{i in
sel, held} g_i E_i(h)`` (:func:`ray_tpu.models.exaone_moe._sparse_ffn` as it
stands; no group limit; :func:`init` makes ``ew_gate ew_up [held, D, F]
ew_down``, what a reference reads, and an engine serves from ``ew_gate_up
[held, D, 2F]`` and ``ew_down``: :func:`ray_tpu.models.exaone_moe.
serving_layout`, once at load).  Final RMSNorm, an output matrix of its own.

Departures from the published model: ``kv_b_proj`` as its two halves a head
and the rotary pairing ``(2i, 2i + 1)`` in place, as :mod:`kimi_k2`; the index
keys are kept in bfloat16 (the V3.2 code keeps them in FP8 after a Hadamard
rotation, which leaves the dot products unchanged); no multi-token prediction,
no vision or audio tower: the text path is what is served.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models.exaone_moe import (
    _selection_bias, _sparse_ffn, _swiglu, serving_layout)
from ray_tpu.models.kimi_k2 import latent_projections
from ray_tpu.models.transformer import _attend
from ray_tpu.ops.dsa import selected_attention
from ray_tpu.ops.layers import dense, layernorm, rmsnorm, rope

__all__ = [
    "Dots3NoteConfig", "init", "init_layer", "apply", "block", "embed",
    "unembed", "kv_heads", "num_params",
]

WINDOW, FULL = "sliding_attention", "full_attention"

# the init's standard deviation of an attention score over random positions
SCORE_SPREAD = 3.0


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 152_064
    n_layers: int = 46
    d_model: int = 5120
    # the full layers' latent attention
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_base: float = 80_000_000.0
    # their indexer
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # the sliding layers' latent attention (``swa_*``)
    swa_n_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_base: float = 50_000.0
    sliding_window: int = 513
    # apply_mla_qkv_lora_rescale
    lora_rescale: bool = True
    d_ff: int = 13_824            # the dense layer's SwiGLU width
    d_expert: int = 1536          # an expert's, routed or shared
    n_experts: int = 256          # the router's width, whatever is held here
    experts_per_token: int = 8
    n_shared_experts: int = 1
    routed_scale: float = 1.0
    # (first, count): the block of experts this chip holds of each sparse
    # layer; None: all of them
    experts_held: Optional[tuple] = None
    first_dense_layers: int = 1   # first_k_dense_replace
    # per layer, as published; longer lists are read up to n_layers.  Left
    # empty: two full layers, then three sliding and a full one, repeated
    layer_types: tuple = ()
    rms_eps: float = 1e-5
    max_seq_len: int = 524_288
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L = self.n_layers
        kinds = tuple(self.layer_types)[:L] or tuple(
            FULL if l == 0 or l % 4 == 1 else WINDOW for l in range(L))
        held = tuple(self.experts_held or (0, self.n_experts))
        assert len(kinds) == L and set(kinds) <= {WINDOW, FULL}, kinds
        assert 0 <= held[0] and held[0] + held[1] <= self.n_experts, held
        # a frozen dataclass that jit closes over has to hash: tuples
        object.__setattr__(self, "layer_types", kinds)
        object.__setattr__(self, "experts_held", held)

    # what :mod:`ray_tpu.models.generate` reads of the layers' caches
    @property
    def sliding_windows(self) -> tuple:
        return tuple(self.sliding_window if t == WINDOW else 0
                     for t in self.layer_types)

    @property
    def latent_cache(self) -> tuple:
        """A full layer's row: ``(values, of which the first are the
        position's value vector)``."""
        return (self.kv_lora_rank + self.qk_rope_head_dim, self.kv_lora_rank)

    @property
    def window_latent_cache(self) -> tuple:
        """A sliding layer's row, as :attr:`latent_cache`."""
        return (self.swa_kv_lora_rank + self.swa_qk_rope_head_dim,
                self.swa_kv_lora_rank)

    @property
    def index_cache(self) -> tuple:
        """``(values of the index key a full layer caches a position, the
        positions a query selects)``."""
        return (self.index_head_dim, self.index_topk)

    @property
    def attention_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def window_attention_scale(self) -> float:
        return (self.swa_qk_nope_head_dim + self.swa_qk_rope_head_dim) ** -0.5

    def sizes(self, window: bool) -> dict:
        """The latent attention of one kind of layer: heads, ranks, head
        sizes, rotary base, scale, the factors on the normed latents."""
        pre = "swa_" if window else ""
        get = lambda name: getattr(self, pre + name)  # noqa: E731
        rq, rkv = get("q_lora_rank"), get("kv_lora_rank")
        return dict(
            heads=get("n_heads"), rq=rq, rkv=rkv, nope=get("qk_nope_head_dim"),
            pe=get("qk_rope_head_dim"), dv=get("v_head_dim"),
            rope_base=get("rope_base"),
            scale=self.window_attention_scale if window else self.attention_scale,
            rescale=((self.d_model / rq) ** 0.5, (self.d_model / rkv) ** 0.5)
            if self.lora_rescale else (1.0, 1.0))

    @staticmethod
    def note_prev(**kw) -> "Dots3NoteConfig":
        return Dots3NoteConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "Dots3NoteConfig":
        base = dict(vocab_size=256, n_layers=5, d_model=32, n_heads=4,
                    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
                    qk_rope_head_dim=8, v_head_dim=8, index_n_heads=2,
                    index_head_dim=16, index_topk=8, swa_n_heads=2,
                    swa_q_lora_rank=24, swa_kv_lora_rank=24,
                    swa_qk_nope_head_dim=16, swa_qk_rope_head_dim=8,
                    swa_v_head_dim=8, sliding_window=5, d_ff=64, d_expert=24,
                    n_experts=16, experts_per_token=4, max_seq_len=512)
        base.update(kw)
        return Dots3NoteConfig(**base)


# the family table (ray_tpu.models.generate.FAMILIES) reads these two: the
# config class, and the presets ``size`` names
Config = Dots3NoteConfig
SIZES = {"note-prev": Dots3NoteConfig.note_prev, "tiny": Dots3NoteConfig.tiny}


def init_layer(cfg: Dots3NoteConfig, key: jax.Array, layer: int) -> Dict[str, Any]:
    """Layer ``layer``'s parameters in ``cfg.dtype``, from ``fold_in(key,
    layer)`` alone (as :func:`ray_tpu.models.kimi_k2.init_layer`).  Fan-in
    scaled normals, the up-projections' for the scale their input really has:
    ``W_uq`` and ``W_qI`` divide by the factor on the q latent, ``W_uk`` and
    ``W_uv`` by the one on the kv latent, so that queries, keys and values
    come out at unit scale (a trained model's weights absorb the factors;
    with plain fan-in weights a score's spread is ``sqrt(50)`` units and the
    values' share of the residual stream three times Kimi-K2's, which makes
    bfloat16 rounding a fifth of a logit).  ``W_uq`` then times
    ``SCORE_SPREAD``: a score's standard deviation over random positions is
    3, so the softmax is NOT flat at 8,000 positions (the largest of 2,048
    scores holds a fifth of the mass) and what the selection leaves out
    shows."""
    D = cfg.d_model
    window = cfg.layer_types[layer] == WINDOW
    z = cfg.sizes(window)
    H, rq, rkv, nope, pe, dv = (z[k] for k in ("heads", "rq", "rkv", "nope", "pe", "dv"))
    r_q, r_kv = z["rescale"]
    keys = iter(jax.random.split(jax.random.fold_in(key, layer), 32))

    def w(*shape, fan_in, scale=1.0):  # fan-in scaled normal, made in cfg.dtype
        return (jax.random.normal(next(keys), shape, cfg.dtype)
                * jnp.asarray(scale * fan_in ** -0.5, cfg.dtype))

    def scale_near_one(n):  # learned norm scales: not all ones, so they count
        return (1.0 + 0.1 * jax.random.normal(next(keys), (n,))).astype(cfg.dtype)

    p = {
        "attn_norm": scale_near_one(D), "ffn_norm": scale_near_one(D),
        "w_dq": w(D, rq, fan_in=D), "q_norm": scale_near_one(rq),
        "w_uq": w(rq, H * (nope + pe), fan_in=rq, scale=SCORE_SPREAD / r_q),
        "w_dkv": w(D, rkv + pe, fan_in=D), "kv_norm": scale_near_one(rkv),
        # kv_b_proj's two halves, a head at a time
        "w_uk": w(H, nope, rkv, fan_in=rkv, scale=1.0 / r_kv),
        "w_uv": w(H, rkv, dv, fan_in=rkv, scale=1.0 / r_kv),
        "w_g": w(D, H, fan_in=D),
        "wo": w(H * dv, D, fan_in=H * dv, scale=0.5),
    }
    if not window:
        Hi, di = cfg.index_n_heads, cfg.index_head_dim
        p.update(
            w_qi=w(rq, Hi * di, fan_in=rq, scale=1.0 / r_q),
            w_ki=w(D, di, fan_in=D),
            ki_norm=scale_near_one(di),
            ki_norm_bias=(0.1 * jax.random.normal(next(keys), (di,))).astype(cfg.dtype),
            w_wi=w(D, Hi, fan_in=D))
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


def init(cfg: Dots3NoteConfig, key: jax.Array) -> Dict[str, Any]:
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


def kv_heads(cfg: Dots3NoteConfig) -> int:
    """Heads a cache holds for a position: the one latent row."""
    return 1


def _index(h, c_q, p, cfg: Dots3NoteConfig, positions):
    """A full layer's indexer inputs -> ``(index queries [B, Hi, T, di], head
    weights [B, T, Hi], index keys [B, 1, T, di])``: rotary on the first
    ``qk_rope_head_dim`` values of queries and keys."""
    B, T, _ = h.shape
    Hi, di, pe = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    rotate = lambda t: jnp.concatenate(  # noqa: E731
        [rope(t[..., :pe], positions, base=cfg.rope_base), t[..., pe:]], axis=-1)
    qi = dense(c_q, p["w_qi"]).reshape(B, T, Hi, di).transpose(0, 2, 1, 3)
    ki = layernorm(dense(h, p["w_ki"]), p["ki_norm"], p["ki_norm_bias"],
                   eps=cfg.rms_eps)[:, None]
    return rotate(qi), dense(h, p["w_wi"]), rotate(ki)


def block(x, p, cfg: Dots3NoteConfig, attend=None, positions=None,
          mesh: Optional[Mesh] = None, *, window: int = 0, valid=None,
          absorbed: bool = False, context=None):
    """One layer.  x: [B, T, D] in cfg.dtype; ``window``: the layer's kind (0:
    a full layer; else a sliding one); rotary at ``positions`` ([T] or [B,
    T]; None: 0..T-1); whether its FFN is dense or sparse shows in its
    parameters.  ``attend(q, k, v, row, index)``: the attention middle, in
    one of the two forms of :func:`ray_tpu.models.kimi_k2.block` (``absorbed``:
    ``q [B, H, T, row]`` against the latent rows themselves).  ``row [B, 1, T,
    576 | 1088]`` is what a cache holds for the positions; ``index`` is None
    on a sliding layer and on a full one ``(index queries [B, Hi, T, di], head
    weights [B, T, Hi], index keys [B, 1, T, di])``, the keys being what a
    cache holds BESIDE the row: the middle selects the positions a query
    attends from them.  ``valid`` ([B, T] or [B, 1] bool; None: all): the real
    tokens, the only ones an expert sees.  ``context``:
    :func:`ray_tpu.models.kimi_k2.latent_projections`' (a prompt's part: the
    keys and values cover what the cache holds before it).  Returns ``(x,
    routed, carried)``; ``routed`` is None for a dense layer."""
    B, T, D = x.shape
    z = cfg.sizes(bool(window))
    positions = jnp.arange(T) if positions is None else positions
    norm = partial(rmsnorm, eps=cfg.rms_eps)
    if attend is None:
        def attend(q, k, v, row, index):
            if index is None:
                return _attend(q, k, v, causal=True, mesh=mesh, window=window,
                               scale=z["scale"])
            return selected_attention(
                q, k, v, index, cfg.index_topk, scale=z["scale"]), None

    h = norm(x, p["attn_norm"])
    with jax.named_scope("attention.mla_proj"):
        q, k, v, row, c_q = latent_projections(
            h, p, heads=z["heads"], nope=z["nope"], norm=norm, absorbed=absorbed,
            rope=lambda t: rope(t, positions, base=z["rope_base"]),
            rescale=z["rescale"], context=context)
        index = None if window else _index(h, c_q, p, cfg, positions)
        gate = jax.nn.sigmoid(dense(h, p["w_g"]).astype(jnp.float32))
    with jax.named_scope(
            "attention.latent_window" if window else "attention.latent_sparse"):
        o, carried = attend(q, k, v, row, index)
    with jax.named_scope("attention.mla_proj"):
        if absorbed:
            o = jnp.einsum("bhtc,hcv->bhtv", o, p["w_uv"].astype(x.dtype))
        o = o.transpose(0, 2, 1, 3) * gate[..., None].astype(o.dtype)
        x = x + dense(o.reshape(B, T, -1), p["wo"])

    h = norm(x, p["ffn_norm"])
    if "router" in p:
        y, routed = _sparse_ffn(h, p, cfg, valid)
    else:
        with jax.named_scope("dense_ffn"):
            y, routed = _swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), None
    return x + y, routed, carried


def embed(params: Dict[str, Any], tokens: jax.Array, cfg: Dots3NoteConfig,
          positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, T] -> x [B, T, D] in cfg.dtype (``positions`` is not used:
    this family's positions are the rotary on the shared keys)."""
    return params["tok_emb"][tokens].astype(cfg.dtype)


def unembed(params: Dict[str, Any], x: jax.Array, cfg: Dots3NoteConfig) -> jax.Array:
    """Final norm and the output matrix (its own, untied, over the slice of
    the vocabulary held): x [B, T, D] -> logits [B, T, V] f32."""
    x = rmsnorm(x, params["final_norm"], eps=cfg.rms_eps)
    return dense(x, params["head"]).astype(jnp.float32)


def apply(params: Dict[str, Any], tokens: jax.Array, cfg: Dots3NoteConfig,
          *, absorbed: bool = False) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, V] f32: the whole forward, no
    cache (the tests hold prefill and decode to it, and the two forms of the
    attention to each other), over :func:`init`'s tree or the served one
    (:func:`ray_tpu.models.exaone_moe.serving_layout`)."""
    params = serving_layout(jax.tree.map(lambda a: a, params))
    x = embed(params, tokens, cfg)
    for p, window in zip(params["layers"], cfg.sliding_windows):
        x, _, _ = block(x, p, cfg, window=window, absorbed=absorbed)
    return unembed(params, x, cfg)


def num_params(params: Dict[str, Any]) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
