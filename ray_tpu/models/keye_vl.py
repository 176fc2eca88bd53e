"""Keye-VL-2.0-family decoder LM (``model_type: KeyeVL2``): a vision tower in
front of the text path, rotary positions on three axes, grouped-query
attention whose queries attend a LEARNED SELECTION of the cached positions,
and softmax-routed experts in every layer.  Pure jax, serving path
(``generate.FAMILIES``).

Why this is a module of its own: a request is not token ids alone.  Where a
prompt's tokens hold the video placeholder (``cfg.video_token_id``) the text
model's input rows are the tower's (:mod:`ray_tpu.models.vision`), and a
token's rotary position is ``(t, h, w)``, no longer its place in the cache:
:func:`rope_index` gives a prompt's positions and the offset (``delta``) a slot
decodes at afterwards, which a cache keeps a slot (``rope_delta``).  Its layers
are all alike but keep K and V per head in a slab AND an index key beside
them (``index_cache``; the first family that selects over a ``k``, ``v``
slab), so :mod:`ray_tpu.models.generate` runs them listed, as it runs dots3's.

Layer equations (pre-norm residual blocks, ``n`` RMSNorm with eps 1e-6 and a
learned scale; no bias on a projection):

- ``u = n1(x)``; ``q = W_q u`` as 32 heads of 128, ``k = W_k u``, ``v = W_v
  u`` as 4 heads of 128; RMSNorm over the 128 values of every q and k head
  (``q_norm``, ``k_norm``: the Qwen3 layer's, ASSUMED); M-RoPE on q and k
  (``mrope_section`` [16, 24, 24], base 1e7:
  :func:`ray_tpu.ops.layers.mrope`); scale ``128 ** -0.5``.
- the indexer (DeepSeek-V3.2's, fed from ``u``: this model has no query
  latent; ASSUMED): ``qI = W_qI u`` (16 x 64), ``kI = LayerNorm(W_kI u)`` (64:
  ONE key a position, **cached** beside K and V), ``wI = W_w u`` (16); M-RoPE
  on all 64 values of both with the sections halved ([8, 12, 12]); ``I[t, s] =
  sum_j wI[t, j] relu(qI[t, j] . kI[s]) 64 ** -0.5 16 ** -0.5`` in float32;
  query ``t`` attends the 2,048 cache positions ``s <= t`` of largest ``I``
  (all while ``t < 2,048``; the lower position among equals), one selection
  shared by its 32 heads (:mod:`ray_tpu.ops.dsa`).  Causality and the
  selection are on CACHE positions; only the rotation uses ``(t, h, w)``.
- ``x += W_o o``; ``g = softmax(W_r n2(x))`` over 128 experts in float32, top
  8, renormalised over the 8 (the same gates as the softmax over the chosen
  logits: :func:`ray_tpu.ops.moe.route_softmax_top_k`); ``x += sum_i g_i
  W_down_i(silu(W_gate_i h) * W_up_i h)`` at width 768, no shared expert
  (:func:`ray_tpu.ops.moe.held_experts_ffn` with every expert held, and told
  so: a prefill call's 16,384 pairs go through the two grouped matmuls as ONE
  block and a token's 8 rows are gathered back and summed, no loop over trips
  of 256 rows; a stage handed a SHARE of the experts keeps the trips).
- final RMSNorm, an untied head.

Positions (Qwen2-VL's ``get_rope_index``, ASSUMED): a text token has ``t = h =
w = next``, ``next`` one more than the largest component of any earlier token;
a video of ``F`` frames of merged grid ``gh x gw`` that starts at ``next = s``
gives frame ``f``, row ``r``, column ``c`` ``(s + f, s + r, s + c)`` and leaves
``next = s + max(F, gh, gw)``.  After ``n`` cached positions a slot decodes at
rotary position ``n + delta``, ``delta = next - n <= 0``.

Not built: several videos or images of unequal grids in one request, an audio
tower, multi-token prediction.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ray_tpu.models import vision
from ray_tpu.models.exaone_moe import serving_layout  # noqa: F401 — the hook
from ray_tpu.ops.dsa import selected_attention
from ray_tpu.ops.layers import dense, layernorm, mrope, rmsnorm
from ray_tpu.ops.moe import held_experts_ffn, route_softmax_top_k

__all__ = [
    "KeyeVLConfig", "init", "init_layer", "apply", "block", "embed", "unembed",
    "kv_heads", "num_params", "rope_index", "vision_config", "encode_video",
]

# the init's standard deviation of an attention score over random positions
# (on the q heads' norm scale: QK-norm fixes a score's spread at 1 whatever
# the projections are)
SCORE_SPREAD = 3.0


@dataclasses.dataclass(frozen=True)
class KeyeVLConfig:
    vocab_size: int = 151_936
    n_layers: int = 48
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_base: float = 10_000_000.0
    mrope_section: tuple = (16, 24, 24)
    # sa_config
    index_n_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    d_expert: int = 768
    n_experts: int = 128
    experts_per_token: int = 8
    # (first, count): the block of experts this chip holds; None: all
    experts_held: Optional[tuple] = None
    rms_eps: float = 1e-6
    max_seq_len: int = 262_144
    video_token_id: int = 151_656
    # the tower and the merger (:mod:`ray_tpu.models.vision`)
    vision_layers: int = 27
    vision_d_model: int = 1152
    vision_heads: int = 16
    vision_d_ff: int = 4304
    vision_patch: int = 14
    vision_table: int = 27
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        held = tuple(self.experts_held or (0, self.n_experts))
        assert 0 <= held[0] and held[0] + held[1] <= self.n_experts, held
        assert sum(self.mrope_section) * 2 == self.head_dim, self.mrope_section
        assert all(s % 2 == 0 for s in self.mrope_section) and sum(
            self.mrope_section) == self.index_head_dim, "the index halves them"
        object.__setattr__(self, "experts_held", held)
        object.__setattr__(self, "mrope_section", tuple(self.mrope_section))

    # what :mod:`ray_tpu.models.generate` reads of the layers' caches
    @property
    def index_cache(self) -> tuple:
        """``(values of the index key a layer caches a position beside its k
        and v, the positions a query selects)``."""
        return (self.index_head_dim, self.index_topk)

    # a slot's rotary position is its cached length plus an offset of its own
    rope_delta_cache = True

    @property
    def all_experts_held(self) -> bool:
        """Every expert the router chooses from is on this chip (a pipeline
        stage holds whole layers): the expert layer's dispatch and the
        engine's counter of it both read this
        (:func:`ray_tpu.ops.moe.dispatch_trips`)."""
        return self.experts_held == (0, self.n_experts)

    @staticmethod
    def keye_vl_2_30b(**kw) -> "KeyeVLConfig":
        return KeyeVLConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "KeyeVLConfig":
        base = dict(vocab_size=256, n_layers=3, d_model=32, n_heads=4,
                    n_kv_heads=2, head_dim=16, mrope_section=(2, 4, 2),
                    index_n_heads=2, index_head_dim=8, index_topk=8,
                    d_expert=24, n_experts=8, experts_per_token=2,
                    max_seq_len=512, video_token_id=255, vision_layers=2,
                    vision_d_model=24, vision_heads=2, vision_d_ff=48,
                    vision_patch=2, vision_table=5)
        base.update(kw)
        return KeyeVLConfig(**base)


# the family table (ray_tpu.models.generate.FAMILIES) reads these two
Config = KeyeVLConfig
SIZES = {"2.0-30b-a3b": KeyeVLConfig.keye_vl_2_30b, "tiny": KeyeVLConfig.tiny}


def vision_config(cfg: KeyeVLConfig) -> vision.VisionConfig:
    return vision.VisionConfig(
        layers=cfg.vision_layers, d_model=cfg.vision_d_model,
        heads=cfg.vision_heads, d_ff=cfg.vision_d_ff, patch=cfg.vision_patch,
        table=cfg.vision_table, out_dim=cfg.d_model, eps=cfg.rms_eps,
        dtype=cfg.dtype)


def init_layer(cfg: KeyeVLConfig, key: jax.Array, layer: int) -> Dict[str, Any]:
    """Layer ``layer``'s parameters in ``cfg.dtype``, from ``fold_in(key,
    layer)`` alone (a served model is made a layer at a time: a layer's 128
    experts are 1.2 GB in bfloat16).  Fan-in scaled normals, ``W_o`` and the
    down-projections at half scale, norm scales ``1 + 0.1 N``; the q heads'
    norm scale times ``SCORE_SPREAD``: with QK-norm a score's standard
    deviation over random positions is 1 whatever ``W_q`` and ``W_k`` are, and
    a softmax that flat over 8,000 positions shows nothing of what the
    selection leaves out (PR 44's lesson); at 3 the largest of 2,048 scores
    holds about a fifth of the mass."""
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hi, di = cfg.index_n_heads, cfg.index_head_dim
    E, F = cfg.experts_held[1], cfg.d_expert
    keys = iter(jax.random.split(jax.random.fold_in(key, layer), 24))

    def w(*shape, scale=1.0):
        return (jax.random.normal(next(keys), shape, cfg.dtype)
                * jnp.asarray(scale * shape[-2] ** -0.5, cfg.dtype))

    def near(n, centre=1.0):
        return (centre * (1.0 + 0.1 * jax.random.normal(next(keys), (n,)))
                ).astype(cfg.dtype)

    return {
        "attn_norm": near(D), "ffn_norm": near(D),
        "q_norm": near(hd, SCORE_SPREAD), "k_norm": near(hd),
        "wq": w(D, H * hd), "wk": w(D, KV * hd), "wv": w(D, KV * hd),
        "wo": w(H * hd, D, scale=0.5),
        "w_qi": w(D, Hi * di), "w_ki": w(D, di), "ki_norm": near(di),
        "ki_norm_bias": (0.1 * jax.random.normal(next(keys), (di,))).astype(cfg.dtype),
        "w_wi": w(D, Hi),
        "router": w(D, cfg.n_experts),
        "ew_gate": w(E, D, F), "ew_up": w(E, D, F),
        "ew_down": w(E, F, D, scale=0.5),
    }


def init(cfg: KeyeVLConfig, key: jax.Array) -> Dict[str, Any]:
    """``{"tok_emb", "head", "final_norm", "layers": [one dict a layer],
    "vision": the tower's and the merger's}``, every leaf in ``cfg.dtype``."""
    k_emb, k_head, k_layers, k_vision = jax.random.split(key, 4)
    D, V = cfg.d_model, cfg.vocab_size
    return {
        "tok_emb": jax.random.normal(k_emb, (V, D), cfg.dtype),
        "head": (jax.random.normal(k_head, (D, V), cfg.dtype)
                 * jnp.asarray(D ** -0.5, cfg.dtype)),
        "final_norm": jnp.ones((D,), cfg.dtype),
        "layers": [init_layer(cfg, k_layers, l) for l in range(cfg.n_layers)],
        "vision": vision.init(vision_config(cfg), k_vision),
    }


def kv_heads(cfg: KeyeVLConfig) -> int:
    return cfg.n_kv_heads


def rope_index(n_tokens: int, first: int, grid: Tuple[int, int, int]):
    """A prompt's rotary positions on the host: ``n_tokens`` tokens of which
    ``[first, first + F x gh x gw)`` are ONE video's (``grid``: frames and the
    MERGED grid's rows and columns; ``F == 0``: a text prompt).  Returns
    ``(positions [3, n_tokens] int32, delta)``: the module docstring's rule;
    the slot decodes at rotary position ``cached length + delta``."""
    F, gh, gw = grid
    n_vis = F * gh * gw
    pos = np.empty((3, n_tokens), np.int32)
    pos[:, :first] = np.arange(first)
    if n_vis:
        f, rc = np.divmod(np.arange(n_vis), gh * gw)
        r, c = np.divmod(rc, gw)
        pos[:, first:first + n_vis] = first + np.stack([f, r, c])
    after = first + (max(F, gh, gw) if n_vis else 0)
    rest = n_tokens - first - n_vis
    pos[:, first + n_vis:] = after + np.arange(rest)
    return pos, int(after + rest - n_tokens)


def _halved(sections) -> tuple:
    return tuple(s // 2 for s in sections)


def _index(u, p, cfg: KeyeVLConfig, positions):
    """The indexer's inputs -> ``(index queries [B, Hi, T, di], head weights
    [B, T, Hi], index keys [B, 1, T, di])``, rotated on all their values."""
    B, T, _ = u.shape
    Hi, di = cfg.index_n_heads, cfg.index_head_dim
    rotate = partial(mrope, positions=positions,
                     sections=_halved(cfg.mrope_section), base=cfg.rope_base)
    qi = dense(u, p["w_qi"]).reshape(B, T, Hi, di).transpose(0, 2, 1, 3)
    ki = layernorm(dense(u, p["w_ki"]), p["ki_norm"], p["ki_norm_bias"],
                   eps=cfg.rms_eps)[:, None]
    return rotate(qi), dense(u, p["w_wi"]), rotate(ki)


def block(x, p, cfg: KeyeVLConfig, attend=None, positions=None,
          mesh: Optional[Mesh] = None, *, window: int = 0, valid=None):
    """One layer.  x: [B, T, D] in cfg.dtype; rotary at ``positions`` (``[B,
    3, T]``: a ``(t, h, w)`` a token; ``[T]`` or ``[B, T]``: text, every axis
    the same; None: 0..T-1).  ``attend(q, k, v, None, index)``: the attention
    middle, q ``[B, H, T, dh]`` and k, v in the KV-head layout a cache stores;
    ``index = (index queries, head weights, index keys)``, the keys being what
    a cache holds BESIDE k and v: the middle selects the positions a query
    attends from them.  ``valid``: the real tokens, the only ones an expert
    sees.  Returns ``(x, routed, carried)``."""
    B, T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = jnp.arange(T) if positions is None else positions
    norm = partial(rmsnorm, eps=cfg.rms_eps)
    scale = hd ** -0.5
    if attend is None:
        def attend(q, k, v, row, index):
            return selected_attention(q, k, v, index, cfg.index_topk,
                                      scale=scale), None

    u = norm(x, p["attn_norm"])
    with jax.named_scope("attention.qkv_proj"):
        rotate = partial(mrope, positions=positions,
                         sections=cfg.mrope_section, base=cfg.rope_base)
        q = norm(dense(u, p["wq"]).reshape(B, T, H, hd), p["q_norm"])
        k = norm(dense(u, p["wk"]).reshape(B, T, KV, hd), p["k_norm"])
        v = dense(u, p["wv"]).reshape(B, T, KV, hd)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        q, k = rotate(q), rotate(k)
        index = _index(u, p, cfg, positions)
    with jax.named_scope("attention.gqa_sparse"):
        o, carried = attend(q, k, v, None, index)
    with jax.named_scope("attention.qkv_proj"):
        x = x + dense(o.transpose(0, 2, 1, 3).reshape(B, T, H * hd), p["wo"])

    y, routed = _sparse_ffn(norm(x, p["ffn_norm"]), p, cfg, valid)
    return x + y, routed, carried


def _sparse_ffn(h, p, cfg: KeyeVLConfig, valid):
    """The expert layer (no shared expert) -> ``(y, routed)``; ``routed``
    counts, of the valid tokens, those each held expert got and how many of
    the held experts got any (the distinct experts a decode step touches)."""
    B, T, D = h.shape
    flat = h.reshape(B * T, D)
    if valid is not None:
        valid = jnp.broadcast_to(valid, (B, T)).reshape(B * T)
    with jax.named_scope("moe.router"):
        experts, gates = route_softmax_top_k(
            flat, p["router"], cfg.experts_per_token)
    with jax.named_scope("moe.expert_ffn"):
        y, tokens = held_experts_ffn(
            flat, experts, gates, p["ew_gate_up"], p["ew_down"],
            first_expert=cfg.experts_held[0], valid=valid,
            all_held=cfg.all_experts_held)
    return y.reshape(B, T, D).astype(h.dtype), {
        "tokens": tokens, "touched": (tokens > 0).sum().astype(jnp.int32)}


def embed(params: Dict[str, Any], tokens: jax.Array, cfg: KeyeVLConfig,
          positions: Optional[jax.Array] = None,
          visual: Optional[Dict[str, Any]] = None) -> jax.Array:
    """tokens [B, T] -> x [B, T, D] in cfg.dtype.  ``visual`` (None: token ids
    alone): ``{"rows": a tuple of the tower's results, each [frames, rows a
    frame, D], "index" [B, T] int32}``: where ``index >= 0`` the input is that
    row of ``rows`` (counted through the tuple, frame after frame), which is
    what stands where the tokens hold the video placeholder."""
    x = params["tok_emb"][tokens].astype(cfg.dtype)
    if visual is None:
        return x
    rows = jnp.concatenate([r.reshape(-1, r.shape[-1]) for r in visual["rows"]])
    index = visual["index"]
    return jnp.where((index >= 0)[..., None],
                     rows[jnp.maximum(index, 0)].astype(cfg.dtype), x)


def unembed(params: Dict[str, Any], x: jax.Array, cfg: KeyeVLConfig) -> jax.Array:
    x = rmsnorm(x, params["final_norm"], eps=cfg.rms_eps)
    return dense(x, params["head"]).astype(jnp.float32)


def encode_video(params: Dict[str, Any], cfg: KeyeVLConfig, patches: jax.Array,
                 grid: Tuple[int, int]) -> jax.Array:
    """The tower over ``patches [F, gh' x gw', patch values]`` uint8 of frames
    of ``grid = (gh', gw')`` patches -> ``[F, gh'/2 x gw'/2, D]``."""
    return vision.encode(params["vision"], vision_config(cfg), patches, grid)


def apply(params: Dict[str, Any], tokens: jax.Array, cfg: KeyeVLConfig,
          video=None) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, V] f32: the whole forward, no
    cache (the tests hold prefill and decode to it).  ``video`` (None: text):
    ``(patches [F, gh' x gw', values] uint8, (gh', gw'))``, ONE video, the same
    for every row, standing where a row's tokens hold the placeholder (a run
    of ``F x gh'/2 x gw'/2`` of them)."""
    params = serving_layout(jax.tree.map(lambda a: a, params))
    B, T = tokens.shape
    positions, visual = None, None
    if video is not None:
        patches, (gh, gw) = video
        rows = encode_video(params, cfg, patches, (gh, gw))
        n_vis = rows.shape[0] * rows.shape[1]
        first = jnp.argmax(tokens == cfg.video_token_id, axis=1)
        at = jnp.arange(T)[None, :] - first[:, None]
        # (the id may come again later, as an answer's token: text there)
        is_vis = (at >= 0) & (at < n_vis)
        merged = (rows.shape[0], gh // 2, gw // 2)
        visual = {"rows": (rows,), "index": jnp.where(is_vis, at, -1)}
        f, rc = jnp.divmod(jnp.clip(at, 0, n_vis - 1), merged[1] * merged[2])
        r, c = jnp.divmod(rc, merged[2])
        s = first[:, None]
        after = s + max(merged) + (at - n_vis)
        text = jnp.where(at < 0, jnp.arange(T)[None, :], after)
        positions = jnp.where(
            is_vis[:, None, :], jnp.stack([s + f, s + r, s + c], axis=1),
            text[:, None, :])
    x = embed(params, tokens, cfg, positions, visual)
    for p in params["layers"]:
        x, _, _ = block(x, p, cfg, positions=positions)
    return unembed(params, x, cfg)


def num_params(params: Dict[str, Any]) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
