"""Granite-4.0-H-family decoder LM (``model_type: granitemoehybrid``): Mamba-2
state-space layers with a full-attention layer among them, and on EVERY layer
softmax-over-top-k routed experts beside a shared MLP.  Pure jax, serving path
(``generate.FAMILIES``).

Why this is a module of its own: most layers cache nothing per POSITION.  A
Mamba layer's request carries a state ``H [heads, head values, state]`` in
float32 and the last ``d_conv - 1`` inputs of its convolution, overwritten
every token, the same size at position 10 and at position 100,000
(``cfg.state_cache``; :func:`ray_tpu.models.generate.init_cache`'s fourth
kind); only the attention layers (4 of 40 as published) keep K and V.  And a
run of Mamba layers is the same body over stacked parameters: the Mamba
layers' parameters are ONE stack (``params["mamba"]``, leaves ``[Mamba layers,
...]``; the attention layers a list, ``params["attention"]``) and the layer
loops of :mod:`ray_tpu.models.generate` roll each run (``cfg.layer_runs``), so
the program holds a body a run and not a body a layer.

Layer equations (``n`` the RMSNorm with a learned scale, eps ``rms_eps``):

- model: ``h0 = embed[ids] * embedding_multiplier``; after the last layer
  ``n``; ``logits = h embed^T / logits_scaling`` (the head is the embedding).
- layer: ``h += r * mixer(n(h))``; ``h += r * (experts(n(h)) + shared(n(h)))``
  with ``r = residual_multiplier``; ``layer_types[l]`` names the mixer.
- Mamba-2 mixer: ``[z | xBC | dt] = x W_in`` (``d_inner | d_inner + 2 N |
  heads``); ``xBC_t = silu(b_c + sum_k w_c[:, k] xBC_{t - 3 + k})`` (zeros
  before the prompt); ``xBC -> X [heads, P], B [N], C [N]``; ``dt = softplus(dt
  + dt_bias)``, ``A = -exp(A_log)`` a head; ``H_t = exp(dt_t A) H_{t-1} + dt_t
  X_t (outer) B_t``; ``Y_t = H_t C_t + D X_t``; ``y = n(Y * silu(z))`` over all
  ``d_inner`` values (the gate BEFORE the norm); ``out = y W_out``.  A prompt
  runs the chunked form (:func:`ray_tpu.ops.ssm.ssd_scan`), a decode step the
  recurrence itself (:func:`ray_tpu.ops.ssm.state_update`).
- attention mixer: grouped-query, no bias, NO position encoding, causal,
  scores scaled by ``attention_multiplier`` (not ``head_dim ** -0.5``).
- experts: ``logits = x W_r`` in float32; the top ``k`` logits are chosen and
  the gates are their softmax; expert ``e`` is ``W_down,e (silu(W_gate,e x) *
  W_up,e x)``; the shared MLP is the same form for every token.  This chip
  holds ``experts_held``; what the absent experts would add is left out.

Departures from the published model: :func:`init` makes an expert's fused
input projection (``[gate | up]``) as its two halves, ``ew_gate`` and
``ew_up`` (the leaves the reference reads); an engine serves from the
published layout, ONE leaf ``ew_gate_up`` (:func:`serving_layout`, laid out
once at load: what :func:`block` reads and
:func:`ray_tpu.ops.moe.held_experts_ffn` takes); ``mamba_n_groups`` is 1 as
published and no group axis is built.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models.exaone_moe import _swiglu
from ray_tpu.models.transformer import _attend
from ray_tpu.ops import ssm
from ray_tpu.ops.layers import dense, rmsnorm
from ray_tpu.ops.moe import (
    gate_up_side_by_side, held_experts_ffn, route_softmax_top_k)

__all__ = [
    "GraniteHybridConfig", "init", "init_layer", "serving_layout", "apply",
    "block", "embed", "unembed", "kv_heads", "num_params", "mamba_whole",
    "mamba_step",
]

MAMBA, ATTENTION = "mamba", "attention"
# what ``sliding_windows`` says of a layer that attends NOTHING: its mixer
# carries a per-request state (ray_tpu.models.generate.RECURRENT)
RECURRENT = -1


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100_352
    n_layers: int = 40
    d_model: int = 4096
    n_heads: int = 32             # the attention layers'
    n_kv_heads: int = 8
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64      # d_inner = heads x head_dim = 2 x d_model
    mamba_state: int = 128
    mamba_conv: int = 4
    mamba_chunk: int = 256
    d_expert: int = 768
    d_shared: int = 1536
    n_experts: int = 72           # the router's width, whatever is held here
    experts_per_token: int = 10
    # (first, count): the block of experts this chip holds of each layer;
    # None: all of them
    experts_held: Optional[tuple] = None
    # per layer, as published; longer lists are read up to n_layers.  Left
    # empty: attention at 5, 15, 25, ... (one period is 10 layers)
    layer_types: tuple = ()
    embedding_multiplier: float = 12.0
    logits_scaling: float = 16.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    rms_eps: float = 1e-5
    max_seq_len: int = 131_072
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L = self.n_layers
        kinds = tuple(self.layer_types)[:L] or tuple(
            ATTENTION if l % 10 == 5 else MAMBA for l in range(L))
        held = tuple(self.experts_held or (0, self.n_experts))
        assert len(kinds) == L and set(kinds) <= {MAMBA, ATTENTION}, kinds
        assert 0 <= held[0] and held[0] + held[1] <= self.n_experts, held
        # a frozen dataclass that jit closes over has to hash: tuples
        object.__setattr__(self, "layer_types", kinds)
        object.__setattr__(self, "experts_held", held)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        """Values the convolution runs over: ``X | B | C``."""
        return self.d_inner + 2 * self.mamba_state

    @property
    def sliding_windows(self) -> tuple:
        """Per layer, the positions it attends (what
        :func:`ray_tpu.models.generate.layer_windows` reads): 0 every one (an
        attention layer), ``RECURRENT`` none (a Mamba layer)."""
        return tuple(0 if t == ATTENTION else RECURRENT for t in self.layer_types)

    @property
    def attention_scale(self) -> float:
        return self.attention_multiplier

    @property
    def state_cache(self) -> dict:
        """What a slot holds a Mamba layer, position-free
        (:func:`ray_tpu.models.generate.init_cache`): the recurrent state in
        float32 (a bfloat16 state is another configuration, not a speed-up),
        laid out ``[tiles, state, heads a tile x head values]``
        (:func:`ray_tpu.ops.ssm.pack_state`: two heads a 128-lane tile as
        published), and the convolution's last inputs."""
        g = ssm.heads_per_tile(self.mamba_heads, self.mamba_head_dim)
        return {"ssm": ((self.mamba_heads // g, self.mamba_state,
                         g * self.mamba_head_dim), jnp.float32),
                "conv": ((self.mamba_conv - 1, self.conv_width), self.dtype)}

    @property
    def layer_runs(self) -> tuple:
        """``(kind, first layer, layers, first of its kind)`` a run of layers
        of one kind: what the layer loops roll."""
        runs, seen = [], {MAMBA: 0, ATTENTION: 0}
        for l, kind in enumerate(self.layer_types):
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, l, 1, seen[kind]])
            seen[kind] += 1
        return tuple(tuple(r) for r in runs)

    @staticmethod
    def h_small(**kw) -> "GraniteHybridConfig":
        return GraniteHybridConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        base = dict(vocab_size=256, n_layers=5, d_model=32, n_heads=4,
                    n_kv_heads=2, head_dim=16, mamba_heads=8, mamba_head_dim=8,
                    mamba_state=16, mamba_chunk=8, d_expert=24, d_shared=48,
                    n_experts=16, experts_per_token=4,
                    layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA, MAMBA),
                    embedding_multiplier=3.0, logits_scaling=2.0,
                    residual_multiplier=0.5, attention_multiplier=0.125,
                    max_seq_len=512)
        base.update(kw)
        return GraniteHybridConfig(**base)


# the family table (ray_tpu.models.generate.FAMILIES) reads these two: the
# config class, and the presets ``size`` names
Config = GraniteHybridConfig
SIZES = {"4.0-h-small": GraniteHybridConfig.h_small,
         "tiny": GraniteHybridConfig.tiny}


def init_layer(cfg: GraniteHybridConfig, key: jax.Array, layer,
               kind: Optional[str] = None) -> Dict[str, Any]:
    """Layer ``layer``'s parameters in ``cfg.dtype``, from ``fold_in(key,
    layer)`` alone (a served model is made a layer at a time and never exists
    in float32; a reference can remake any one layer).  ``kind``: the layer's
    mixer (None: ``cfg.layer_types[layer]``; :func:`init` names it, its
    ``layer`` being traced).  The vectors the config gives no values for
    follow the Mamba-2 convention: ``A_log = log(uniform(1, 16))``,
    ``dt_bias`` the inverse softplus of a step drawn log-uniformly in [0.001,
    0.1], ``D = 1``, which keeps a random-weight state from dying or blowing
    up over thousands of positions."""
    kind = kind or cfg.layer_types[layer]
    D, F, Fs = cfg.d_model, cfg.d_expert, cfg.d_shared
    keys = iter(jax.random.split(jax.random.fold_in(key, layer), 24))

    def w(*shape, fan_in, scale=1.0):  # fan-in scaled normal, made in cfg.dtype
        return (jax.random.normal(next(keys), shape, cfg.dtype)
                * jnp.asarray(scale * fan_in ** -0.5, cfg.dtype))

    def scale_near_one(n):  # learned norm scales: not all ones, so they count
        return (1.0 + 0.1 * jax.random.normal(next(keys), (n,))).astype(cfg.dtype)

    if kind == MAMBA:
        H, di, C = cfg.mamba_heads, cfg.d_inner, cfg.conv_width
        step = jnp.exp(jax.random.uniform(
            next(keys), (H,), minval=jnp.log(0.001), maxval=jnp.log(0.1)))
        p = {
            # [z | xBC | dt], and xBC is [X | B | C]
            "w_in": w(D, di + C + H, fan_in=D),
            "conv_w": w(C, cfg.mamba_conv, fan_in=cfg.mamba_conv),
            "conv_b": (0.1 * jax.random.normal(next(keys), (C,))).astype(cfg.dtype),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(cfg.dtype),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (H,), minval=1.0, maxval=16.0)).astype(cfg.dtype),
            "D": jnp.ones((H,), cfg.dtype),
            "ssm_norm": scale_near_one(di),
            "w_out": w(di, D, fan_in=di),
        }
    else:
        Hq, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        p = {"wq": w(D, Hq * hd, fan_in=D), "wk": w(D, KV * hd, fan_in=D),
             "wv": w(D, KV * hd, fan_in=D), "wo": w(Hq * hd, D, fan_in=Hq * hd)}
    p.update(
        mixer_norm=scale_near_one(D), ffn_norm=scale_near_one(D),
        router=w(D, cfg.n_experts, fan_in=D),  # over ALL experts, no bias
        ew_gate=w(cfg.experts_held[1], D, F, fan_in=D),
        ew_up=w(cfg.experts_held[1], D, F, fan_in=D),
        ew_down=w(cfg.experts_held[1], F, D, fan_in=F),
        sw_gate=w(D, Fs, fan_in=D), sw_up=w(D, Fs, fan_in=D),
        sw_down=w(Fs, D, fan_in=Fs),
    )
    return p


# the embedding's rows are drawn this small so that the TIED head does not
# simply return the input token: ``h0 = 12 e`` stays in the residual stream,
# and ``n(h) . e / 16`` of the input's own row would else stand tens of
# standard deviations above every other logit, whatever the layers computed
EMBED_STD = 2.0 ** -10


def init(cfg: GraniteHybridConfig, key: jax.Array) -> Dict[str, Any]:
    """``{"tok_emb", "final_norm", "mamba", "attention"}``: the Mamba layers
    in order as ONE stack, leaves ``[Mamba layers, ...]`` in ``cfg.dtype``,
    layer ``l``'s slice being :func:`init_layer`'s (made a layer at a time
    inside one ``lax.map``, so the stack is written once and never doubled);
    the attention layers in order as a list."""
    k_emb, k_layers = jax.random.split(key)
    params = {
        "tok_emb": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model),
                                      cfg.dtype)
                    * jnp.asarray(EMBED_STD, cfg.dtype)),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    of = lambda kind: [l for l, t in enumerate(cfg.layer_types) if t == kind]  # noqa: E731
    if of(MAMBA):
        params[MAMBA] = jax.lax.map(
            lambda l: init_layer(cfg, k_layers, l, MAMBA),
            jnp.asarray(of(MAMBA), jnp.int32))
    params[ATTENTION] = [init_layer(cfg, k_layers, l) for l in of(ATTENTION)]
    return params


def serving_layout(params: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`init`'s tree as it is SERVED, what :func:`block` reads: the
    Mamba stack's ``ew_gate`` and ``ew_up`` as ONE leaf ``ew_gate_up`` ``[Mamba
    layers, held, D, 2F]`` and each attention layer's as one ``[held, D, 2F]``
    (:func:`ray_tpu.ops.moe.gate_up_side_by_side`), the stack and then a layer
    at a time, IN PLACE on those dicts, which the caller owns
    (:func:`ray_tpu.models.generate.serving_layout`)."""
    for p in (params.get(MAMBA, {}), *params.get(ATTENTION, ())):
        gate_up_side_by_side(p)
    return params


def kv_heads(cfg: GraniteHybridConfig) -> int:
    """K/V heads a cache holds for a position of an attention layer."""
    return cfg.n_kv_heads


def _ssm_inputs(xbc, dt, p, cfg: GraniteHybridConfig):
    """The convolved ``xBC [..., C]`` and raw ``dt [..., H]`` -> ``(X [..., H,
    P], B, C [..., N], dt, A, D)``, the last three float32."""
    di, N = cfg.d_inner, cfg.mamba_state
    x = xbc[..., :di].reshape(*xbc.shape[:-1], cfg.mamba_heads, cfg.mamba_head_dim)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    return (x, xbc[..., di:di + N], xbc[..., di + N:],
            jax.nn.softplus(f32(dt) + f32(p["dt_bias"])),
            -jnp.exp(f32(p["A_log"])), f32(p["D"]))


def mamba_whole(xbc, dt, p, cfg: GraniteHybridConfig, lengths=None):
    """The Mamba middle over whole rows from a zero state: ``xbc [B, T, C]``
    before its convolution, ``dt [B, T, H]`` raw, ``lengths [B]`` the real
    tokens of right-padded rows (None: all).  A padded position changes
    nothing: its ``dt`` is 0, and the tail kept is of the real inputs.
    Returns ``(y [B, T, d_inner] float32, (state [B, tiles, N, g * P]
    float32 in the cache's layout, tail [B, d_conv - 1, C]))``: what a cache
    keeps of each row."""
    B, T, _ = xbc.shape
    lengths = jnp.full((B,), T, jnp.int32) if lengths is None else lengths
    with jax.named_scope("ssm.conv"):
        x, b, c, dt, a, d = _ssm_inputs(
            ssm.causal_conv(xbc, p["conv_w"], p["conv_b"]), dt, p, cfg)
        tail = ssm.conv_tail(xbc, lengths, cfg.mamba_conv - 1)
    with jax.named_scope("ssm.scan"):
        dt = jnp.where((jnp.arange(T)[None, :] < lengths[:, None])[..., None],
                       dt, 0.0)
        y, state = ssm.ssd_scan(x, dt, a, b, c, chunk=cfg.mamba_chunk)
        y = y + d[:, None] * x.astype(jnp.float32)
    g = ssm.heads_per_tile(cfg.mamba_heads, cfg.mamba_head_dim)
    return y.reshape(B, T, cfg.d_inner), (ssm.pack_state(state, g), tail)


def mamba_step(xbc, dt, p, cfg: GraniteHybridConfig, tail, update):
    """The Mamba middle of ONE token a row: ``xbc [B, 1, C]``, ``dt [B, 1,
    H]``, ``tail [d_conv - 1, B, C]`` the row's last inputs as a cache holds
    them, ``update(decay [B, H], dtx [B, H, P], b, c [B, N]) -> y [B, H, P]``
    the state's step, in place, by whoever holds the state
    (:func:`ray_tpu.ops.ssm.state_update`).  Returns ``(y [B, 1, d_inner]
    float32, the tail with this input in)``."""
    with jax.named_scope("ssm.conv"):
        last = jnp.concatenate([tail, xbc[:, 0][None].astype(tail.dtype)])
        f32 = last.astype(jnp.float32)
        conv = p["conv_b"].astype(jnp.float32) + sum(
            p["conv_w"][:, k].astype(jnp.float32) * f32[k]
            for k in range(cfg.mamba_conv))
        x, b, c, dt, a, d = _ssm_inputs(
            jax.nn.silu(conv).astype(xbc.dtype), dt[:, 0], p, cfg)
        xf = x.astype(jnp.float32)
    y = update(jnp.exp(dt * a), dt[..., None] * xf,
               b.astype(jnp.float32), c.astype(jnp.float32))
    y = y + d[:, None] * xf
    return y.reshape(-1, 1, cfg.d_inner), last[1:]


def _sparse_ffn(h, p, cfg: GraniteHybridConfig, valid):
    """The expert layer as this chip holds it, beside the shared MLP ->
    ``(y, routed)``; ``routed`` counts, of the valid tokens, those each held
    expert got, how many of the held experts got any, and the valid tokens
    themselves (``rows``: the rows whose state a decode step has to move)."""
    B, T, D = h.shape
    flat = h.reshape(B * T, D)
    valid = (jnp.ones((B * T,), bool) if valid is None
             else jnp.broadcast_to(valid, (B, T)).reshape(B * T))
    with jax.named_scope("moe.router"):
        experts, gates = route_softmax_top_k(
            flat, p["router"], cfg.experts_per_token)
    with jax.named_scope("moe.expert_ffn"):
        y, tokens = held_experts_ffn(
            flat, experts, gates, p["ew_gate_up"], p["ew_down"],
            first_expert=cfg.experts_held[0], valid=valid, layer=p.get("layer"))
    with jax.named_scope("moe.shared_ffn"):
        shared = _swiglu(h, p["sw_gate"], p["sw_up"], p["sw_down"])
    y = y.reshape(B, T, D) + shared.astype(jnp.float32)
    return y, {"tokens": tokens, "touched": (tokens > 0).sum().astype(jnp.int32),
               "rows": valid.sum().astype(jnp.int32)}


def block(x, p, cfg: GraniteHybridConfig, mix=None, positions=None,
          mesh: Optional[Mesh] = None, *, kind: str, valid=None):
    """One layer.  x: [B, T, D] in cfg.dtype; ``kind``: its mixer.  ``mix`` is
    the mixer's middle, what differs between a whole sequence, a prefill and
    a decode step: for an attention layer ``mix(q, k, v)`` in the KV-head
    layout a cache stores (:mod:`ray_tpu.models.transformer`; there is no
    position encoding, so ``positions`` is not used); for a Mamba layer
    ``mix(xbc, dt, p)`` (None: :func:`mamba_whole` from a zero state).
    ``valid`` ([B, T] or [B, 1] bool; None: all): the real tokens, the only
    ones an expert sees.  Returns ``(x, routed, carried)``."""
    B, T, D = x.shape
    norm = partial(rmsnorm, eps=cfg.rms_eps)
    residual = lambda x, y: (  # noqa: E731 — in float32: 0.22 is no bf16 value
        x.astype(jnp.float32) + cfg.residual_multiplier * y.astype(jnp.float32)
    ).astype(x.dtype)

    h = norm(x, p["mixer_norm"])
    if kind == MAMBA:
        di, C = cfg.d_inner, cfg.conv_width
        mix = mix or partial(mamba_whole, cfg=cfg)
        with jax.named_scope("ssm.in_proj"):
            proj = dense(h, p["w_in"])
            z, xbc, dt = proj[..., :di], proj[..., di:di + C], proj[..., di + C:]
        y, carried = mix(xbc, dt, p)
        with jax.named_scope("ssm.out_proj"):
            gated = y * jax.nn.silu(z.astype(jnp.float32))
            out = dense(norm(gated, p["ssm_norm"]).astype(x.dtype), p["w_out"])
    else:
        Hq, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        mix = mix or partial(_attend, causal=True, mesh=mesh,
                             scale=cfg.attention_scale)
        q = dense(h, p["wq"]).reshape(B, T, Hq, hd)
        k = dense(h, p["wk"]).reshape(B, T, KV, hd)
        v = dense(h, p["wv"]).reshape(B, T, KV, hd)
        with jax.named_scope("attention.full"):
            o, carried = mix(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)))
        out = dense(o.transpose(0, 2, 1, 3).reshape(B, T, Hq * hd), p["wo"])
    x = residual(x, out)
    y, routed = _sparse_ffn(norm(x, p["ffn_norm"]), p, cfg, valid)
    return residual(x, y), routed, carried


def embed(params: Dict[str, Any], tokens: jax.Array, cfg: GraniteHybridConfig,
          positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, T] -> x [B, T, D] in cfg.dtype, times the embedding
    multiplier (``positions`` is not used: this family encodes none)."""
    return (params["tok_emb"][tokens].astype(jnp.float32)
            * cfg.embedding_multiplier).astype(cfg.dtype)


def unembed(params: Dict[str, Any], x: jax.Array, cfg: GraniteHybridConfig) -> jax.Array:
    """Final norm and the TIED head (the embedding's rows held here) over the
    logits' scaling: x [B, T, D] -> logits [B, T, V] f32."""
    x = rmsnorm(x, params["final_norm"], eps=cfg.rms_eps)
    return jnp.einsum("btd,vd->btv", x, params["tok_emb"].astype(x.dtype),
                      preferred_element_type=jnp.float32) / cfg.logits_scaling


# the leaves of the Mamba stack that are NOT sliced a layer: they feed the
# grouped-matmul kernel, and a slice feeding a kernel is a copy
_WHOLE = ("ew_gate_up", "ew_down")


def layer_of(stack: Dict[str, Any], at) -> Dict[str, Any]:
    """Layer ``at`` (which may be traced: the rolled loops' index) of the
    Mamba stack: every leaf's slice, but the experts' weights WHOLE beside
    the index (``"layer"``), for :func:`ray_tpu.ops.moe.held_experts_ffn` to
    pick the layer's experts inside the kernel."""
    p = {k: v if k in _WHOLE else jax.lax.dynamic_index_in_dim(
        v, at, 0, keepdims=False) for k, v in stack.items()}
    return {**p, "layer": at}


def layer_params(params: Dict[str, Any], cfg: GraniteHybridConfig, layer: int):
    """Layer ``layer``'s own parameters: a slice of the Mamba stack, or an
    entry of the attention layers' list."""
    kind = cfg.layer_types[layer]
    at = cfg.layer_types[:layer].count(kind)
    if kind == ATTENTION:
        return params[kind][at]
    return jax.tree.map(lambda a: a[at], params[kind])


def apply(params: Dict[str, Any], tokens: jax.Array, cfg: GraniteHybridConfig) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, V] f32: the whole forward, no
    cache, a layer at a time (the tests hold prefill and decode to it), over
    :func:`init`'s tree or the served one."""
    params = serving_layout(jax.tree.map(lambda a: a, params))
    x = embed(params, tokens, cfg)
    for l, kind in enumerate(cfg.layer_types):
        x, _, _ = block(x, layer_params(params, cfg, l), cfg, kind=kind)
    return unembed(params, x, cfg)


def num_params(params: Dict[str, Any]) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
