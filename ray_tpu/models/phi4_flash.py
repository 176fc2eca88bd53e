"""Phi-4-mini-flash-family decoder LM (``model_type: phi4flash``): SambaY, a
decoder-hybrid-decoder.  Pure jax, serving path (``generate.FAMILIES``).

Why this is a module of its own: the stack has TWO halves that cache
differently.  The lower half (layers ``0 .. L/2 - 1``) alternates Mamba-1
state-space layers with window-512 attention layers; layer ``L/2`` is a
Mamba-1 layer whose scan output is also the MEMORY ``m``; layer ``L/2 + 1`` is
the ONE full-attention layer, and its K and V are THE shared cache: every odd
layer above it holds only a query and an output projection and attends that
slab (cross attention, YOCO), every even layer above it is a gated memory
unit that multiplies ``m`` of the SAME position by a gate and caches nothing.
So a prompt position other than the last needs the lower half and layer ``L/2
+ 1``'s K and V only: the prefill stops half way up
(:func:`ray_tpu.models.generate.prefill_at`).  All attention is DIFFERENTIAL:
two softmaxes a head pair, subtracted under a learned scalar.

Layer equations (``n`` LayerNorm with scale and bias, eps ``norm_eps``; no
position encoding anywhere):

- model: ``h = embed[ids]``; the layers; ``logits = n_f(h) embed^T`` (tied).
- layer ``l``: ``h += mixer_l(n1(h))``; ``h += W_down (silu(g) * u)`` with ``[g |
  u] = n2(h) W_gate_up``.
- Mamba-1 (``d_inner = expand x d``, state ``N``, ``dt_rank``): ``[x | z] = u
  W_in``; ``x_t = silu(b_c + sum_k w_c[:, k] x_{t-3+k})``; ``[r | B | C] = x
  W_x``; ``dt = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; ``H_t = exp(dt_t
  A) H_{t-1} + dt_t x_t (outer) B_t``; ``y_t = H_t C_t + D x_t``; ``out = (y
  silu(z)) W_out`` (:func:`ray_tpu.ops.ssm.selective_scan` for a prompt or a
  part, :func:`ray_tpu.ops.ssm.selective_state_update` for a decode step).
- GMU: ``out = (silu(u W_1) * m_t) W_2``.
- differential attention: ``q = u W_q + b_q`` (``H`` heads of ``dh``); where
  the layer owns K and V, ``k, v = u W_k + b_k, u W_v + b_v`` (``KV`` heads).
  Query pair ``p`` (heads ``2p, 2p + 1``) uses K/V pair ``i = p // 2`` (heads
  ``2i, 2i + 1``): ``a1 = softmax(q_{2p} k_{2i}^T s) [v_{2i} | v_{2i+1}]``, ``a2
  = softmax(q_{2p+1} k_{2i+1}^T s) [v_{2i} | v_{2i+1}]``, ``s = dh ** -0.5``;
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init =
  0.8 - 0.6 exp(-0.3 l)``; ``o_p = rmsnorm(a1 - lambda a2; gamma) (1 -
  lambda_init)``; the pairs' ``2 dh`` values side by side feed ``W_o`` (+
  ``b_o``).

**How a pair is read through the ordinary kernels.**  A cache position holds
``KV / 2`` PAIR heads of ``2 dh`` values: ``K_i = [k_{2i} | k_{2i+1}]``, ``V_i =
[v_{2i} | v_{2i+1}]`` (a plain reshape of the projections).  A query head is
padded to ``2 dh`` with zeros on the half it does not use (``[q | 0]`` for an
even head, ``[0 | q]`` for an odd one), so that ``q . K_i`` IS ``q . k_{2i}`` or
``q . k_{2i+1}`` and the values are the pair's ``2 dh`` wide: grouped-query
attention with ``H`` query heads over ``KV / 2`` cached heads of ``2 dh``, which
is what the flash prefill pair, the ring read and the ragged decode kernel
already compute.  The subtraction and the norm (:func:`diff_combine`) follow.

Departures from the published model: the residual stream is ``cfg.dtype``
everywhere (the published code keeps it in float32 after a Mamba layer);
``A_log`` is stored ``[N, d_inner]`` (the transpose of the published layout:
channels on the lanes); the MLP's fused ``[gate | up]`` is one matrix as
published.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.granite_hybrid import RECURRENT
from ray_tpu.models.transformer import _attend
from ray_tpu.ops import ssm
from ray_tpu.ops.layers import dense, layernorm

__all__ = [
    "Phi4FlashConfig", "init", "apply", "embed", "unembed", "kv_heads",
    "num_params", "lower_stack", "shared_kv", "shared_layer", "upper_stack",
    "diff_combine",
]

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
# what ``sliding_windows`` says of a layer that caches NOTHING and mixes no
# positions (ray_tpu.models.generate.UNCACHED), and of one that reads the slab
# layer ``k`` owns (``READS - k``; ray_tpu.models.generate.reads_layer)
UNCACHED = -2
READS = -3


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200_064
    n_layers: int = 32
    d_model: int = 2560
    n_heads: int = 40
    n_kv_heads: int = 20
    d_ff: int = 10_240
    sliding_window: int = 512
    mb_per_layer: int = 2         # one Mamba layer a period of this many
    mamba_state: int = 16
    mamba_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0        # 0: ceil(d_model / 16)
    norm_eps: float = 1e-5
    max_seq_len: int = 262_144
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert self.n_layers % 4 == 0 and self.mb_per_layer == 2, self.n_layers
        assert self.n_heads % 4 == 0 and self.n_kv_heads * 2 == self.n_heads, (
            self.n_heads, self.n_kv_heads)
        assert self.d_model % self.n_heads == 0
        if not self.mamba_dt_rank:
            object.__setattr__(self, "mamba_dt_rank", -(-self.d_model // 16))

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def cache_head_dim(self) -> int:
        """Values a cached head holds a position: a PAIR of K (or V) heads
        side by side (module docstring)."""
        return 2 * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def memory_layer(self) -> int:
        """The Mamba layer whose scan output the gated memory units read."""
        return self.n_layers // 2

    @property
    def shared_layer(self) -> int:
        """The one full-attention layer: its K and V are the shared cache."""
        return self.n_layers // 2 + 1

    @property
    def layer_types(self) -> tuple:
        def kind(l):
            if l <= self.memory_layer:
                return MAMBA if l % 2 == 0 else WINDOW
            if l == self.shared_layer:
                return FULL
            return GMU if l % 2 == 0 else CROSS

        return tuple(kind(l) for l in range(self.n_layers))

    @property
    def sliding_windows(self) -> tuple:
        """Per layer, what it attends (what
        :func:`ray_tpu.models.generate.layer_windows` reads): ``RECURRENT`` a
        Mamba layer, the window a window layer, 0 the full layer, ``UNCACHED``
        a gated memory unit, ``READS - k`` a layer that reads layer ``k``'s
        slab."""
        of = {MAMBA: RECURRENT, WINDOW: self.sliding_window, FULL: 0,
              GMU: UNCACHED, CROSS: READS - self.shared_layer}
        return tuple(of[t] for t in self.layer_types)

    @property
    def attention_scale(self) -> float:
        return self.head_dim ** -0.5

    window_attention_scale = attention_scale

    @property
    def state_cache(self) -> dict:
        """What a slot holds a Mamba layer, position-free
        (:func:`ray_tpu.models.generate.init_cache`): the state in float32 as
        ``[N, rows, 128]`` (:func:`ray_tpu.ops.ssm.channel_tiles`: channels on
        the lanes), and the convolution's last inputs."""
        return {"ssm": ((self.mamba_state, *ssm.channel_tiles(self.d_inner)),
                        jnp.float32),
                "conv": ((self.mamba_conv - 1, self.d_inner), self.dtype)}

    # the state after a prompt's part is the state a later part starts from
    # (ray_tpu.models.generate.can_continue)
    state_carried_in = True
    # a decode step reads the window layers' rings a live slot's tiles at a
    # time (ray_tpu.models.generate.ring_read_by_tile)
    window_rings_by_tile = True

    @staticmethod
    def mini_flash(**kw) -> "Phi4FlashConfig":
        return Phi4FlashConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "Phi4FlashConfig":
        base = dict(vocab_size=256, n_layers=8, d_model=32, n_heads=4,
                    n_kv_heads=2, d_ff=64, sliding_window=8, mamba_state=4,
                    max_seq_len=512)
        base.update(kw)
        return Phi4FlashConfig(**base)


# the family table (ray_tpu.models.generate.FAMILIES) reads these two
Config = Phi4FlashConfig
SIZES = {"mini-flash": Phi4FlashConfig.mini_flash, "tiny": Phi4FlashConfig.tiny}

# the stacks of layers alike, in layer order, and the one layer of its kind
STACKS = (MAMBA, WINDOW, GMU, CROSS)
# the embedding's rows are drawn this small so that the TIED head does not
# simply return the input token (granite_hybrid.EMBED_STD: the same reason)
EMBED_STD = 2.0 ** -6
# the spread of an attention score: both softmaxes of a pair near uniform
# would leave ``a1 - lambda a2`` a difference of two near-equal means, which
# the norm after it blows up together with its bfloat16 rounding (PERF.md
# section 6, PR 44's lesson for a norm after a subtraction)
SCORE_STD = 3.0


def kinds_of(cfg: Phi4FlashConfig, kind: str) -> list:
    return [l for l, t in enumerate(cfg.layer_types) if t == kind]


def init_layer(cfg: Phi4FlashConfig, key: jax.Array, layer, kind: str) -> Dict[str, Any]:
    """Layer ``layer``'s parameters in ``cfg.dtype``, from ``fold_in(key,
    layer)`` alone (the model is made a layer at a time and never exists in
    float32).  Mamba's vectors as the Mamba paper initialises them: ``A = -(1
    .. N)`` a channel, ``dt``'s bias the inverse softplus of a step drawn
    log-uniformly in [0.001, 0.1], ``D = 1``."""
    D, F, di, N, R = (cfg.d_model, cfg.d_ff, cfg.d_inner, cfg.mamba_state,
                      cfg.mamba_dt_rank)
    keys = iter(jax.random.split(jax.random.fold_in(key, layer), 24))
    out_scale = (2 * cfg.n_layers) ** -0.5  # the stream keeps unit size

    def w(*shape, fan_in, scale=1.0):  # fan-in scaled normal, made in cfg.dtype
        return (jax.random.normal(next(keys), shape, cfg.dtype)
                * jnp.asarray(scale * fan_in ** -0.5, cfg.dtype))

    def near(n, mean=1.0, std=0.1):  # learned scales and biases that count
        return (mean + std * jax.random.normal(next(keys), (n,))).astype(cfg.dtype)

    p = {"n1_w": near(D), "n1_b": near(D, 0.0), "n2_w": near(D),
         "n2_b": near(D, 0.0), "w_gate_up": w(D, 2 * F, fan_in=D),
         "w_down": w(F, D, fan_in=F, scale=out_scale)}
    if kind == MAMBA:
        step = jnp.exp(jax.random.uniform(
            next(keys), (di,), minval=math.log(0.001), maxval=math.log(0.1)))
        p.update(
            w_in=w(D, 2 * di, fan_in=D),  # [x | z]
            conv_w=w(di, cfg.mamba_conv, fan_in=cfg.mamba_conv),
            conv_b=near(di, 0.0),
            w_x=w(di, R + 2 * N, fan_in=di),  # [r | B | C]
            w_dt=w(R, di, fan_in=R),
            b_dt=(step + jnp.log(-jnp.expm1(-step))).astype(cfg.dtype),
            A_log=jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=jnp.float32))[:, None], (N, di)).astype(cfg.dtype),
            D=jnp.ones((di,), cfg.dtype),
            w_out=w(di, D, fan_in=di, scale=out_scale))
    elif kind == GMU:
        p.update(w1=w(D, di, fan_in=D), w2=w(di, D, fan_in=di, scale=out_scale))
    else:
        dh = cfg.head_dim
        spread = SCORE_STD ** 0.5
        p.update(wq=w(D, D, fan_in=D, scale=spread), bq=near(D, 0.0),
                 wo=w(D, D, fan_in=D, scale=out_scale), bo=near(D, 0.0),
                 lq1=near(dh, 0.0), lk1=near(dh, 0.0), lq2=near(dh, 0.0),
                 lk2=near(dh, 0.0), subln=near(2 * dh))
        if kind != CROSS:
            kv = cfg.n_kv_heads * dh
            p.update(wk=w(D, kv, fan_in=D, scale=spread), bk=near(kv, 0.0),
                     wv=w(D, kv, fan_in=D), bv=near(kv, 0.0))
    return p


def init(cfg: Phi4FlashConfig, key: jax.Array) -> Dict[str, Any]:
    """``{"tok_emb", "final_norm_w", "final_norm_b", "mamba", "window", "full",
    "gmu", "cross"}``: the layers of a kind in order as ONE stack, leaves
    ``[layers of the kind, ...]`` in ``cfg.dtype`` (made a layer at a time
    inside one ``lax.map``, so the stack is written once and never doubled);
    the one full layer by itself."""
    k_emb, k_layers = jax.random.split(key)
    params = {
        "tok_emb": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model),
                                      cfg.dtype)
                    * jnp.asarray(EMBED_STD, cfg.dtype)),
        "final_norm_w": jnp.ones((cfg.d_model,), cfg.dtype),
        "final_norm_b": jnp.zeros((cfg.d_model,), cfg.dtype),
        FULL: init_layer(cfg, k_layers, cfg.shared_layer, FULL),
    }
    for kind in STACKS:
        params[kind] = lax.map(
            lambda l, kind=kind: init_layer(cfg, k_layers, l, kind),
            jnp.asarray(kinds_of(cfg, kind), jnp.int32))
    return params


def kv_heads(cfg: Phi4FlashConfig) -> int:
    """Heads a cache holds a position of the window layers and of the shared
    slab: PAIRS of the published K/V heads (``cfg.cache_head_dim`` wide)."""
    return cfg.n_kv_heads // 2


def layer_of(stack: Dict[str, Any], at) -> Dict[str, Any]:
    """Layer ``at`` (which may be traced: the rolled loops' index) of a
    stack."""
    return {k: lax.dynamic_index_in_dim(v, at, 0, keepdims=False)
            for k, v in stack.items()}


def layer_params(params: Dict[str, Any], cfg: Phi4FlashConfig, layer: int):
    """Layer ``layer``'s own parameters."""
    kind = cfg.layer_types[layer]
    if kind == FULL:
        return params[FULL]
    return jax.tree.map(lambda a: a[kinds_of(cfg, kind).index(layer)],
                        params[kind])


# ---------------------------------------------------------------------------
# The mixers
# ---------------------------------------------------------------------------


def _ssm_inputs(x, p, cfg: Phi4FlashConfig):
    """The convolved ``x [..., d_inner]`` -> ``(dt [..., d_inner] float32 after
    its softplus, A [N, d_inner] float32, B, C [..., N])``."""
    R, N = cfg.mamba_dt_rank, cfg.mamba_state
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    rbc = dense(x, p["w_x"])
    dt = jax.nn.softplus(
        f32(dense(rbc[..., :R], p["w_dt"])) + f32(p["b_dt"]))
    return dt, -jnp.exp(f32(p["A_log"])), rbc[..., R:R + N], rbc[..., R + N:]


def mamba_whole(x, p, cfg: Phi4FlashConfig, lengths=None, before=None,
                state_in=None):
    """The Mamba middle over whole rows: ``x [B, T, d_inner]`` before its
    convolution, ``lengths [B]`` the real tokens of right-padded rows (None:
    all), ``before [B, d_conv - 1, d_inner]`` and ``state_in`` what a slot
    kept of the part of the prompt BEFORE these rows (None: a prompt's start).
    Returns ``(y [B, T, d_inner] float32, (state, tail [B, d_conv - 1,
    d_inner]))``: what a cache keeps of each row."""
    B, T, _ = x.shape
    lengths = jnp.full((B,), T, jnp.int32) if lengths is None else lengths
    with jax.named_scope("ssm.conv"):
        xc = ssm.causal_conv(x, p["conv_w"], p["conv_b"], before)
        tail = ssm.conv_tail(x, lengths, cfg.mamba_conv - 1, before)
        dt, a, b, c = _ssm_inputs(xc, p, cfg)
    with jax.named_scope("ssm.selective_scan"):
        y, state = ssm.selective_scan(xc, dt, a, b, c, p["D"], state_in, lengths)
    return y, (state, tail)


def mamba_step(x, p, cfg: Phi4FlashConfig, tail, update):
    """The Mamba middle of ONE token a row: ``x [B, 1, d_inner]``, ``tail
    [d_conv - 1, B, d_inner]`` the row's last inputs as a cache holds them,
    ``update(dt, dtx [B, d_inner], A, b, c [B, N]) -> y`` the state's step, in
    place, by whoever holds the state
    (:func:`ray_tpu.ops.ssm.selective_state_update`).  Returns ``(y [B, 1,
    d_inner] float32, the tail with this input in)``."""
    with jax.named_scope("ssm.conv"):
        last = jnp.concatenate([tail, x[:, 0][None].astype(tail.dtype)])
        f32 = last.astype(jnp.float32)
        conv = p["conv_b"].astype(jnp.float32) + sum(
            p["conv_w"][:, k].astype(jnp.float32) * f32[k]
            for k in range(cfg.mamba_conv))
        xc = jax.nn.silu(conv).astype(x.dtype)
        dt, a, b, c = _ssm_inputs(xc, p, cfg)
        xf = xc.astype(jnp.float32)
    y = update(dt, dt * xf, a, b, c) + p["D"].astype(jnp.float32) * xf
    return y[:, None], last[1:]


def pad_queries(q: jax.Array) -> jax.Array:
    """``[..., H, dh]`` -> ``[..., H, 2 dh]``: an even head's values then zeros,
    an odd head's after zeros (module docstring)."""
    even = (jnp.arange(q.shape[-2]) % 2 == 0)[:, None]
    zero = jnp.zeros_like(q)
    return jnp.concatenate(
        [jnp.where(even, q, zero), jnp.where(even, zero, q)], axis=-1)


def lambda_init(layer) -> jax.Array:
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def diff_combine(a: jax.Array, p, cfg: Phi4FlashConfig, layer) -> jax.Array:
    """The two softmaxes of every head pair, ``a [B, H, T, 2 dh]`` (head ``2p``
    the first, ``2p + 1`` the second), -> ``[B, T, H dh]``: ``rmsnorm(a1 -
    lambda a2; gamma) (1 - lambda_init)`` a pair, the pairs side by side."""
    B, H, T, W = a.shape
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    init = lambda_init(layer)
    lam = (jnp.exp(jnp.sum(f32(p["lq1"]) * f32(p["lk1"])))
           - jnp.exp(jnp.sum(f32(p["lq2"]) * f32(p["lk2"]))) + init)
    a = f32(a).reshape(B, H // 2, 2, T, W)
    o = a[:, :, 0] - lam * a[:, :, 1]
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    o = o * f32(p["subln"]) * (1.0 - init)
    return o.transpose(0, 2, 1, 3).reshape(B, T, H // 2 * W).astype(cfg.dtype)


def _mlp(x, p):
    gu = dense(x, p["w_gate_up"])
    F = gu.shape[-1] // 2
    return dense(jax.nn.silu(gu[..., :F]) * gu[..., F:], p["w_down"])


def _layer(x, p, cfg, mixer):
    norm = partial(layernorm, eps=cfg.norm_eps)
    out, *rest = mixer(norm(x, p["n1_w"], p["n1_b"]))
    x = x + out.astype(x.dtype)
    x = x + _mlp(norm(x, p["n2_w"], p["n2_b"]), p)
    return (x, *rest)


def mamba_layer(x, p, cfg: Phi4FlashConfig, mix=None):
    """A Mamba-1 layer.  ``mix(x [B, T, d_inner], p) -> (y float32, carried)``
    is the middle (None: :func:`mamba_whole` from a zero state).  Returns
    ``(x, y, carried)``: ``y`` is what a gated memory unit reads where this is
    the memory layer."""
    mix = mix or partial(mamba_whole, cfg=cfg)

    def mixer(h):
        with jax.named_scope("ssm.in_proj"):
            xz = dense(h, p["w_in"])
            xs, z = xz[..., :cfg.d_inner], xz[..., cfg.d_inner:]
        y, carried = mix(xs, p)
        with jax.named_scope("ssm.out_proj"):
            gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype)
            return dense(gated, p["w_out"]), y, carried

    return _layer(x, p, cfg, mixer)


def gmu_layer(x, p, cfg: Phi4FlashConfig, memory):
    """A gated memory unit: ``memory [B, T, d_inner]`` float32, the memory
    layer's ``y`` at the same positions."""
    def mixer(h):
        with jax.named_scope("gmu"):
            gate = jax.nn.silu(dense(h, p["w1"]).astype(jnp.float32))
            return (dense((gate * memory).astype(h.dtype), p["w2"]),)

    return _layer(x, p, cfg, mixer)[0]


def _pairs_kv(h, p, cfg: Phi4FlashConfig):
    """K and V of the normed ``h [B, T, D]`` in the pair layout ``[B, KV / 2,
    T, 2 dh]`` (module docstring)."""
    B, T, _ = h.shape
    return tuple(dense(h, p[w], p[b]).reshape(
        B, T, cfg.n_kv_heads // 2, 2 * cfg.head_dim).transpose(0, 2, 1, 3)
        for w, b in (("wk", "bk"), ("wv", "bv")))


def attention_layer(x, p, cfg: Phi4FlashConfig, layer, attend):
    """A differential attention layer.  ``attend(q [B, H, T, 2 dh], k, v [B,
    KV / 2, T, 2 dh]) -> (a [B, H, T, 2 dh], carried)`` is the middle in the
    PAIR layout a cache stores (module docstring); a layer without ``wk``
    (cross attention) hands it ``k = v = None``.  Returns ``(x, carried)``."""
    B, T, D = x.shape
    H, dh = cfg.n_heads, cfg.head_dim

    def mixer(h):
        q = pad_queries(dense(h, p["wq"], p["bq"]).reshape(B, T, H, dh))
        k, v = _pairs_kv(h, p, cfg) if "wk" in p else (None, None)
        a, carried = attend(q.transpose(0, 2, 1, 3), k, v)
        with jax.named_scope("attention.diff_combine"):
            o = diff_combine(a, p, cfg, layer)
        return dense(o, p["wo"], p["bo"]), carried

    return _layer(x, p, cfg, mixer)


# ---------------------------------------------------------------------------
# The two halves, rolled
# ---------------------------------------------------------------------------


def lower_stack(params, cfg: Phi4FlashConfig, x, carry, mamba, attend):
    """Layers ``0 .. shared_layer - 1``: the (Mamba, window) pairs as ONE
    rolled loop over the two stacks, then the memory layer.  ``mamba(at, x, p,
    carry) -> (y, kept, carry)`` and ``attend(at, q, k, v, carry) -> (a, kept,
    carry)`` are the middles of the ``at``-th layer of their kind; ``carry`` is
    whatever the caller threads through the layers (a decode step's buffers
    and states; None).  Returns ``(x, the memory layer's y, carry, the Mamba
    layers' kept stacked [layers, ...], the window layers' kept)``."""
    pairs = cfg.memory_layer // 2

    def one_mamba(x, carry, at):
        box = []

        def mix(xs, p):
            y, kept, new = mamba(at, xs, p, carry)
            box.append(new)
            return y, kept

        x, y, kept = mamba_layer(x, layer_of(params[MAMBA], at), cfg, mix)
        return x, y, kept, box[0]

    def pair(state, at):
        x, carry = state
        x, _, kept_m, carry = one_mamba(x, carry, at)
        box = []

        def mix(q, k, v):
            a, kept, new = attend(at, q, k, v, carry)
            box.append(new)
            return a, kept

        x, kept_w = attention_layer(
            x, layer_of(params[WINDOW], at), cfg, 2 * at + 1, mix)
        return (x, box[0]), (kept_m, kept_w)

    (x, carry), (kept_m, kept_w) = lax.scan(pair, (x, carry), jnp.arange(pairs))
    x, memory, kept, carry = one_mamba(x, carry, pairs)
    kept_m = jax.tree.map(lambda a, b: jnp.concatenate([a, b[None]]), kept_m, kept)
    return x, memory, carry, kept_m, kept_w


def shared_kv(params, cfg: Phi4FlashConfig, x):
    """The shared cache's K and V of ``x [B, T, D]`` (the stream as it enters
    the full layer), in the pair layout ``[B, KV / 2, T, 2 dh]``: all a
    prompt position other than the last needs of the layers from here up."""
    p = params[FULL]
    return _pairs_kv(layernorm(x, p["n1_w"], p["n1_b"], eps=cfg.norm_eps), p, cfg)


def shared_layer(params, cfg: Phi4FlashConfig, x, attend):
    """The full-attention layer, whose K and V are the shared cache."""
    return attention_layer(x, params[FULL], cfg, cfg.shared_layer, attend)


def upper_stack(params, cfg: Phi4FlashConfig, x, memory, attend):
    """Layers ``shared_layer + 1 ..``: the (GMU, cross) pairs as ONE rolled
    loop.  ``attend(q) -> a``: the shared slab's read.  Returns ``x``."""
    def pair(x, at):
        x = gmu_layer(x, layer_of(params[GMU], at), cfg, memory)
        x, _ = attention_layer(
            x, layer_of(params[CROSS], at), cfg, cfg.shared_layer + 2 + 2 * at,
            lambda q, k, v: (attend(q), None))
        return x, None

    return lax.scan(pair, x, jnp.arange(len(kinds_of(cfg, CROSS))))[0]


def embed(params: Dict[str, Any], tokens: jax.Array, cfg: Phi4FlashConfig,
          positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, T] -> x [B, T, D] in cfg.dtype (``positions`` is not used:
    this family encodes none)."""
    return params["tok_emb"][tokens].astype(cfg.dtype)


def unembed(params: Dict[str, Any], x: jax.Array, cfg: Phi4FlashConfig) -> jax.Array:
    """Final norm and the TIED head: x [B, T, D] -> logits [B, T, V] f32."""
    with jax.named_scope("head"):
        x = layernorm(x, params["final_norm_w"], params["final_norm_b"],
                      eps=cfg.norm_eps)
        return jnp.einsum("btd,vd->btv", x, params["tok_emb"].astype(x.dtype),
                          preferred_element_type=jnp.float32)


def apply(params: Dict[str, Any], tokens: jax.Array, cfg: Phi4FlashConfig) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, V] f32: the whole forward, no
    cache, EVERY layer at every position (the tests hold prefill's early exit
    and decode to it)."""
    scale = cfg.attention_scale
    x = embed(params, tokens, cfg)
    window = lambda at, q, k, v, carry: (  # noqa: E731
        _attend(q, k, v, causal=True, mesh=None, window=cfg.sliding_window,
                scale=scale)[0], None, carry)
    x, memory, _, _, _ = lower_stack(
        params, cfg, x, None,
        lambda at, xs, p, carry: (mamba_whole(xs, p, cfg)[0], None, carry),
        window)
    held = []

    def full(q, k, v):
        held.extend((k, v))
        return _attend(q, k, v, causal=True, mesh=None, scale=scale)[0], None

    x, _ = shared_layer(params, cfg, x, full)
    x = upper_stack(params, cfg, x, memory, lambda q: _attend(
        q, *held, causal=True, mesh=None, scale=scale)[0])
    return unembed(params, x, cfg)


def num_params(params: Dict[str, Any]) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
