"""Plain reference for the EXAONE-MoE family (``model_type: exaone_moe``,
huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B): the forward pass in
straightforward ``jax.numpy``, float32, matmul precision "highest".  No
cache, no kernels, no grouped matmul, nothing from ``ray_tpu.models``:
attention is materialised under its mask (a group of query heads at a time,
so that 4,736 positions fit beside the served weights), the experts are a
loop with a dense mask.

It reads the parameter TREE the program made (the weights are the program's,
the arithmetic is not): ``tok_emb [V, D]``, ``head [D, V]``, ``final_norm``
and ``layers``, a list with one dict a layer: ``attn_norm ffn_norm q_norm
k_norm wq wk wv wo`` and either ``w_gate w_up w_down`` (a dense layer) or
``router [D, E] router_bias [E] ew_gate ew_up [held, D, F] ew_down [held, F,
D] sw_gate sw_up sw_down`` (a sparse one).  Leaves may be bfloat16 (what a
server held); a layer is widened where it is used, one layer at a time.

``sizes`` holds what shapes do not say: ``n_heads``, ``n_kv_heads``,
``head_dim``, ``sliding_windows`` (per layer: 0 a full layer, else the
window), ``top_k``, ``routed_scale``, ``first_expert`` (the experts in the
tree are ``first_expert ..`` of the router's width; 0 and all of them is the
uncut model), ``rope_theta``, ``rms_eps``.

The equations (``n`` = RMSNorm with a learned scale; pre-norm residuals):

- attention: ``q, k, v = W_q n(x), W_k n(x), W_v n(x)``; ``q, k``
  RMS-normalised over the head dimension with a learned scale; on a window
  layer rotary (theta ``rope_theta``, rotate-half) and ``i`` attends ``i -
  window < j <= i``; on a full layer no rotary and ``j <= i``; scale
  ``head_dim ** -0.5``; ``x += W_o o``.
- dense FFN: ``x += W_down(silu(W_gate h) * W_up h)``.
- sparse FFN: ``s = sigmoid(h W_r)``, ``sel = top_k(s + b)``, ``g_i =
  routed_scale * s_i / sum_{j in sel} s_j``, ``x += E_shared(h) + sum_{i in
  sel, in the tree} g_i E_i(h)``.
- head: final RMSNorm, the output matrix.

Departures from the published description, each because the config.json does
not say and the family's (or the DeepSeek-V3-style router's) convention does:
pre-norm placement; QK-norm; rotary on window layers only; the selection bias
``b`` exists and enters the choice only; the window counts the current
position.  The multi-token-prediction module is left out (not served).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _through(lower):
    """Operands as the reference holds them: float32, or rounded through the
    dtype ``lower`` names first (only the control of a cell's limits lowers
    it: ``drivers/serve_family.py``)."""
    if lower is None:
        return lambda a: jnp.asarray(a).astype(jnp.float32)
    return lambda a: jnp.asarray(a).astype(jnp.float32).astype(
        jnp.dtype(lower)).astype(jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, heads, T, d] at positions 0..T-1, rotate-half."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _swiglu(h, w_gate, w_up, w_down, f):
    return f(jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "head_dim", "window", "top_k", "routed_scale",
    "first_expert", "rope_theta", "rms_eps", "lower"))
def _layer(x, p, *, n_heads, n_kv_heads, head_dim, window, top_k,
           routed_scale, first_expert, rope_theta, rms_eps, lower):
    """x [B, T, D] float32, p one layer's parameters as stored."""
    f = _through(lower)
    p = jax.tree.map(f, p)
    B, T, D = x.shape
    h = f(_rmsnorm(x, p["attn_norm"], rms_eps))
    heads = lambda t, n: t.reshape(B, T, n, head_dim).transpose(0, 2, 1, 3)
    q = _rmsnorm(heads(h @ p["wq"], n_heads), p["q_norm"], rms_eps)
    k = _rmsnorm(heads(h @ p["wk"], n_kv_heads), p["k_norm"], rms_eps)
    v = heads(h @ p["wv"], n_kv_heads)
    if window:
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = (j <= i) & (j > i - window) if window else j <= i
    group = n_heads // n_kv_heads
    outs = []
    for kv in range(n_kv_heads):  # a KV head's query heads at a time
        qg = f(q[:, kv * group:(kv + 1) * group])
        s = (qg @ f(k[:, kv, None]).transpose(0, 1, 3, 2)) * head_dim ** -0.5
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(f(w) @ f(v[:, kv, None]))
    o = jnp.concatenate(outs, 1).transpose(0, 2, 1, 3).reshape(B, T, -1)
    x = x + f(o) @ p["wo"]

    h = f(_rmsnorm(x, p["ffn_norm"], rms_eps))
    if "router" not in p:
        return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], f)
    s = jax.nn.sigmoid(h @ p["router"])                       # [B, T, E]
    _, sel = jax.lax.top_k(s + p["router_bias"], top_k)
    chosen = jnp.take_along_axis(s, sel, -1)
    gates = routed_scale * chosen / chosen.sum(-1, keepdims=True)
    y = _swiglu(h, p["sw_gate"], p["sw_up"], p["sw_down"], f)
    for e in range(p["ew_gate"].shape[0]):  # every held expert, densely
        g = jnp.where(sel == first_expert + e, gates, 0.0).sum(-1)
        y = y + g[..., None] * _swiglu(
            h, p["ew_gate"][e], p["ew_up"][e], p["ew_down"][e], f)
    return x + y


@functools.partial(jax.jit, static_argnames=("rms_eps", "lower"))
def _head(x, norm, head, *, rms_eps, lower):
    f = _through(lower)
    return f(_rmsnorm(x, f(norm), rms_eps)) @ f(head)


def logits(params, tokens, sizes: dict, lower=None):
    """tokens [B, T] int32 -> logits [B, T, V] float32.  ``lower``: a dtype's
    name; every matmul operand (weights and activations) is rounded through
    it first, which is how the control of a cell's limits computes the
    reference "in a lower precision"."""
    static = {k: sizes[k] for k in (
        "n_heads", "n_kv_heads", "head_dim", "top_k", "routed_scale",
        "first_expert", "rope_theta", "rms_eps")}
    with jax.default_matmul_precision("highest"):
        x = _through(lower)(params["tok_emb"][tokens])
        for p, window in zip(params["layers"], sizes["sliding_windows"]):
            x = _layer(x, p, window=int(window), lower=lower, **static)
        return _head(x, params["final_norm"], params["head"],
                     rms_eps=sizes["rms_eps"], lower=lower)
