"""Plain reference for the Kimi-K2 family (``model_type: kimi_k2``,
huggingface.co/moonshotai/Kimi-K2.7-Code; the DeepSeek-V3 layer): the forward
pass in straightforward ``jax.numpy``, float32, matmul precision "highest".
No cache, no kernels, no grouped matmul, no absorbed form, nothing from
``ray_tpu.models``: the multi-head latent attention is written UN-absorbed
(every position's ``k_nope`` and ``v`` up-projected from its latent row) and
materialised under its causal mask, the experts are a loop with a dense mask,
YaRN is written out.

Computed in blocks so that 9,216 positions fit beside the served weights: a
row of the batch at a time, a group of heads at a time, a block of queries at
a time against all keys (the scores of a block are materialised whole), and
each row's logits are brought to the host as they are made (the result is a
numpy array).

It reads the parameter TREE the program made (the weights are the program's,
the arithmetic is not): ``tok_emb [V, D]``, ``head [D, V]``, ``final_norm``
and ``layers``, a list with one dict a layer: ``attn_norm ffn_norm w_dq [D,
rq] q_norm w_uq [rq, H * (nope + pe)] w_dkv [D, rkv + pe] kv_norm w_uk [H,
nope, rkv] w_uv [H, rkv, dv] wo [H * dv, D]`` and either ``w_gate w_up
w_down`` (a dense layer) or ``router [D, E] router_bias [E] ew_gate ew_up
[held, D, F] ew_down [held, F, D] sw_gate sw_up sw_down`` (a sparse one).
Leaves may be bfloat16 (what a server held); a weight is widened where it is
used.

``sizes`` holds what shapes do not say: ``n_heads``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``top_k``, ``routed_scale``, ``first_expert`` (the
experts in the tree are ``first_expert ..`` of the router's width),
``rope_theta``, ``rope_scaling`` (the published group: ``factor``,
``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
``mscale``, ``mscale_all_dim``), ``rms_eps``.

The equations (``n`` = RMSNorm with a learned scale; pre-norm residuals):

- ``c_q = n(W_dq h)``; ``[q_nope | q_pe] = W_uq c_q`` a head; ``[c | k_pe] =
  W_dkv h``; ``c = n(c)``; ``q_pe, k_pe`` rotated (YaRN; ``k_pe`` is one key
  for all heads); ``k_nope = W_uk c``, ``v = W_uv c`` a head.
- ``s_ij = scale (q_nope_i . k_nope_j + q_pe_i . k_pe_j)`` for ``j <= i``,
  ``scale = (nope + pe) ** -0.5 * m * m``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``; softmax; ``x += W_o (sum_j p_ij v_j)``.
- YaRN: ``inv_freq_i = base_i / factor`` where dimension ``i`` turns fewer
  than ``beta_slow`` times over the original positions, ``base_i`` where more
  than ``beta_fast`` times, linear in ``i`` between the two correction
  dimensions; cos and sin times ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)``.
- dense FFN: ``x += W_down(silu(W_gate h) * W_up h)``.
- sparse FFN: ``s = sigmoid(h W_r)``, ``sel = top_k(s + b)``, ``g_i =
  routed_scale * s_i / sum_{j in sel} s_j``, ``x += E_shared(h) + sum_{i in
  sel, in the tree} g_i E_i(h)``.
- head: final RMSNorm, the output matrix.

Departures from the published description, each because the config.json does
not say and the DeepSeek-V3 code's convention does: pre-norm placement; the
selection bias ``b`` exists and enters the choice only; the rotary pairs
dimension ``2i`` with ``2i + 1`` (the published code permutes each pair to
``(i, i + d/2)`` first, which leaves every dot product as it is).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HEAD_GROUP = 8      # heads whose scores are materialised together
QUERY_BLOCK = 1024  # queries a block of materialised scores


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


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: dict) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies, one dimension at a time."""
    original = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        base = theta ** (-2.0 * i / dim)
        interpolated = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(base / scaling["factor"] * interpolated
                   + base * (1.0 - interpolated))
    return np.asarray(out, np.float32)


def _rope(x, theta, scaling):
    """x [.., T, d] at positions 0..T-1, dimension 2i paired with 2i + 1."""
    d = x.shape[-1]
    inv = jnp.asarray(yarn_inv_freq(d, theta, scaling))
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv[None, :]
    m = (yarn_mscale(scaling["factor"], scaling["mscale"])
         / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _swiglu(h, w_gate, w_up, w_down, f):
    return f(jax.nn.silu(h @ f(w_gate)) * (h @ f(w_up))) @ f(w_down)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "nope", "pe", "top_k", "routed_scale", "first_expert",
    "rope_theta", "rope_scaling", "rms_eps", "lower"))
def _layer(x, p, *, n_heads, nope, pe, top_k, routed_scale, first_expert,
           rope_theta, rope_scaling, rms_eps, lower):
    """x [1, T, D] float32, p one layer's parameters as stored."""
    f = _through(lower)
    scaling = dict(rope_scaling)
    _, T, D = x.shape
    rkv = p["kv_norm"].shape[0]
    h = f(_rmsnorm(x, f(p["attn_norm"]), rms_eps))[0]            # [T, D]
    c_q = f(_rmsnorm(h @ f(p["w_dq"]), f(p["q_norm"]), rms_eps))
    ckv = h @ f(p["w_dkv"])                                       # [T, rkv + pe]
    c = f(_rmsnorm(ckv[:, :rkv], f(p["kv_norm"]), rms_eps))
    k_pe = f(_rope(ckv[:, rkv:], rope_theta, scaling))            # [T, pe]
    m = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    scale = (nope + pe) ** -0.5 * m * m
    w_uq = f(p["w_uq"]).reshape(-1, n_heads, nope + pe)
    outs = []
    for g in range(0, n_heads, HEAD_GROUP):  # a group of heads at a time
        heads = slice(g, min(g + HEAD_GROUP, n_heads))
        q = jnp.einsum("tr,rhd->htd", c_q, w_uq[:, heads])        # [h, T, 192]
        q = jnp.concatenate([
            q[..., :nope], _rope(q[..., nope:], rope_theta, scaling)], -1)
        k_nope = jnp.einsum("tc,hdc->htd", c, f(p["w_uk"][heads]))
        k = jnp.concatenate([
            k_nope, jnp.broadcast_to(k_pe, (k_nope.shape[0], T, pe))], -1)
        v = f(jnp.einsum("tc,hcv->htv", c, f(p["w_uv"][heads])))
        q, k = f(q), f(k)
        rows = []
        for lo in range(0, T, QUERY_BLOCK):  # a block of queries, all keys
            i = jnp.arange(lo, min(lo + QUERY_BLOCK, T))[:, None]
            s = (q[:, lo:lo + QUERY_BLOCK] @ k.transpose(0, 2, 1)) * scale
            s = jnp.where(jnp.arange(T)[None, :] <= i, s, -jnp.inf)
            rows.append(f(jax.nn.softmax(s, axis=-1)) @ v)
        outs.append(jnp.concatenate(rows, 1))                     # [h, T, dv]
    o = jnp.concatenate(outs, 0).transpose(1, 0, 2).reshape(T, -1)
    x = x + (f(o) @ f(p["wo"]))[None]

    h = f(_rmsnorm(x, f(p["ffn_norm"]), rms_eps))
    if "router" not in p:
        return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], f)
    s = jax.nn.sigmoid(h @ f(p["router"]))                        # [1, T, E]
    _, sel = jax.lax.top_k(s + f(p["router_bias"]), top_k)
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


def layer_statics(sizes: dict, lower=None) -> dict:
    """:func:`_layer`'s keywords from ``sizes`` (hashable: jit closes over
    them)."""
    return dict(
        n_heads=sizes["n_heads"], nope=sizes["qk_nope_head_dim"],
        pe=sizes["qk_rope_head_dim"], top_k=sizes["top_k"],
        routed_scale=sizes["routed_scale"], first_expert=sizes["first_expert"],
        rope_theta=float(sizes["rope_theta"]),
        rope_scaling=tuple(sorted(
            (k, v) for k, v in sizes["rope_scaling"].items() if k != "type")),
        rms_eps=sizes["rms_eps"], lower=lower)


def logits(params, tokens, sizes: dict, lower=None):
    """tokens [B, T] int32 -> logits [B, T, V] float32, on the HOST (numpy).
    ``lower``: a dtype's name; every matmul operand (weights and activations)
    is rounded through it first, which is how the control of a cell's limits
    computes the reference "in a lower precision"."""
    static = layer_statics(sizes, lower)
    out = []
    with jax.default_matmul_precision("highest"):
        for row in np.asarray(tokens):  # a row of the batch at a time
            x = _through(lower)(params["tok_emb"][jnp.asarray(row)[None]])
            for p in params["layers"]:
                x = _layer(x, p, **static)
            out.append(np.asarray(_head(
                x, params["final_norm"], params["head"],
                rms_eps=sizes["rms_eps"], lower=lower)[0]))
    return np.stack(out)
