"""Plain reference for the Granite-4.0-H family (``model_type:
granitemoehybrid``, huggingface.co/ibm-granite/granite-4.0-h-small): the
forward pass in straightforward ``jax.numpy``, float32, matmul precision
"highest".  No cache, no kernels, no grouped matmul, no chunked scan, nothing
from ``ray_tpu.models`` or ``ray_tpu.ops``: a Mamba-2 layer is the
step-by-step recurrence itself (one ``lax.scan`` over the positions, where
the program prefills by the chunked quadratic form and decodes through a
cache of states), the attention is materialised under its causal mask, the
experts are a loop with a dense mask.

Computed a row of the batch at a time, a group of heads and a block of
queries at a time in the attention layers, and each row's logits are brought
to the host as they are made (the result is a numpy array).

It reads the parameter TREE the program made (the weights are the program's,
the arithmetic is not): ``tok_emb [V, D]`` (also the head), ``final_norm``,
``mamba`` (ONE dict whose leaves are stacked over the Mamba layers in order:
``mixer_norm ffn_norm w_in [D, 2 d_inner + 2 N + heads] conv_w [C, K] conv_b
dt_bias A_log D [heads] ssm_norm [d_inner] w_out [d_inner, D]``) and
``attention`` (a list, one dict an attention layer: ``mixer_norm ffn_norm wq
wk wv wo``); every layer of either kind also holds ``router [D, E] ew_gate
ew_up [held, D, F] ew_down [held, F, D] sw_gate sw_up sw_down``.  Leaves may
be bfloat16 (what a server held); a weight is widened where it is used.

``sizes`` holds what shapes do not say: ``layer_types`` (one name a layer),
``n_heads``, ``n_kv_heads`` (attention), ``mamba_heads``, ``mamba_state``,
``top_k``, ``first_expert`` (the experts in the tree are ``first_expert ..``
of the router's width), ``embedding_multiplier``, ``logits_scaling``,
``residual_multiplier``, ``attention_multiplier``, ``rms_eps``.

The equations (``n`` = RMSNorm with a learned scale):

- ``h = embed[ids] * embedding_multiplier``; a layer: ``h += r mixer(n(h))``,
  then ``h += r (experts(n(h)) + shared(n(h)))``, ``r`` the residual
  multiplier; ``logits = n(h) embed^T / logits_scaling``.
- Mamba-2: ``[z | xBC | dt] = x W_in``; ``xBC_t = silu(b + sum_k w[:, k]
  xBC_{t-3+k})``, zeros before the sequence; ``xBC = [X | B | C]``; ``dt =
  softplus(dt + dt_bias)``; ``A = -exp(A_log)``; ``H_t = exp(dt_t A) H_{t-1} +
  dt_t X_t (outer) B_t``; ``Y_t = H_t C_t + D X_t``; ``out = n(Y silu(z))
  W_out``, the norm over all ``d_inner`` values.
- attention: ``q, k, v = W_q x, W_k x, W_v x``; no position encoding; ``s_ij
  = attention_multiplier q_i . k_j`` for ``j <= i``; softmax; ``out = W_o o``.
- experts: the ``top_k`` largest router logits, gates their softmax; ``y =
  shared(x) + sum_{i chosen, in the tree} g_i E_i(x)``, ``E(x) = W_down
  (silu(W_gate x) * W_up x)``.

Departures from the published description, each because the config.json does
not say: ``intermediate_size`` (768) is read as ONE expert's width (the
catalog notes the inference); ``W_in``'s columns are ordered ``z | xBC | dt``
and ``xBC``'s ``X | B | C`` (the Mamba-2 reference code's order); the learned
vectors ``A_log``, ``dt_bias``, ``D`` are whatever the tree holds (the
program draws them by the Mamba-2 convention); an expert's fused ``[gate |
up]`` projection is read as its two halves.
"""

from __future__ import annotations

import functools

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


def _swiglu(h, w_gate, w_up, w_down, f):
    return f(jax.nn.silu(h @ f(w_gate)) * (h @ f(w_up))) @ f(w_down)


def _mamba(h, p, f, *, heads, state):
    """h [T, D] (normed, rounded) -> ``Y silu(z)`` [T, d_inner], before the
    mixer's norm and output projection."""
    T = h.shape[0]
    d_inner = p["ssm_norm"].shape[0]
    width, taps = p["conv_w"].shape
    proj = h @ f(p["w_in"])
    z, xbc, dt = (proj[:, :d_inner], proj[:, d_inner:d_inner + width],
                  proj[:, d_inner + width:])
    padded = jnp.concatenate([jnp.zeros((taps - 1, width)), f(xbc)])
    w = jnp.asarray(p["conv_w"]).astype(jnp.float32)
    conv = jnp.asarray(p["conv_b"]).astype(jnp.float32) + sum(
        w[:, k] * padded[k:k + T] for k in range(taps))
    xbc = f(jax.nn.silu(conv))
    x = xbc[:, :d_inner].reshape(T, heads, d_inner // heads)
    b, c = xbc[:, d_inner:d_inner + state], xbc[:, d_inner + state:]
    wide = lambda name: jnp.asarray(p[name]).astype(jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(dt + wide("dt_bias"))                    # [T, heads]
    a = -jnp.exp(wide("A_log"))

    def position(held, inputs):  # the recurrence, one position at a time
        x_t, b_t, c_t, dt_t = inputs
        held = (jnp.exp(dt_t * a)[:, None, None] * held
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return held, (held * c_t[None, None, :]).sum(-1)

    _, y = jax.lax.scan(
        position, jnp.zeros((heads, d_inner // heads, state)), (x, b, c, dt))
    y = (y + wide("D")[:, None] * x).reshape(T, d_inner)
    return y * jax.nn.silu(z)


@functools.partial(jax.jit, static_argnames=(
    "kind", "n_heads", "n_kv_heads", "mamba_heads", "mamba_state", "top_k",
    "first_expert", "residual", "attention_scale", "rms_eps", "lower"))
def _layer(x, p, *, kind, n_heads, n_kv_heads, mamba_heads, mamba_state,
           top_k, first_expert, residual, attention_scale, rms_eps, lower):
    """x [1, T, D] float32, p one layer's parameters as stored."""
    f = _through(lower)
    _, T, D = x.shape
    h = f(_rmsnorm(x, f(p["mixer_norm"]), rms_eps))[0]            # [T, D]
    if kind == "mamba":
        gated = _mamba(h, p, f, heads=mamba_heads, state=mamba_state)
        out = f(_rmsnorm(gated, f(p["ssm_norm"]), rms_eps)) @ f(p["w_out"])
    else:
        hd = p["wq"].shape[1] // n_heads
        q = f(h @ f(p["wq"])).reshape(T, n_heads, hd).transpose(1, 0, 2)
        k = f(h @ f(p["wk"])).reshape(T, n_kv_heads, hd).transpose(1, 0, 2)
        v = f(h @ f(p["wv"])).reshape(T, n_kv_heads, hd).transpose(1, 0, 2)
        group = n_heads // n_kv_heads
        outs = []
        for g in range(0, n_heads, HEAD_GROUP):  # a group of heads at a time
            heads = np.arange(g, min(g + HEAD_GROUP, n_heads))
            kg, vg = k[heads // group], v[heads // group]  # its K/V head each
            rows = []
            for lo in range(0, T, QUERY_BLOCK):  # a block of queries, all keys
                i = jnp.arange(lo, min(lo + QUERY_BLOCK, T))[:, None]
                s = (q[heads, lo:lo + QUERY_BLOCK] @ kg.transpose(0, 2, 1)
                     ) * attention_scale
                s = jnp.where(jnp.arange(T)[None, :] <= i, s, -jnp.inf)
                rows.append(f(jax.nn.softmax(s, axis=-1)) @ vg)
            outs.append(jnp.concatenate(rows, 1))                 # [h, T, hd]
        o = jnp.concatenate(outs, 0).transpose(1, 0, 2).reshape(T, -1)
        out = f(o) @ f(p["wo"])
    x = x + residual * out[None]

    h = f(_rmsnorm(x, f(p["ffn_norm"]), rms_eps))
    chosen, sel = jax.lax.top_k(h @ f(p["router"]), top_k)        # logits
    gates = jax.nn.softmax(chosen, axis=-1)
    y = _swiglu(h, p["sw_gate"], p["sw_up"], p["sw_down"], f)
    for e in range(p["ew_gate"].shape[0]):  # every held expert, densely
        g = jnp.where(sel == first_expert + e, gates, 0.0).sum(-1)
        y = y + g[..., None] * _swiglu(
            h, p["ew_gate"][e], p["ew_up"][e], p["ew_down"][e], f)
    return x + residual * y


@functools.partial(jax.jit, static_argnames=("scaling", "rms_eps", "lower"))
def _head(x, norm, emb, *, scaling, rms_eps, lower):
    f = _through(lower)
    return f(_rmsnorm(x, f(norm), rms_eps)) @ f(emb).T / scaling


def layer_statics(sizes: dict, lower=None) -> dict:
    """:func:`_layer`'s keywords from ``sizes`` (hashable: jit closes over
    them), without the layer's ``kind``."""
    return dict(
        n_heads=sizes["n_heads"], n_kv_heads=sizes["n_kv_heads"],
        mamba_heads=sizes["mamba_heads"], mamba_state=sizes["mamba_state"],
        top_k=sizes["top_k"], first_expert=sizes["first_expert"],
        residual=float(sizes["residual_multiplier"]),
        attention_scale=float(sizes["attention_multiplier"]),
        rms_eps=sizes["rms_eps"], lower=lower)


def layer_params(params, sizes: dict, layer: int):
    """Layer ``layer``'s parameters out of the tree: a slice of the Mamba
    layers' stack, or an entry of the attention layers' list."""
    kinds = list(sizes["layer_types"])
    at = kinds[:layer].count(kinds[layer])
    if kinds[layer] == "attention":
        return params["attention"][at]
    return jax.tree.map(lambda a: a[at], params["mamba"])


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
            x = x * float(sizes["embedding_multiplier"])
            for l, kind in enumerate(sizes["layer_types"]):
                x = _layer(x, layer_params(params, sizes, l), kind=kind, **static)
            out.append(np.asarray(_head(
                x, params["final_norm"], params["tok_emb"],
                scaling=float(sizes["logits_scaling"]),
                rms_eps=sizes["rms_eps"], lower=lower)[0]))
    return np.stack(out)
