"""GPT-2, the plain way: float32 ``jax.numpy``, materialized attention, a
Python loop over the layers, no kernels, no cache, no remat, no sharding
annotations.  It follows the published model (Radford et al. 2019, and
``modeling_gpt2.py`` of transformers): pre-LayerNorm blocks, learned
positions, tanh-GELU, tied output embedding.  It shares no code with
``ray_tpu/models``; only the parameter LAYOUT is the program's (stacked
``[L, ...]`` leaves: wte, wpe, blocks{ln1_w, ln1_b, wqkv, bqkv, wo, bo,
ln2_w, ln2_b, w1, b1, w2, b2}, lnf_w, lnf_b), because the weights under
test are the program's own.

On a TPU a float32 matmul runs in lower precision unless asked otherwise,
so every entry point here runs under ``default_matmul_precision("highest")``.
One block is jitted once and called per layer, which keeps the compile to
seconds at 48 layers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5  # GPT-2's layer_norm_epsilon


def _layernorm(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_heads",))
def _block(x, p, n_heads):
    """x [B, T, D] float32, p one layer's parameters."""
    B, T, D = x.shape
    dh = D // n_heads
    h = _layernorm(x, p["ln1_w"], p["ln1_b"])
    qkv = h @ p["wqkv"] + p["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    heads = lambda t: t.reshape(B, T, n_heads, dh).transpose(0, 2, 1, 3)
    q, k, v = heads(q), heads(k), heads(v)
    scores = (q @ k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1) @ v
    att = att.transpose(0, 2, 1, 3).reshape(B, T, D)
    x = x + att @ p["wo"] + p["bo"]
    h = _layernorm(x, p["ln2_w"], p["ln2_b"])
    return x + _gelu_tanh(h @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


@jax.jit
def _embed(wte, wpe, tokens):
    return wte[tokens] + wpe[: tokens.shape[1]]


@jax.jit
def _head(x, lnf_w, lnf_b, wte):
    return _layernorm(x, lnf_w, lnf_b) @ wte.T


@jax.jit
def _nll(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def logits(params, tokens, n_heads: int):
    """tokens [B, T] int32 -> logits [B, T, V] float32."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        x = _embed(f32(params["wte"]), f32(params["wpe"]), tokens)
        blocks = params["blocks"]
        n_layers = blocks["wqkv"].shape[0]
        for layer in range(n_layers):
            x = _block(x, f32({k: v[layer] for k, v in blocks.items()}),
                       n_heads)
        return _head(x, f32(params["lnf_w"]), f32(params["lnf_b"]),
                     f32(params["wte"]))


def loss(params, inputs, targets, n_heads: int, rows_per_call: int = 2):
    """Mean next-token cross entropy over ``inputs``/``targets`` [B, T],
    computed ``rows_per_call`` sequences at a time so that the float32
    logits of a whole training batch never have to exist at once."""
    total, count = 0.0, 0
    for i in range(0, inputs.shape[0], rows_per_call):
        rows = slice(i, i + rows_per_call)
        nll = _nll(logits(params, inputs[rows], n_heads), targets[rows])
        total += float(nll.sum())
        count += nll.size
    return total / count
