"""Plain reference for the Phi-4-mini-flash family (``model_type: phi4flash``,
huggingface.co/microsoft/Phi-4-mini-flash-reasoning; SambaY with differential
attention, arXiv:2507.06607): the forward pass in straightforward
``jax.numpy``, float32, matmul precision "highest".  No cache, no kernels, no
early exit, nothing from ``ray_tpu.models`` or ``ray_tpu.ops``: a Mamba-1
layer is the step-by-step recurrence itself (one ``lax.scan`` over the
positions), the attention is materialised under its mask a block of queries
at a time with the FOUR softmax-value products of a head pair written out
(where the program pads a query head with zeros and reads a pair of K/V heads
as one head twice as wide), and the WHOLE stack runs at every position (where
the program's prefill runs the layers above the shared cache for a prompt's
last position only).

Computed a row of the batch at a time; the MLP and the head a block of
positions at a time (``[T, 2 x 10,240]`` float32 is 1.5 GB and ``[T, 200,064]``
14.9 GB at ``T`` = 18,560); the logits are written into a NumPy array a block
at a time, for the positions that hold tokens, and never exist on the device
whole (a large result's buffer is reused by the next call: ``REUSE_BYTES``).  One layer's weights are
widened to float32 at a time.

It reads the parameter TREE the program made (the weights are the program's,
the arithmetic is not): ``tok_emb [V, D]`` (also the head), ``final_norm_w``,
``final_norm_b``, four stacks whose leaves are stacked over the layers of the
kind in order (``mamba``, ``window``, ``gmu``, ``cross``) and ``full``, the one
full-attention layer.  Every layer: ``n1_w n1_b n2_w n2_b w_gate_up [D, 2 F]
w_down [F, D]``.  Mamba: ``w_in [D, 2 d_inner] conv_w [d_inner, K] conv_b w_x
[d_inner, R + 2 N] w_dt [R, d_inner] b_dt A_log [N, d_inner] D w_out``.
Attention: ``wq bq wo bo lq1 lk1 lq2 lk2 [dh] subln [2 dh]`` and, where the
layer owns K and V (``window``, ``full``), ``wk bk wv bv``.  GMU: ``w1 [D,
d_inner] w2``.

``sizes``: ``n_layers``, ``n_heads``, ``n_kv_heads``, ``sliding_window``,
``norm_eps``.

The equations (``n`` = LayerNorm with scale and bias; no position encoding):

- ``h = embed[ids]``; layer ``l``: ``h += mixer_l(n1(h))``; ``h += W_down
  (silu(g) * u)``, ``[g | u] = n2(h) W_gate_up``; ``logits = n_f(h) embed^T``.
- which mixer: ``l < L/2``: even Mamba-1, odd window attention; ``l = L/2``
  Mamba-1 whose ``y`` is also the memory ``m``; ``l = L/2 + 1`` full attention,
  whose K and V every cross layer reads; above: even GMU, odd cross attention.
- Mamba-1: ``[x | z] = u W_in``; ``x_t = silu(b_c + sum_k w_c[:, k] x_{t-3+k})``;
  ``[r | B | C] = x W_x``; ``dt = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t``; ``y_t = H_t C_t + D
  x_t``; ``out = (y silu(z)) W_out``.
- GMU: ``out = (silu(u W_1) * m_t) W_2``.
- differential attention: query pair ``p`` (heads ``2p, 2p + 1``), K/V pair ``i
  = p // 2`` (heads ``2i, 2i + 1``): ``a1 = softmax(q_{2p} k_{2i}^T / sqrt(dh))
  [v_{2i} | v_{2i+1}]``, ``a2 = softmax(q_{2p+1} k_{2i+1}^T / sqrt(dh)) [v_{2i} |
  v_{2i+1}]`` (causal; a window layer: positions ``t - W + 1 .. t``); ``lambda
  = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 l)``; ``o_p = rmsnorm(a1 - lambda a2; gamma) (1 - lambda_init)``.

Departures from the published code, each noted in the configuration's
``assumed``: the residual stream is float32 throughout (the published code
keeps it float32 after a Mamba layer only); ``A_log`` is read ``[N, d_inner]``,
as the program stores it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128    # queries a block of materialised scores
PAIR_GROUP = 5       # query pairs whose scores are materialised together
MLP_BLOCK = 4096     # positions a block of the MLP
HEAD_BLOCK = 1024    # positions a block of logits


def _through(lower):
    """Operands as the reference holds them: float32, or rounded through the
    dtype ``lower`` names first (only the control of a cell's limits lowers
    it: ``drivers/serve_family.py``)."""
    if lower is None:
        return lambda a: jnp.asarray(a).astype(jnp.float32)
    return lambda a: jnp.asarray(a).astype(jnp.float32).astype(
        jnp.dtype(lower)).astype(jnp.float32)


def _wide(a):
    return jnp.asarray(a).astype(jnp.float32)


def _layernorm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _mamba(h, p, f):
    """h [T, D] (normed, rounded) -> ``(out [T, D], y [T, d_inner])``."""
    T = h.shape[0]
    d_inner, taps = p["conv_w"].shape
    n = p["A_log"].shape[0]
    rank = p["w_dt"].shape[0]
    xz = h @ f(p["w_in"])
    x, z = xz[:, :d_inner], xz[:, d_inner:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, d_inner)), f(x)])
    w = _wide(p["conv_w"])
    x = f(jax.nn.silu(_wide(p["conv_b"]) + sum(
        w[:, k] * padded[k:k + T] for k in range(taps))))
    rbc = f(x @ f(p["w_x"]))
    dt = jax.nn.softplus(
        f(rbc[:, :rank]) @ f(p["w_dt"]) + _wide(p["b_dt"]))   # [T, d_inner]
    b, c = rbc[:, rank:rank + n], rbc[:, rank + n:]
    a = -jnp.exp(_wide(p["A_log"])).T                          # [d_inner, N]

    def position(held, inputs):  # the recurrence, one position at a time
        x_t, dt_t, b_t, c_t = inputs
        held = (jnp.exp(dt_t[:, None] * a) * held
                + (dt_t * x_t)[:, None] * b_t[None, :])
        return held, held @ c_t

    _, y = jax.lax.scan(position, jnp.zeros((d_inner, n)), (x, dt, b, c))
    y = y + _wide(p["D"]) * x
    return f(y * jax.nn.silu(z)) @ f(p["w_out"]), y


def _diff_attention(h, kv_from, p, f, *, n_heads, n_kv_heads, window,
                    lam_init, eps):
    """h [T, D] (normed, rounded): the queries' input; ``kv_from``: ``(k, v)
    [KV, T, dh]`` of the layer that owns them (None: this layer's own, which
    are also returned).  -> ``(out [T, D], (k, v))``."""
    T, D = h.shape
    dh = D // n_heads
    heads = lambda t, n: f(t).reshape(T, n, dh).transpose(1, 0, 2)  # noqa: E731
    q = heads(h @ f(p["wq"]) + _wide(p["bq"]), n_heads)
    if kv_from is None:
        kv_from = (heads(h @ f(p["wk"]) + _wide(p["bk"]), n_kv_heads),
                   heads(h @ f(p["wv"]) + _wide(p["bv"]), n_kv_heads))
    k, v = kv_from
    lam = (jnp.exp(jnp.sum(_wide(p["lq1"]) * _wide(p["lk1"])))
           - jnp.exp(jnp.sum(_wide(p["lq2"]) * _wide(p["lk2"]))) + lam_init)
    scale = 1.0 / math.sqrt(dh)
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    # the keys a block of queries can see: every one up to its end, or, in a
    # window layer, the window before its first query and the block itself
    # (keys laid after ``reach`` rows of padding, masked by their position)
    reach = -(-window // block) * block if window else 0
    span = reach + block if window else T
    pairs = n_heads // 2
    outs = []
    for g in range(0, pairs, PAIR_GROUP):  # a group of query pairs at a time
        ps = np.arange(g, min(g + PAIR_GROUP, pairs))
        q1, q2 = q[2 * ps], q[2 * ps + 1]
        padded = lambda t: jnp.pad(t, ((0, 0), (reach, 0), (0, 0)))  # noqa: E731
        k1, k2 = padded(k[2 * (ps // 2)]), padded(k[2 * (ps // 2) + 1])
        v1, v2 = padded(v[2 * (ps // 2)]), padded(v[2 * (ps // 2) + 1])

        def rows(lo):  # a block of queries against the keys it can see
            first = lo if window else 0
            cut = lambda t, n, at: jax.lax.dynamic_slice_in_dim(t, at, n, 1)  # noqa: E731
            i = lo + jnp.arange(block)[:, None]
            j = first - reach + jnp.arange(span)[None, :]
            mask = (j >= 0) & (j <= i) & ((j > i - window) if window else True)

            def weights(qs, ks):
                s = (cut(qs, block, lo) @ cut(ks, span, first).transpose(0, 2, 1)
                     ) * scale
                return f(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1))

            w1, w2 = weights(q1, k1), weights(q2, k2)
            u1, u2 = cut(v1, span, first), cut(v2, span, first)
            a1 = jnp.concatenate([w1 @ u1, w1 @ u2], -1)    # the four products
            a2 = jnp.concatenate([w2 @ u1, w2 @ u2], -1)
            o = a1 - lam * a2
            o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)
            return o * _wide(p["subln"]) * (1.0 - lam_init)

        got = jax.lax.map(rows, jnp.arange(0, T, block))    # [blocks, pairs, block, 2 dh]
        outs.append(got.transpose(1, 0, 2, 3).reshape(len(ps), T, 2 * dh))
    o = jnp.concatenate(outs, 0).transpose(1, 0, 2).reshape(T, D)
    return f(o) @ f(p["wo"]) + _wide(p["bo"]), kv_from


def _mlp(x, p, f, eps):
    rows = []
    for lo in range(0, x.shape[0], MLP_BLOCK):
        h = f(_layernorm(x[lo:lo + MLP_BLOCK], _wide(p["n2_w"]),
                         _wide(p["n2_b"]), eps))
        gu = h @ f(p["w_gate_up"])
        half = gu.shape[-1] // 2
        rows.append(f(jax.nn.silu(gu[:, :half]) * gu[:, half:]) @ f(p["w_down"]))
    return x + jnp.concatenate(rows)


@functools.partial(jax.jit, static_argnames=(
    "kind", "n_heads", "n_kv_heads", "window", "eps", "lower"))
def _layer(x, p, memory, kv, lam_init, *, kind, n_heads, n_kv_heads, window,
           eps, lower):
    """x [T, D] float32, p one layer's parameters as stored -> ``(x, memory,
    kv)``: the memory and the shared K/V pass through the layers that do not
    make them."""
    f = _through(lower)
    h = f(_layernorm(x, _wide(p["n1_w"]), _wide(p["n1_b"]), eps))
    if kind == "mamba":
        out, memory = _mamba(h, p, f)
    elif kind == "gmu":
        out = f(jax.nn.silu(h @ f(p["w1"])) * memory) @ f(p["w2"])
    else:
        out, own = _diff_attention(
            h, kv if kind == "cross" else None, p, f, n_heads=n_heads,
            n_kv_heads=n_kv_heads, window=window if kind == "window" else 0,
            lam_init=lam_init, eps=eps)
        kv = own if kind == "full" else kv
    return _mlp(x + out, p, f, eps), memory, kv


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, w, b, emb, *, eps, lower):
    f = _through(lower)
    return f(_layernorm(x, _wide(w), _wide(b), eps)) @ f(emb).T


# A result of this size or more is kept and handed out AGAIN by the next call
# that asks for the same shape.  The accepted driver
# (``drivers/serve_family.FamilyReference.check``) still holds the previous
# group's array while it asks for the next group's, and is done reading it
# by then: at the published vocabulary two of them (2 x 2 x 18,432 x 200,064
# float32 = 59 GB) do not fit the chip host's 40 GiB, one does.  Smaller
# results (every test's) are always fresh arrays.
REUSE_BYTES = 1 << 30
_kept: dict = {}


def _result(shape) -> np.ndarray:
    """A float32 array for the logits: zeros, lazily committed by the host."""
    if 4 * int(np.prod(shape)) < REUSE_BYTES:
        return np.zeros(shape, np.float32)
    if _kept.get("shape") != shape:
        _kept.clear()
        _kept.update(shape=shape, array=np.zeros(shape, np.float32))
    return _kept["array"]


def layer_kinds(n_layers: int) -> list:
    half = n_layers // 2
    return [("mamba" if l % 2 == 0 else "window") if l <= half
            else "full" if l == half + 1
            else ("gmu" if l % 2 == 0 else "cross") for l in range(n_layers)]


def layer_params(params, kinds: list, layer: int):
    """Layer ``layer``'s parameters out of the tree: the one full layer, or a
    slice of its kind's stack."""
    kind = kinds[layer]
    if kind == "full":
        return params["full"]
    at = kinds[:layer].count(kind)
    return jax.tree.map(lambda a: a[at], params[kind])


def logits(params, tokens, sizes: dict, lower=None):
    """tokens [B, T] int32 -> logits [B, T, V] float32, on the HOST (a NumPy
    array filled a block of positions at a time).  ``lower``: a dtype's name;
    every matmul operand (weights and activations) is rounded through it
    first, which is how the control of a cell's limits computes the reference
    "in a lower precision"."""
    kinds = layer_kinds(sizes["n_layers"])
    static = dict(n_heads=sizes["n_heads"], n_kv_heads=sizes["n_kv_heads"],
                  window=sizes["sliding_window"], eps=sizes["norm_eps"],
                  lower=lower)
    tokens = np.asarray(tokens)
    B, T = tokens.shape
    out = _result((B, T, params["tok_emb"].shape[0]))
    d_inner = params["mamba"]["conv_w"].shape[1]
    dh = params["tok_emb"].shape[1] // sizes["n_heads"]
    with jax.default_matmul_precision("highest"):
        for r, row in enumerate(tokens):  # a row of the batch at a time
            x = _through(lower)(params["tok_emb"][jnp.asarray(row)])
            memory = jnp.zeros((T, d_inner), jnp.float32)
            kv = (jnp.zeros((sizes["n_kv_heads"], T, dh), jnp.float32),) * 2
            for l, kind in enumerate(kinds):
                x, memory, kv = _layer(
                    x, layer_params(params, kinds, l), memory, kv,
                    jnp.float32(0.8 - 0.6 * math.exp(-0.3 * l)), kind=kind,
                    **static)
            # (the positions after a row's last token hold padding: every
            # layer is causal, nothing reads their logits, and they stay as
            # they are: pages of the result that are never touched cost the
            # host nothing)
            held = int(np.flatnonzero(row)[-1]) + 1 if row.any() else 1
            for lo in range(0, held, HEAD_BLOCK):
                out[r, lo:lo + HEAD_BLOCK] = np.asarray(_head(
                    x[lo:lo + HEAD_BLOCK], params["final_norm_w"],
                    params["final_norm_b"], params["tok_emb"],
                    eps=sizes["norm_eps"], lower=lower))
    return out
