"""Plain reference for the dots3-note family (``model_type: dots3_note``,
huggingface.co/dots-studio/dots3-note-prev): the forward pass in
straightforward ``jax.numpy``, float32, matmul precision "highest".  No cache,
no kernels, no grouped matmul, no absorbed form, no bisection, nothing from
``ray_tpu.models`` or ``ray_tpu.ops``: both kinds of latent attention are
written UN-absorbed (every position's ``k_nope`` and ``v`` up-projected from
its latent row) with materialised masked scores, the index scores and the
selection are written out (``lax.top_k`` over a query's causal scores), the
experts are a loop with a dense mask.

Computed in blocks so that 17,408 positions fit beside the served weights: a
row of the batch at a time, a group of heads at a time, a block of queries at
a time against all keys (a sliding layer: against the keys its window can
reach), and each row's logits are brought to the host as they are made.

It reads the parameter TREE the program made (the weights are the program's,
the arithmetic is not): ``tok_emb [V, D]``, ``head [D, V]``, ``final_norm``
and ``layers``, one dict a layer: ``attn_norm ffn_norm w_dq [D, rq] q_norm
w_uq [rq, H * (nope + pe)] w_dkv [D, rkv + pe] kv_norm w_uk [H, nope, rkv]
w_uv [H, rkv, dv] w_g [D, H] wo [H * dv, D]``; a full layer also ``w_qi [rq,
Hi * di] w_ki [D, di] ki_norm ki_norm_bias w_wi [D, Hi]``; and either ``w_gate
w_up w_down`` (a dense layer) or ``router [D, E] router_bias [E] ew_gate ew_up
[held, D, F] ew_down [held, F, D] sw_gate sw_up sw_down`` (a sparse one).

``sizes`` holds what shapes do not say: ``layer_types``, per kind of layer
(``full`` / ``sliding``) ``qk_nope_head_dim`` and ``rope_theta``,
``index_n_heads``, ``index_topk``, ``index_rope_dim``, ``sliding_window``,
``lora_rescale``, ``top_k``, ``routed_scale``, ``first_expert``, ``rms_eps``.

The equations (``n`` = RMSNorm with a learned scale; pre-norm residuals):

- ``c_q = r_q n(W_dq h)``; ``[q_nope | q_pe] = W_uq c_q`` a head; ``[c | k_pe]
  = W_dkv h``; ``c = r_kv n(c)``; ``q_pe, k_pe`` rotated (plain rotary; ``k_pe``
  is one key for all heads); ``k_nope = W_uk c``, ``v = W_uv c`` a head; ``r_q
  = sqrt(D / rq)``, ``r_kv = sqrt(D / rkv)`` where ``lora_rescale``.
- full layer: ``qI = W_qI c_q`` (Hi heads x di), ``kI = LayerNorm(W_kI h)``,
  rotary on the first ``index_rope_dim`` values of both, ``w = W_w h``; ``I[t,
  s] = sum_h w[t, h] relu(qI[t, h] . kI[s]) di ** -0.5 Hi ** -0.5``; ``S_t`` =
  the ``index_topk`` positions ``s <= t`` of largest ``I[t, s]`` (``lax.top_k``:
  the lower position first among equals; all positions while ``t <
  index_topk``).  ``s_ij = scale (q_i . k_j)`` for ``j in S_i``; softmax over
  ``S_i``.
- sliding layer: ``s_ij`` for ``i - window < j <= i``.
- both: ``scale = (nope + pe) ** -0.5``; ``g = sigmoid(W_g h)`` a head; ``x
  += W_o (g * sum_j p_ij v_j)``.
- dense FFN: ``x += W_down(silu(W_gate h) * W_up h)``.
- sparse FFN: ``s = sigmoid(h W_r)``, ``sel = top_k(s + b)``, ``g_i =
  routed_scale * s_i / sum_{j in sel} s_j``, ``x += E_shared(h) + sum_{i in
  sel, in the tree} g_i E_i(h)``.
- head: final RMSNorm, the output matrix.

Departures from the published description, each because the config.json does
not say: pre-norm placement and the norms on the two latents (the DeepSeek-V3
layer's); the selection bias ``b`` enters the choice only; the rotary pairs
dimension ``2i`` with ``2i + 1``; the rescale read as factors on the normed
latents; the gate read as one sigmoid a head on the attention's output; the
window as ``i - window < j <= i``; the index keys in the served dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HEAD_GROUP = 8      # heads whose scores are materialised together
QUERY_BLOCK = 1024  # at most this many queries a block of materialised scores
INDEX_BLOCK = 256   # the same for the index scores, all index heads at once


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


def _layernorm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _rope(x, theta):
    """x [.., T, d] at positions 0..T-1, dimension 2i paired with 2i + 1."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _swiglu(h, w_gate, w_up, w_down, f):
    return f(jax.nn.silu(h @ f(w_gate)) * (h @ f(w_up))) @ f(w_down)


def _block_of(T: int, most: int) -> int:
    """The largest whole number of 128s up to ``most`` that divides ``T`` (T
    itself where it is no multiple of 128: a test's short sequence)."""
    return next((b for b in range(most, 0, -128) if T % b == 0), T)


def selection(h, c_q, p, f, *, index_heads, index_topk, rope_dim, theta, eps):
    """The full layer's chosen positions, ``keep [T, T]`` bool: row ``t`` the
    ``index_topk`` positions ``s <= t`` of largest index score; every causal
    position where the sequence is no longer than ``index_topk``."""
    T = h.shape[0]
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    if T <= index_topk:
        return causal
    rotate = lambda t: jnp.concatenate(  # noqa: E731
        [_rope(t[..., :rope_dim], theta), t[..., rope_dim:]], -1)
    q = (c_q @ f(p["w_qi"])).reshape(T, index_heads, -1).transpose(1, 0, 2)
    q = f(rotate(q))                                              # [Hi, T, di]
    k = f(rotate(_layernorm(h @ f(p["w_ki"]), f(p["ki_norm"]),
                            f(p["ki_norm_bias"]), eps)))          # [T, di]
    w = h @ f(p["w_wi"])                                          # [T, Hi]
    di = q.shape[-1]
    block = _block_of(T, INDEX_BLOCK)

    def rows(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, block, 1)
        wb = jax.lax.dynamic_slice_in_dim(w, first, block, 0)
        products = jax.nn.relu(jnp.einsum("hqd,kd->hqk", qb, k))
        scores = jnp.einsum("hqk,qh->qk", products, wb) * (
            di ** -0.5 * index_heads ** -0.5)
        seen = jnp.arange(T)[None, :] <= (first + jnp.arange(block))[:, None]
        _, top = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), index_topk)
        chosen = jnp.zeros((block, T), bool).at[
            jnp.arange(block)[:, None], top].set(True)
        return chosen & seen  # a row shorter than index_topk: all it has

    keep = jax.lax.map(rows, jnp.arange(T // block) * block)
    return keep.reshape(T, T)


@functools.partial(jax.jit, static_argnames=(
    "window", "nope", "rope_theta", "index_heads", "index_topk",
    "index_rope_dim", "lora_rescale", "top_k", "routed_scale", "first_expert",
    "rms_eps", "lower"))
def _layer(x, p, *, window, nope, rope_theta, index_heads, index_topk,
           index_rope_dim, lora_rescale, top_k, routed_scale, first_expert,
           rms_eps, lower):
    """x [1, T, D] float32, p one layer's parameters as stored; ``window``: 0
    for a full layer."""
    f = _through(lower)
    _, T, D = x.shape
    rq, rkv = p["q_norm"].shape[0], p["kv_norm"].shape[0]
    n_heads = p["w_uk"].shape[0]
    r_q, r_kv = ((D / rq) ** 0.5, (D / rkv) ** 0.5) if lora_rescale else (1.0, 1.0)
    h = f(_rmsnorm(x, f(p["attn_norm"]), rms_eps))[0]            # [T, D]
    c_q = f(r_q * _rmsnorm(h @ f(p["w_dq"]), f(p["q_norm"]), rms_eps))
    ckv = h @ f(p["w_dkv"])                                       # [T, rkv + pe]
    c = f(r_kv * _rmsnorm(ckv[:, :rkv], f(p["kv_norm"]), rms_eps))
    k_pe = f(_rope(ckv[:, rkv:], rope_theta))                     # [T, pe]
    pe = k_pe.shape[-1]
    scale = (nope + pe) ** -0.5
    if window:
        keep = None
    else:
        keep = selection(h, c_q, p, f, index_heads=index_heads,
                         index_topk=index_topk, rope_dim=index_rope_dim,
                         theta=rope_theta, eps=rms_eps)
    gate = jax.nn.sigmoid(h @ f(p["w_g"]))                        # [T, H]
    w_uq = f(p["w_uq"]).reshape(-1, n_heads, nope + pe)
    block = _block_of(T, QUERY_BLOCK)
    # a sliding layer's block of queries reaches back window - 1 keys
    reach = T if not window else min(T, block + -(-window // 128) * 128)
    outs = []
    for g in range(0, n_heads, HEAD_GROUP):  # a group of heads at a time
        heads = slice(g, min(g + HEAD_GROUP, n_heads))
        q = jnp.einsum("tr,rhd->htd", c_q, w_uq[:, heads])
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], rope_theta)], -1)
        k_nope = jnp.einsum("tc,hdc->htd", c, f(p["w_uk"][heads]))
        k = jnp.concatenate([
            k_nope, jnp.broadcast_to(k_pe, (k_nope.shape[0], T, pe))], -1)
        v = f(jnp.einsum("tc,hcv->htv", c, f(p["w_uv"][heads])))
        q, k = f(q), f(k)

        def rows(first, q=q, k=k, v=v):  # a block of queries and its keys
            start = jnp.clip(first + block - reach, 0, T - reach)
            i = (first + jnp.arange(block))[:, None]
            j = (start + jnp.arange(reach))[None, :]
            qb = jax.lax.dynamic_slice_in_dim(q, first, block, 1)
            kb = jax.lax.dynamic_slice_in_dim(k, start, reach, 1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, reach, 1)
            s = (qb @ kb.transpose(0, 2, 1)) * scale
            if window:
                mask = (j <= i) & (j > i - window)
            else:
                mask = jax.lax.dynamic_slice_in_dim(keep, first, block, 0) & (j <= i)
            s = jnp.where(mask, s, -jnp.inf)
            return f(jax.nn.softmax(s, axis=-1)) @ vb

        out = jax.lax.map(rows, jnp.arange(T // block) * block)   # [n, h, block, dv]
        outs.append(out.transpose(1, 0, 2, 3).reshape(out.shape[1], T, -1))
    o = jnp.concatenate(outs, 0) * gate.T[:, :, None]             # [H, T, dv]
    o = o.transpose(1, 0, 2).reshape(T, -1)
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


def layer_statics(sizes: dict, layer: int, lower=None) -> dict:
    """:func:`_layer`'s keywords for layer ``layer`` from ``sizes`` (hashable:
    jit closes over them)."""
    window = sizes["layer_types"][layer] == "sliding_attention"
    kind = sizes["sliding" if window else "full"]
    return dict(
        window=sizes["sliding_window"] if window else 0,
        nope=kind["qk_nope_head_dim"], rope_theta=float(kind["rope_theta"]),
        index_heads=sizes["index_n_heads"], index_topk=sizes["index_topk"],
        index_rope_dim=sizes["index_rope_dim"],
        lora_rescale=bool(sizes["lora_rescale"]), top_k=sizes["top_k"],
        routed_scale=sizes["routed_scale"], first_expert=sizes["first_expert"],
        rms_eps=sizes["rms_eps"], lower=lower)


def logits(params, tokens, sizes: dict, lower=None):
    """tokens [B, T] int32 -> logits [B, T, V] float32, on the HOST (numpy).
    ``lower``: a dtype's name; every matmul operand (weights and activations)
    is rounded through it first, which is how the control of a cell's limits
    computes the reference "in a lower precision"."""
    out = []
    with jax.default_matmul_precision("highest"):
        for row in np.asarray(tokens):  # a row of the batch at a time
            x = _through(lower)(params["tok_emb"][jnp.asarray(row)[None]])
            for l, p in enumerate(params["layers"]):
                x = _layer(x, p, **layer_statics(sizes, l, lower))
            out.append(np.asarray(_head(
                x, params["final_norm"], params["head"],
                rms_eps=sizes["rms_eps"], lower=lower)[0]))
    return np.stack(out)
