"""Plain reference for EvaByte (``model_type: evabyte``, ``attention_class:
eva``; huggingface.co/EvaByte/EvaByte): the forward pass in straightforward
``jax.numpy``, float32, matmul precision "highest".  No cache, no kernels, no
merge of partial softmaxes, nothing from ``ray_tpu.models`` or ``ray_tpu.ops``:
a window's scores against its own keys and against the summaries of every
earlier window are MATERIALISED side by side and one softmax runs over the
row.

Computed in blocks so that 18,560 positions fit beside the served weights at
the published widths: a row of the batch at a time, a layer at a time (one
jitted function, its weights raised to float32 as it uses them), a window of
queries at a time and a group of heads at a time, the MLP a block of rows at a
time.

It reads the parameter TREE the program made (the weights are the program's,
the arithmetic is not): ``tok_emb [V, D]``, ``head [D, P x V]`` (head ``p``'s
outputs are columns ``[p V, (p + 1) V)``), ``final_norm [D]`` and ``blocks``,
leaves stacked over the layers: ``attn_norm ffn_norm [L, D]``, ``wq wk wv [L,
D, H x dh]``, ``wo [L, H x dh, D]``, ``eva_phi eva_mu [L, H, dh]``, ``w_gate
w_up [L, D, F]``, ``w_down [L, F, D]``.  ``sizes`` holds what shapes do not
say: ``window_size``, ``chunk_size``, ``rope_theta``, ``rms_eps``,
``vocab_size``.

The equations (``s = dh ** -0.5``; window of position ``j``: ``j // W``; a
CHUNK is ``c`` consecutive positions, ``W % c == 0``):

1. ``h = RMSNorm(x) (1 + g)``; ``q, k, v = h Wq, h Wk, h Wv`` (no bias), a
   head ``dh`` values; rotary (all ``dh`` values, absolute positions,
   dimension ``i`` paired with ``i + dh / 2``) on ``q`` and ``k``.
2. Pooling, a head ``a``, a chunk ``C`` of a COMPLETE window, on the rotated
   keys: ``p_j = softmax_{j in C}(s k_j . phi_a)``; ``K~_C = sum_j p_j k_j +
   mu_a``; ``V~_C = sum_j p_j v_j``.
3. Query ``i`` attends ``E(i) = {j : j // W == i // W, j <= i}`` exactly and
   ``R(i) = {C : C's window < i // W}`` by their summaries, under ONE softmax:
   ``o_i = (sum_E e^{s q_i.k_j} v_j + sum_R e^{s q_i.K~_C} V~_C) / (sum_E
   e^{s q_i.k_j} + sum_R e^{s q_i.K~_C})``.
4. ``y = x + o Wo``; ``x' = y + Wdown(silu(Wgate h') * Wup h')``, ``h' =
   RMSNorm(y) (1 + g')``.
5. Final norm (``1 + g`` again), ``logits = h Whead``; head ``p`` predicts
   byte ``i + 1 + p``.

Everything here is float32, so ``fp32_skip_add``, ``mixedp_attn`` and
``fp32_logits`` say nothing more.  Departures from the published description,
each because the ``config.json`` does not say (the configuration's
``assumed``): the pooling's form and the place of ``s`` in it; pooling after
the rotation; the rotary pairing; the order of the heads in ``Whead``'s
columns; windows as absolute multiples of ``W``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HEAD_GROUP = 8     # heads whose scores are materialised together
ROW_BLOCK = 2048   # at most this many rows of the MLP at a time


def _through(lower):
    """Operands as the reference holds them: float32, or rounded through the
    dtype ``lower`` names first (only the control of a cell's limits lowers
    it: ``drivers/serve_family.py``)."""
    if lower is None:
        return lambda a: jnp.asarray(a).astype(jnp.float32)
    return lambda a: jnp.asarray(a).astype(jnp.float32).astype(
        jnp.dtype(lower)).astype(jnp.float32)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    """x [H, T, d] at positions 0..T-1, dimension i paired with i + d / 2."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _block_of(T: int, most: int) -> int:
    """The largest whole number of 128s up to ``most`` that divides ``T`` (T
    itself where it is no multiple of 128: a test's short sequence)."""
    if T % 128:
        return T
    return max(b for b in range(128, min(T, most) + 1, 128) if T % b == 0)


def _by_head_group(fn, *per_head):
    """``fn`` over groups of ``HEAD_GROUP`` heads (leading axis), one group's
    scores alive at a time."""
    H = per_head[0].shape[0]
    if H % HEAD_GROUP:
        return fn(*per_head)
    grouped = [a.reshape(H // HEAD_GROUP, HEAD_GROUP, *a.shape[1:])
               for a in per_head]
    out = jax.lax.map(lambda args: fn(*args), tuple(grouped))
    return out.reshape(H, *out.shape[2:])


def _attention(q, k, v, phi, mu, window, chunk, f):
    """q, k, v [H, T, dh] rotated -> [H, T, dh]: step 2 and 3 above, a window
    of queries at a time."""
    H, T, dh = q.shape
    s = dh ** -0.5
    outs, far_k, far_v = [], [], []
    for lo in range(0, T, window):
        hi = min(T, lo + window)
        qw, kw, vw = (f(a[:, lo:hi]) for a in (q, k, v))
        causal = jnp.tril(jnp.ones((hi - lo, hi - lo), bool))
        sk = jnp.concatenate(far_k, 1) if far_k else jnp.zeros((H, 0, dh))
        sv = jnp.concatenate(far_v, 1) if far_v else jnp.zeros((H, 0, dh))

        def rows(qw, kw, vw, sk, sv):
            near = jnp.where(causal, jnp.einsum("htd,hsd->hts", qw, kw) * s,
                             -jnp.inf)
            far = jnp.einsum("htd,hcd->htc", qw, f(sk)) * s
            p = f(jax.nn.softmax(jnp.concatenate([near, far], -1), -1))
            n = kw.shape[1]
            return (jnp.einsum("hts,hsd->htd", p[..., :n], vw)
                    + jnp.einsum("htc,hcd->htd", p[..., n:], f(sv)))

        outs.append(_by_head_group(rows, qw, kw, vw, sk, sv))
        if hi - lo == window:  # a complete window: pooled for those after it
            kc, vc = (a.reshape(H, window // chunk, chunk, dh) for a in (kw, vw))
            p = jax.nn.softmax(
                (kc * f(phi)[:, None, None, :]).sum(-1) * s, -1)[..., None]
            far_k.append((f(p) * kc).sum(2) + f(mu)[:, None, :])
            far_v.append((f(p) * vc).sum(2))
    return jnp.concatenate(outs, 1)


@functools.partial(jax.jit, static_argnames=("window", "chunk", "theta", "eps",
                                             "lower"))
def _layer(x, p, *, window, chunk, theta, eps, lower):
    """One layer over one sequence ``x [T, D]`` float32."""
    f = _through(lower)
    T, D = x.shape
    H, dh = p["eva_phi"].shape
    heads = lambda t: t.reshape(T, H, dh).transpose(1, 0, 2)  # noqa: E731
    h = f(_rmsnorm(x, f(p["attn_norm"]), eps))
    q = _rope(heads(h @ f(p["wq"])), theta)
    k = _rope(heads(h @ f(p["wk"])), theta)
    o = _attention(q, k, heads(h @ f(p["wv"])), p["eva_phi"], p["eva_mu"],
                   window, chunk, f)
    y = x + f(o.transpose(1, 0, 2).reshape(T, H * dh)) @ f(p["wo"])

    def mlp(rows):
        h = f(_rmsnorm(rows, f(p["ffn_norm"]), eps))
        return rows + f(jax.nn.silu(h @ f(p["w_gate"])) * (h @ f(p["w_up"]))
                        ) @ f(p["w_down"])

    block = _block_of(T, ROW_BLOCK)
    return jax.lax.map(mlp, y.reshape(T // block, block, D)).reshape(T, D)


def logits(params, tokens, sizes, lower=None, all_heads: bool = False):
    """``tokens [B, T]`` -> float32 logits ``[B, T, V]`` of head 0, the next
    byte's (``all_heads``: ``[B, T, P, V]``), as a numpy array, a row of the
    batch at a time.  ``lower``: see :func:`_through`."""
    f = _through(lower)
    V = sizes["vocab_size"]
    blocks = params["blocks"]
    n_layers = blocks["wq"].shape[0]
    out = []
    with jax.default_matmul_precision("highest"):
        for row in np.asarray(tokens):
            x = f(params["tok_emb"])[jnp.asarray(row)]
            for l in range(n_layers):
                x = _layer(x, jax.tree.map(lambda a: a[l], blocks),
                           window=sizes["window_size"], chunk=sizes["chunk_size"],
                           theta=float(sizes["rope_theta"]),
                           eps=float(sizes["rms_eps"]), lower=lower)
            h = f(_rmsnorm(x, f(params["final_norm"]), float(sizes["rms_eps"])))
            head = params["head"] if all_heads else params["head"][:, :V]
            got = np.asarray(h @ f(head))
            out.append(got.reshape(len(row), -1, V) if all_heads else got)
    return np.stack(out)
