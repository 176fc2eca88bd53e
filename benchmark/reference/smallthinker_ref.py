"""Plain reference for the SmallThinker family as it is TRAINED: the forward
pass, the loss and its gradients in float32 ``jax.numpy`` at matmul precision
"highest".  No kernel, no sharding rule, no batching (a sequence at a time),
nothing of the program's (``ray_tpu.models.smallthinker``) but the shape of
its parameter tree.

Layer ``l`` with input ``x`` (the published description; departures below)::

    z   = x W_r                            # router logits from the layer's INPUT, un-normed
    S   = top_k(z);  g = softmax(z[S])     # = softmax over all E, renormalised over the chosen k
    h   = RMSNorm(x; w_a)
    q, k, v = h W_q, h W_k, h W_v          # n_heads | n_kv_heads | n_kv_heads of head_dim
    if rope_layout[l]: rotary(q, k)        # rotate-half, every dimension, base rope_theta
    o   = causal softmax(q k^T / sqrt(head_dim)) v;  i - window < j <= i if sliding_window_layout[l]
    x   = x + o W_o
    x   = x + sum_{e in S} g_e W_d,e (relu(W_g,e h2) * W_u,e h2),   h2 = RMSNorm(x; w_f)

    ce  = mean_t -log softmax(RMSNorm(x_L; w) W_head)[target_t]
    aux = sum_l E sum_e f_le P_le;   L = ce + aux_weight * aux
    f_le = share of the step's (token, slot) pairs routed to e (no gradient)
    P_le = mean over the step's tokens of softmax(z_t)[e]

How it is written, and why:

- masked scores are written out a block of ``Q_BLOCK`` queries at a time
  against ALL the keys, the blocks as one jitted loop (one dispatch a layer)
  whose body is under ``jax.checkpoint``, as the loop over the experts is:
  the backward pass then holds one block's scores and one expert's
  intermediates, which is what lets the reference run beside the program at
  the cell's own sizes.  Neither changes a value.
- the experts are a loop over the experts, each over the rows the router sent
  it (gathered; every expert padded with a zero row to the fullest one's whole
  ``ROW_BUCKET``s so that few shapes compile): the same sum as a dense mask
  over every token, at ``k / E`` of its arithmetic.  That needs the CHOICE ``S`` as concrete
  numbers, so the forward pass runs eagerly, and a gradient is taken with the
  choice of a first, undifferentiated pass handed in (the choice carries no
  gradient: the published gates are a softmax over the chosen logits).
- :func:`grad_norms` differentiates with respect to the NAMED leaves only
  (every other parameter is a constant), a sequence at a time: ``f`` is
  gradient-free and ``P`` a mean, so once the first pass has the step's ``f``
  the loss is a sum over sequences.
- ``lower`` (:func:`loss` alone: the forward pass): a dtype name.  Every
  matmul operand is rounded through it first (the control of a cell's limits:
  the nearest precision below the configuration's must come out NOT correct).
  No gradient is taken through the rounding: a cotangent rounded through
  float8 flushes to zero, and a norm of exactly 0 separates nothing.

``sizes``: ``n_heads, n_kv_heads, head_dim, top_k, rope_layout,
sliding_window_layout, sliding_window, rope_theta, rms_eps, aux_weight``.
"""

from __future__ import annotations


from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_ACT = {"relu": jax.nn.relu, "silu": jax.nn.silu}
Q_BLOCK = 512
ROW_BUCKET = 1024
NEG = -1e30


def _mm(a, b, lower=None):
    if lower:
        a, b = (t.astype(lower).astype(jnp.float32) for t in (a, b))
    return jnp.matmul(a, b, precision="highest")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, base):
    """``x [T, heads, d]``: rotate-half rotary at positions 0..T-1."""
    t, _, d = x.shape
    inv = 1.0 / (base ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = jnp.asarray(np.arange(t)[:, None] * inv, jnp.float32)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def _attend_block(q, k, v, first, b, window, lower):
    """The ``b`` queries of ``q [T, H, d]`` from position ``first`` on against
    all of ``k, v [T, KV, d]``, each KV head serving ``H / KV`` query heads."""
    q = jax.lax.dynamic_slice_in_dim(q, first, b)
    (_, h, d), kv = q.shape, k.shape[1]
    if lower:
        q, k = (t.astype(lower).astype(jnp.float32) for t in (q, k))
    s = jnp.einsum("qngd,knd->ngqk", q.reshape(b, kv, h // kv, d), k,
                   precision="highest") * d ** -0.5
    i = first + jnp.arange(b)[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    mask = (j <= i) & ((j > i - window) if window else True)
    p = jax.nn.softmax(jnp.where(mask, s, NEG), axis=-1)
    if lower:
        p, v = (t.astype(lower).astype(jnp.float32) for t in (p, v))
    return jnp.einsum("ngqk,knd->qngd", p, v, precision="highest").reshape(b, h, d)


@partial(jax.jit, static_argnums=(3, 4))
def _attention(q, k, v, window, lower):
    """Masked scores written out a block of ``Q_BLOCK`` queries at a time
    against all the keys.  (One jitted loop over the blocks, each under
    ``jax.checkpoint``: one dispatch a layer, and the backward pass holds ONE
    block's scores; neither changes a value.)"""
    t = q.shape[0]
    block = min(Q_BLOCK, t)
    one = jax.checkpoint(
        lambda first: _attend_block(q, k, v, first, block, window, lower))
    return jax.lax.map(one, jnp.arange(0, t, block)).reshape(q.shape)


def route(z, top_k: int) -> np.ndarray:
    """The ``top_k`` largest logits' experts ``[T, k]``, as numbers."""
    return np.asarray(jax.lax.top_k(z, top_k)[1])


@partial(jax.jit, static_argnums=(8, 9))
def _experts_loop(h, rows, gate, w_gate_up, w_down, named, named_gate_up,
                  named_down, act, lower):
    """``y [T, D]``: a loop over the experts, each over the rows the router
    sent it: ``rows [E, R]`` (token indices, ``T`` for padding), ``gate [E,
    R]`` (0 for padding).  ``named [S]``: experts whose matrices are
    ``named_gate_up [S, D, 2F]`` / ``named_down [S, F, D]`` and not the leaf's
    (the ones a gradient is taken of).  (One jitted loop, each expert under
    ``jax.checkpoint``: see :func:`_attention`.)"""
    t, d = h.shape
    f = w_down.shape[1]
    hp = jnp.concatenate([h, jnp.zeros((1, d), h.dtype)])   # row t: padding

    @jax.checkpoint
    def one(e, rows, gate):
        w_gu, w_d = w_gate_up[e], w_down[e]
        for i in range(named.shape[0]):
            w_gu = jnp.where(named[i] == e, named_gate_up[i], w_gu)
            w_d = jnp.where(named[i] == e, named_down[i], w_d)
        gu = _mm(hp[rows], w_gu, lower)
        return _mm(_ACT[act](gu[:, :f]) * gu[:, f:], w_d, lower) * gate[:, None]

    def step(y, per_expert):
        e, rows, gate = per_expert
        return y.at[rows].add(one(e, rows, gate)), None

    y, _ = jax.lax.scan(step, jnp.zeros((t + 1, d), jnp.float32),
                        (jnp.arange(rows.shape[0]), rows, gate))
    return y[:t]


def _experts(leaf, named, l, h, z, chosen, act, lower):
    """``sum_{e in S} g_e W_d,e (act(W_g,e h) * W_u,e h)`` for ``h [T, D]``,
    ``chosen [T, k]`` the concrete choice: every expert's rows as one padded
    table (whole ``ROW_BUCKET``s of the fullest expert, so that few shapes
    compile), then :func:`_experts_loop`.  ``named``: the experts of this
    layer whose matrices are differentiated on their own, ``{e: (gate_up,
    down)}``."""
    t = h.shape[0]
    w_gate_up, w_down = leaf("layers", l, "ew_gate_up"), leaf("layers", l, "ew_down")
    n_experts = w_down.shape[0]
    counts = np.bincount(chosen.reshape(-1), minlength=n_experts)
    width = max(ROW_BUCKET, -(-int(counts.max()) // ROW_BUCKET) * ROW_BUCKET)
    rows = np.full((n_experts, width), t, np.int32)
    slots = np.zeros((n_experts, width), np.int32)
    for e in np.nonzero(counts)[0]:
        tok, slot = np.nonzero(chosen == e)
        rows[e, :len(tok)], slots[e, :len(tok)] = tok, slot
    gates = jax.nn.softmax(jnp.take_along_axis(z, jnp.asarray(chosen), -1), -1)
    gates = jnp.concatenate([gates, jnp.zeros((1, gates.shape[1]), gates.dtype)])
    stack = lambda i, like: (  # noqa: E731
        jnp.stack([named[e][i] for e in sorted(named)]) if named
        else jnp.zeros((0,) + like.shape[1:], jnp.float32))
    return _experts_loop(
        h, rows, gates[rows, slots], w_gate_up, w_down,
        np.asarray(sorted(named), np.int32), stack(0, w_gate_up),
        stack(1, w_down), act, lower)


def _layer(leaf, l, x, sizes, chosen, lower):
    """One layer for one sequence ``x [T, D]`` under the choice ``chosen``
    (None: made here, eagerly) -> ``(x, z, chosen)``."""
    H, KV, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    eps, t = sizes["rms_eps"], x.shape[0]
    w = lambda name: leaf("layers", l, name)  # noqa: E731
    z = jnp.matmul(x, w("router"), precision="highest")   # never lowered: the choice
    if chosen is None:
        chosen = route(z, sizes["top_k"])
    h = _rms(x, w("attn_norm"), eps)
    q = _mm(h, w("wq"), lower).reshape(t, H, hd)
    k = _mm(h, w("wk"), lower).reshape(t, KV, hd)
    v = _mm(h, w("wv"), lower).reshape(t, KV, hd)
    if sizes["rope_layout"][l]:
        q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
    window = sizes["sliding_window"] * sizes["sliding_window_layout"][l]
    o = _attention(q, k, v, window, lower)
    x = x + _mm(o.reshape(t, H * hd), w("wo"), lower)
    x = x + _experts(leaf, leaf.named(l), l, _rms(x, w("ffn_norm"), eps), z,
                     chosen, sizes.get("activation", "relu"), lower)
    return x, z, chosen


def _leaf_of(params, given=None):
    """``leaf(*path)``: the parameter at ``path``, from ``given`` (the leaves a
    gradient is taken of, by path) if it is there; ``leaf.named(l)``: the
    experts of layer ``l`` that ``given`` holds on their own (paths that end
    in an expert's number), ``{e: (gate_up, down)}``, either matrix the
    leaf's own where only the other is given."""
    given = given or {}

    def leaf(*path):
        if path in given:
            return given[path]
        tree = params
        for key in path:
            tree = tree[key]
        return jnp.asarray(tree, jnp.float32)

    def named(l):
        experts = {path[3] for path in given
                   if len(path) == 4 and path[:2] == ("layers", l)}
        return {e: tuple(
            given.get(("layers", l, name, e), leaf("layers", l, name)[e])
            for name in ("ew_gate_up", "ew_down")) for e in experts}

    leaf.named = named
    return leaf


def sequence(params, tokens, targets, sizes, *, chosen=None, given=None,
             lower=None):
    """One sequence ``tokens [T]`` -> ``(nll_sum, [sum_t softmax(z_t) [E] a
    layer], [chosen [T, k] a layer])``.  ``chosen``: a first pass's, a layer
    each (None: chosen here, eagerly)."""
    leaf = _leaf_of(params, given)
    n_layers = len(params["layers"])
    x = jnp.asarray(leaf("tok_emb"))[jnp.asarray(tokens)]
    probs, made = [], []
    for l in range(n_layers):
        x, z, picked = _layer(
            leaf, l, x, sizes, None if chosen is None else chosen[l], lower)
        made.append(picked)
        probs.append(jax.nn.softmax(z, axis=-1).sum(0))
    logits = _mm(_rms(x, leaf("final_norm"), sizes["rms_eps"]), leaf("head"), lower)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, jnp.asarray(targets)[:, None], -1)[:, 0]
    return nll.sum(), probs, made


def _aux(pairs, probs, n_tokens: int, top_k: int):
    """``sum_l E sum_e f_le P_le`` from the step's counts: ``pairs [L, E]``,
    ``probs [L, E]`` (sums over tokens)."""
    f = jnp.asarray(pairs, jnp.float32) / (n_tokens * top_k)
    return (f.shape[1] * f * (probs / n_tokens)).sum()


def _count(chosen, n_experts: int) -> np.ndarray:
    return np.stack([np.bincount(c.reshape(-1), minlength=n_experts) for c in chosen])


def loss(params, inputs, targets, sizes, lower=None, keep=None):
    """``(ce, aux)`` of the step ``inputs, targets [B, T]`` as floats;
    ``keep``: a dict that receives ``pairs [L, E]`` and every sequence's
    choice (:func:`grad_norms`' first pass)."""
    inputs, targets = np.asarray(inputs), np.asarray(targets)
    n_experts = params["layers"][0]["router"].shape[-1]
    nll, probs, pairs, chosen = 0.0, 0.0, 0, []
    with jax.default_matmul_precision("highest"):
        for tok, tgt in zip(inputs, targets):
            n, p, c = sequence(params, tok, tgt, sizes, lower=lower)
            nll, probs = nll + n, probs + jnp.stack(p)
            pairs = pairs + _count(c, n_experts)
            chosen.append(c)
        aux = _aux(pairs, probs, inputs.size, sizes["top_k"])
    ce, aux = float(nll / inputs.size), float(aux)
    if keep is not None:
        keep.update(pairs=pairs, chosen=chosen, ce=ce, aux=aux)
    return ce, aux


def grads(params, inputs, targets, sizes, leaves, kept=None):
    """``{name: dL/d leaf}`` for ``leaves {name: path}`` (a path: the keys and
    indices from the tree's root), ``L = ce + aux_weight * aux`` of the whole
    step, a sequence at a time under the first pass's ``f`` and choice
    (``kept``: a dict that receives that pass's ``ce``, ``aux``, ``pairs``)."""
    inputs, targets = np.asarray(inputs), np.asarray(targets)
    kept = {} if kept is None else kept
    loss(params, inputs, targets, sizes, keep=kept)
    n, top_k = inputs.size, sizes["top_k"]
    plain = _leaf_of(params)
    given = {tuple(path): (plain(*path[:-1])[path[-1]] if len(path) == 4
                           else plain(*path)) for path in leaves.values()}

    def objective(given, tok, tgt, chosen):
        nll, probs, _ = sequence(params, tok, tgt, sizes, chosen=chosen,
                                 given=given)
        return nll / n + sizes["aux_weight"] * _aux(
            kept["pairs"], jnp.stack(probs), n, top_k)

    total = None
    with jax.default_matmul_precision("highest"):
        for tok, tgt, chosen in zip(inputs, targets, kept["chosen"]):
            g = jax.grad(objective)(given, tok, tgt, chosen)
            total = g if total is None else jax.tree.map(jnp.add, total, g)
    return {name: total[tuple(path)] for name, path in leaves.items()}


def grad_norms(params, inputs, targets, sizes, leaves, kept=None):
    """``{name: ||dL/d leaf||}``: :func:`grads`' norms, as floats."""
    return {name: float(jnp.sqrt(jnp.sum(jnp.square(g)))) for name, g in grads(
        params, inputs, targets, sizes, leaves, kept).items()}
