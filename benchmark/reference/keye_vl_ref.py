"""Plain reference for the Keye-VL-2.0 family (``model_type: KeyeVL2``,
huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B): tower, merger, three-axis
positions, indexer, selection, attention, experts and head in straightforward
``jax.numpy``, float32, matmul precision "highest".  No cache, no kernels, no
grouped matmul, no bisection, nothing from ``ray_tpu.models`` or
``ray_tpu.ops``: the selection is ``lax.top_k`` over a query's causal index
scores, the attention is materialised masked scores, the experts are a loop
over all of them with a dense mask, the position table's interpolation is a
gather and a lerp written out.

Computed in blocks so that 17,408 positions fit beside the served weights at
the published widths: a row of the batch at a time, the tower a FRAME at a
time, a group of heads and a block of queries at a time against all keys, an
expert at a time, and each row's logits are brought to the host as they are
made.

It reads the parameter TREE the program made (the weights are the program's,
the arithmetic is not): ``tok_emb [V, D]``, ``head [D, V]``, ``final_norm``;
``layers``, one dict a layer: ``attn_norm ffn_norm q_norm k_norm [dh] wq [D, H
dh] wk wv [D, KV dh] wo [H dh, D] w_qi [D, Hi di] w_ki [D, di] ki_norm
ki_norm_bias w_wi [D, Hi] router [D, E] ew_gate ew_up [E, D, F] ew_down [E, F,
D]``; ``vision``: ``patch_w [588, Dv] patch_b pos_table [S, S, Dv]``, ``blocks``
(leaves stacked ``[layers, ...]``: ``ln1_w ln1_b wq bq wk bk wv bv wo bo ln2_w
ln2_b w1 b1 w2 b2``), ``post_ln_w post_ln_b merge_ln_w merge_ln_b merge_w1
merge_b1 merge_w2 merge_b2``.

``sizes`` holds what shapes do not say: ``head_dim``, ``mrope_section``,
``rope_theta``, ``index_n_heads``, ``index_topk``, ``top_k``, ``rms_eps``,
``video_token_id``, ``vision_heads``; and, for the controls of a cell's
limits only, ``one_axis_positions`` (the video's tokens take the positions a
text token would: a reference that must NOT agree with the program).

The equations (``n`` RMSNorm with a learned scale, pre-norm residuals; the
section-1 rules of ISSUE 59, every one of which the configuration file lists
under ``assumed`` with the model it is taken from):

- positions: a text token ``t = h = w = next``, ``next`` one more than the
  largest component of any earlier token; a video of ``F`` frames of merged
  grid ``gh x gw`` starting at ``next = s``: frame ``f``, row ``r``, column
  ``c`` at ``(s + f, s + r, s + c)``, then ``next = s + max(F, gh, gw)``.
- rotary: the rotate-half pairing (value ``i`` with ``i + d / 2``); frequency
  ``i`` of the ``d / 2`` (``theta ** (-2i / d)``) turns by the axis its section
  names: ``mrope_section`` on q and k, the sections halved on the indexer's
  64 values.
- attention: ``q = n_q(W_q u)`` (32 x 128), ``k = n_k(W_k u)``, ``v = W_v u``
  (4 x 128), head ``h`` reads KV head ``h // 8``; the indexer ``qI = W_qI u``,
  ``kI = LayerNorm(W_kI u)``, ``wI = W_w u``; ``I[t, s] = sum_j wI[t, j]
  relu(qI[t, j] . kI[s]) di ** -0.5 Hi ** -0.5``; query ``t`` attends the
  ``index_topk`` positions ``s <= t`` of largest ``I`` (``lax.top_k``: the
  lower position first among equals; all while ``t < index_topk``); softmax
  at ``dh ** -0.5`` over those alone; ``x += W_o o``.
- experts: ``g = softmax(W_r n2(x))`` over ALL experts, the 8 largest kept and
  renormalised to sum 1; ``x += sum g_i W_down_i(silu(W_gate_i h) W_up_i h)``.
- tower: a patch ``(x / 255 - 0.5) / 0.5``; ``W_p x + b_p + P[r, c]`` (the
  table interpolated bilinearly, half-pixel centres, edges held); blocks ``h
  += W_o Attn(LN h)`` with biases, 2-D rotary (a head's first quarter-pairs by
  row, the next by column, rotate-half, base 10,000), ``h += W_2
  gelu_tanh(W_1 LN h + b_1) + b_2``; a final LN; the merger LN, four patches
  of a 2 x 2 square side by side, ``W_b gelu(W_a . + b_a) + b_b``.

Departures from the published model: none that the section-1 rules do not
state; ``q_chunk_size`` / ``kv_chunk_size`` are read as a kernel's block sizes
and change nothing here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HEAD_GROUP = 8      # query heads whose scores are materialised together
QUERY_BLOCK = 1024  # at most this many queries a block of materialised scores
INDEX_BLOCK = 256   # the same for the index scores, all index heads at once


def _through(lower):
    """Operands as the reference holds them: float32, or rounded through the
    dtype ``lower`` names first (a control of a cell's limits)."""
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


def _block_of(T: int, most: int) -> int:
    return next((b for b in range(most, 0, -128) if T % b == 0), T)


def positions_of(tokens: np.ndarray, video_token_id: int, merged,
                 one_axis: bool = False) -> np.ndarray:
    """``[3, T]`` int: the rule above for one row of token ids whose video (if
    any) is the run of placeholder ids; ``merged = (F, gh, gw)``."""
    T = len(tokens)
    pos = np.zeros((3, T), np.int64)
    nxt, i = 0, 0
    while i < T:
        if tokens[i] == video_token_id and merged is not None and not one_axis:
            F, gh, gw = merged
            for f in range(F):
                for r in range(gh):
                    for c in range(gw):
                        if i < T:
                            pos[:, i] = (nxt + f, nxt + r, nxt + c)
                            i += 1
            nxt += max(F, gh, gw)
            merged = None  # ONE video a request
        else:
            pos[:, i] = nxt
            nxt += 1
            i += 1
    return pos


def _mrope(x, pos, sections, theta):
    """x [.., T, d]; pos [3, T]; frequency i turns by the axis of its section."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    axis = np.repeat(np.arange(3), sections)                   # [d / 2]
    ang = jnp.asarray(pos, jnp.float32)[axis].T * inv[None, :]  # [T, d / 2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# -- the tower ----------------------------------------------------------------

def _table_at(table, gh: int, gw: int):
    """The learned table [S, S, D] at a gh x gw grid: bilinear, half-pixel
    centres, the edges held; a gather and two lerps."""
    S = table.shape[0]

    def axis(n):
        src = np.clip((np.arange(n) + 0.5) * S / n - 0.5, 0.0, S - 1.0)
        lo = np.minimum(np.floor(src).astype(np.int64), S - 2)
        return lo, jnp.asarray(src - lo, jnp.float32)

    (r0, fr), (c0, fc) = axis(gh), axis(gw)
    top = table[r0][:, c0] * (1 - fc)[None, :, None] + table[r0][:, c0 + 1] * fc[None, :, None]
    bot = (table[r0 + 1][:, c0] * (1 - fc)[None, :, None]
           + table[r0 + 1][:, c0 + 1] * fc[None, :, None])
    return top * (1 - fr)[:, None, None] + bot * fr[:, None, None]


@functools.partial(jax.jit, static_argnames=("grid", "heads", "eps", "lower"))
def _tower(patches, p, *, grid, heads, eps, lower):
    """patches [F, gh x gw, values] uint8 -> [F, gh/2 x gw/2, D], a frame at a
    time."""
    f = _through(lower)
    gh, gw = grid
    N = gh * gw
    D = p["patch_w"].shape[1]
    hd = D // heads
    pos = _table_at(f(p["pos_table"]), gh, gw).reshape(N, D)
    rows, cols = np.divmod(np.arange(N), gw)
    inv = 10000.0 ** (-np.arange(hd // 4, dtype=np.float32) * 2 / (hd // 2))
    ang = jnp.asarray(np.concatenate(
        [rows[:, None] * inv, cols[:, None] * inv], -1), jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def rot(t):  # [heads, N, hd]
        a, b = t[..., :hd // 2], t[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def frame(px):
        x = (px.astype(jnp.float32) / 255.0 - 0.5) / 0.5
        h = f(x) @ f(p["patch_w"]) + f(p["patch_b"]) + pos

        def block(h, b):
            u = f(_layernorm(h, f(b["ln1_w"]), f(b["ln1_b"]), eps))
            q, k, v = ((u @ f(b["w" + n]) + f(b["b" + n])).reshape(
                N, heads, hd).transpose(1, 0, 2) for n in "qkv")
            s = (f(rot(q)) @ f(rot(k)).transpose(0, 2, 1)) * hd ** -0.5
            o = f(jax.nn.softmax(s, -1)) @ f(v)
            h = h + f(o.transpose(1, 0, 2).reshape(N, D)) @ f(b["wo"]) + f(b["bo"])
            u = f(_layernorm(h, f(b["ln2_w"]), f(b["ln2_b"]), eps))
            m = jax.nn.gelu(u @ f(b["w1"]) + f(b["b1"]), approximate=True)
            return h + f(m) @ f(b["w2"]) + f(b["b2"]), None

        h, _ = jax.lax.scan(block, h, p["blocks"])
        h = _layernorm(h, f(p["post_ln_w"]), f(p["post_ln_b"]), eps)
        h = _layernorm(h, f(p["merge_ln_w"]), f(p["merge_ln_b"]), eps)
        h = h.reshape(gh // 2, 2, gw // 2, 2, D).transpose(0, 2, 1, 3, 4).reshape(
            (gh // 2) * (gw // 2), 4 * D)
        m = jax.nn.gelu(f(h) @ f(p["merge_w1"]) + f(p["merge_b1"]),
                        approximate=False)
        return f(m) @ f(p["merge_w2"]) + f(p["merge_b2"])

    return jax.lax.map(frame, patches)


def tower(params, patches, grid, sizes: dict, lower=None):
    """The tower's and the merger's rows for ``patches [F, gh' x gw', values]``
    uint8 -> ``[F, gh'/2 x gw'/2, D]`` float32."""
    with jax.default_matmul_precision("highest"):
        return _tower(jnp.asarray(patches), params["vision"], grid=tuple(grid),
                      heads=sizes["vision_heads"], eps=sizes["rms_eps"],
                      lower=lower)


# -- the text model -----------------------------------------------------------

def selection(u, pos, p, f, *, index_heads, index_topk, sections, theta, eps):
    """``keep [T, T]`` bool: row ``t`` the ``index_topk`` cache positions ``s
    <= t`` of largest index score; every causal position where the sequence is
    no longer than ``index_topk``."""
    T = u.shape[0]
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    if T <= index_topk:
        return causal
    halved = tuple(s // 2 for s in sections)
    q = (u @ f(p["w_qi"])).reshape(T, index_heads, -1).transpose(1, 0, 2)
    q = f(_mrope(q, pos, halved, theta))                          # [Hi, T, di]
    k = f(_mrope(_layernorm(u @ f(p["w_ki"]), f(p["ki_norm"]),
                            f(p["ki_norm_bias"]), eps), pos, halved, theta))
    w = u @ f(p["w_wi"])                                          # [T, Hi]
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
        return chosen & seen

    return jax.lax.map(rows, jnp.arange(T // block) * block).reshape(T, T)


@functools.partial(jax.jit, static_argnames=(
    "head_dim", "sections", "rope_theta", "index_heads", "index_topk", "top_k",
    "rms_eps", "lower"))
def _layer(x, pos, p, *, head_dim, sections, rope_theta, index_heads,
           index_topk, top_k, rms_eps, lower):
    """x [T, D] float32, pos [3, T], p one layer's parameters as stored."""
    f = _through(lower)
    T, D = x.shape
    H, KV = p["wq"].shape[1] // head_dim, p["wk"].shape[1] // head_dim
    u = f(_rmsnorm(x, f(p["attn_norm"]), rms_eps))
    keep = selection(u, pos, p, f, index_heads=index_heads,
                     index_topk=index_topk, sections=sections,
                     theta=rope_theta, eps=rms_eps)
    heads_of = lambda w, n: (u @ f(w)).reshape(T, n, head_dim).transpose(1, 0, 2)  # noqa: E731
    q = _mrope(_rmsnorm(heads_of(p["wq"], H), f(p["q_norm"]), rms_eps),
               pos, sections, rope_theta)
    k = _mrope(_rmsnorm(heads_of(p["wk"], KV), f(p["k_norm"]), rms_eps),
               pos, sections, rope_theta)
    v = heads_of(p["wv"], KV)
    q, k, v = f(q), f(k), f(v)
    block = _block_of(T, QUERY_BLOCK)
    outs = []
    for g in range(0, H, HEAD_GROUP):  # a group of query heads at a time
        qg = q[g:g + HEAD_GROUP]
        of = np.arange(g, min(g + HEAD_GROUP, H)) // (H // KV)
        kg, vg = k[of], v[of]

        def rows(first, qg=qg, kg=kg, vg=vg):
            qb = jax.lax.dynamic_slice_in_dim(qg, first, block, 1)
            s = (qb @ kg.transpose(0, 2, 1)) * head_dim ** -0.5
            mask = jax.lax.dynamic_slice_in_dim(keep, first, block, 0)
            s = jnp.where(mask, s, -jnp.inf)
            return f(jax.nn.softmax(s, axis=-1)) @ vg

        out = jax.lax.map(rows, jnp.arange(T // block) * block)
        outs.append(out.transpose(1, 0, 2, 3).reshape(out.shape[1], T, -1))
    o = jnp.concatenate(outs, 0).transpose(1, 0, 2).reshape(T, -1)
    x = x + f(o) @ f(p["wo"])

    h = f(_rmsnorm(x, f(p["ffn_norm"]), rms_eps))
    g = jax.nn.softmax(h @ f(p["router"]), axis=-1)               # [T, E]
    chosen, sel = jax.lax.top_k(g, top_k)
    gates = chosen / chosen.sum(-1, keepdims=True)

    def expert(e, y):  # every expert, densely, an expert at a time
        w = jnp.where(sel == e, gates, 0.0).sum(-1)
        act = jax.nn.silu(h @ f(p["ew_gate"][e])) * (h @ f(p["ew_up"][e]))
        return y + w[:, None] * (f(act) @ f(p["ew_down"][e]))

    return x + jax.lax.fori_loop(0, p["ew_gate"].shape[0], expert,
                                 jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("rms_eps", "lower"))
def _head(x, norm, head, *, rms_eps, lower):
    f = _through(lower)
    return f(_rmsnorm(x, f(norm), rms_eps)) @ f(head)


def layer_statics(sizes: dict, lower=None) -> dict:
    return dict(
        head_dim=sizes["head_dim"], sections=tuple(sizes["mrope_section"]),
        rope_theta=float(sizes["rope_theta"]),
        index_heads=sizes["index_n_heads"], index_topk=sizes["index_topk"],
        top_k=sizes["top_k"], rms_eps=sizes["rms_eps"], lower=lower)


def logits(params, tokens, sizes: dict, lower=None, videos=None, rows=None):
    """tokens [B, T] int32 -> logits [B, T, V] float32, on the HOST (numpy).
    ``rows`` (None: every position): one ``(start, stop)`` a row of the batch,
    the positions whose logits are wanted (a list of ``[stop - start, V]``
    then: at 151,936 rows of vocabulary a whole sequence's logits are 10 GB).
    ``videos``: one entry a row, None (a text row) or ``(patches [F, gh' x
    gw', values] uint8, (gh', gw'))``: the video whose rows stand where the
    row's tokens hold ``sizes["video_token_id"]``.  ``lower``: a dtype's name;
    every matmul operand (weights and activations, the tower's too) is rounded
    through it first."""
    out = []
    f = _through(lower)
    tokens = np.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        for b, row in enumerate(tokens):  # a row of the batch at a time
            x = f(params["tok_emb"][jnp.asarray(row)])
            video = videos[b] if videos is not None else None
            merged = None
            if video is not None:
                patches, (gh, gw) = video
                seen = tower(params, patches, (gh, gw), sizes, lower)
                merged = (seen.shape[0], gh // 2, gw // 2)
                # (the id may come again later, as an answer's token: text)
                at = np.flatnonzero(row == sizes["video_token_id"])[
                    :seen.shape[0] * seen.shape[1]]
                assert len(at) == seen.shape[0] * seen.shape[1] and (
                    np.diff(at) == 1).all(), (len(at), seen.shape)
                x = x.at[jnp.asarray(at)].set(
                    f(seen.reshape(-1, seen.shape[-1])))
            pos = positions_of(row, sizes["video_token_id"], merged,
                               bool(sizes.get("one_axis_positions")))
            for p in params["layers"]:
                x = _layer(x, jnp.asarray(pos, jnp.int32), p,
                           **layer_statics(sizes, lower))
            if rows is not None:
                x = x[rows[b][0]:rows[b][1]]
            out.append(np.asarray(_head(
                x, params["final_norm"], params["head"],
                rms_eps=sizes["rms_eps"], lower=lower)))
    return out if rows is not None else np.stack(out)
