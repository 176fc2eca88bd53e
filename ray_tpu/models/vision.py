"""A vision tower in front of a text model: a native-resolution ViT over the
patches of video frames, and the merger that turns every 2 x 2 square of
patches into ONE row of the text model's input (the SigLIP-so400m tower the
Keye-VL reports start from, with Qwen2-VL's merger).  Pure jax; used by
:mod:`ray_tpu.models.keye_vl`, served by :mod:`ray_tpu.serve.llm` as a program
of its own (``jit_llm_vision_encode``).

Equations (``LN`` LayerNorm with scale and bias, eps ``cfg.eps``), a frame of
``gh x gw`` patches (both even), patches in row-major order:

- a patch is ``patch x patch x channels`` bytes (14 x 14 x 3 = 588),
  normalised ``(x / 255 - 0.5) / 0.5``; ``h = W_p x + b_p`` (1,152) ``+ P[r,
  c]``, ``P`` the learned ``table x table`` (27 x 27) position table
  interpolated bilinearly to the frame's grid (half-pixel centres, the edges
  held: :func:`interpolation`);
- ``layers`` (27) pre-LN blocks: ``h += W_o Attn(LN1 h)``, ``h += W_2
  gelu_tanh(W_1 LN2 h + b_1) + b_2``; attention among the patches of ONE frame
  (no mask inside a frame, nothing across frames), ``heads`` (16) heads of 72
  with biases on q, k, v and o; 2-D rotary on q and k: of a head's 36 pairs
  (value ``i`` with ``i + 36``) the first 18 turn by the patch's row and the
  last 18 by its column, frequency ``j`` of either half ``10000 ** (-2j /
  36)``;
- a final LN; the merger: LN, the four patches of a 2 x 2 square side by side
  (``(2R, 2C), (2R, 2C + 1), (2R + 1, 2C), (2R + 1, 2C + 1)``: 4,608), ``W_b
  gelu(W_a . + b_a) + b_b`` (4,608 -> 4,608 -> ``out_dim``, the exact GELU).

A frame becomes ``(gh / 2) x (gw / 2)`` rows, row-major.  Frames are
independent, so a call is a batch of them.

The head width (72) does not tile the chip's 128 lanes.  The tower's attention
is plain XLA einsums over a frame's ``[heads, patches, patches]`` float32
scores (16 x 256 x 256 a frame at the cell's grid: 4 MB), which the compiler
pads to the lanes itself: the scores and value sums are ~4 % of a frame's
operations (8.2 of 223 GFLOP at 16 x 16 patches) and, measured on the chip,
~1.7 ms of a 25.6 ms call of 16 frames (PERF.md section 5, PR 59); a kernel
over two heads a lane group is PERF.md's open question (7.25(c)).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.layers import dense, layernorm

__all__ = ["VisionConfig", "init", "encode", "interpolation", "num_params"]


@dataclasses.dataclass(frozen=True)
class VisionConfig:
    layers: int = 27
    d_model: int = 1152
    heads: int = 16
    d_ff: int = 4304
    patch: int = 14
    channels: int = 3
    table: int = 27          # the learned position table's side
    merge: int = 2           # patches a side of the square one row stands for
    out_dim: int = 2048      # the text model's width
    rope_base: float = 10_000.0
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def patch_values(self) -> int:
        return self.patch * self.patch * self.channels

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads


def init(cfg: VisionConfig, key: jax.Array) -> Dict[str, Any]:
    """Fan-in scaled normals in ``cfg.dtype``, the blocks' leaves stacked
    ``[layers, ...]`` (one rolled loop); ``W_o`` and ``W_2`` at half scale, norm
    scales ``1 + 0.1 N``, biases ``0.02 N``."""
    D, F, L = cfg.d_model, cfg.d_ff, cfg.layers
    M = D * cfg.merge ** 2
    keys = iter(jax.random.split(key, 40))

    def w(*shape, scale=1.0):
        return (jax.random.normal(next(keys), shape, cfg.dtype)
                * jnp.asarray(scale * shape[-2] ** -0.5, cfg.dtype))

    def b(*shape):
        return (0.02 * jax.random.normal(next(keys), shape)).astype(cfg.dtype)

    def g(*shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape)).astype(cfg.dtype)

    return {
        "patch_w": w(cfg.patch_values, D), "patch_b": b(D),
        "pos_table": (0.5 * jax.random.normal(
            next(keys), (cfg.table, cfg.table, D))).astype(cfg.dtype),
        "blocks": {
            "ln1_w": g(L, D), "ln1_b": b(L, D), "ln2_w": g(L, D), "ln2_b": b(L, D),
            "wq": w(L, D, D), "bq": b(L, D), "wk": w(L, D, D), "bk": b(L, D),
            "wv": w(L, D, D), "bv": b(L, D), "wo": w(L, D, D, scale=0.5),
            "bo": b(L, D), "w1": w(L, D, F), "b1": b(L, F),
            "w2": w(L, F, D, scale=0.5), "b2": b(L, D)},
        "post_ln_w": g(D), "post_ln_b": b(D),
        "merge_ln_w": g(D), "merge_ln_b": b(D),
        "merge_w1": w(M, M), "merge_b1": b(M),
        "merge_w2": w(M, cfg.out_dim), "merge_b2": b(cfg.out_dim),
    }


def interpolation(n: int, table: int) -> np.ndarray:
    """``[n, table]`` float32: row ``i`` the bilinear weights of grid place
    ``i`` of ``n`` over a table side (half-pixel centres: source coordinate
    ``(i + 0.5) table / n - 0.5``, held to the table's ends)."""
    src = np.clip((np.arange(n) + 0.5) * table / n - 0.5, 0.0, table - 1.0)
    lo = np.minimum(np.floor(src).astype(np.int64), table - 2)
    frac = (src - lo).astype(np.float32)
    out = np.zeros((n, table), np.float32)
    out[np.arange(n), lo] = 1.0 - frac
    out[np.arange(n), lo + 1] += frac
    return out


def _rope_2d(x: jax.Array, grid: Tuple[int, int], base: float) -> jax.Array:
    """``x [F, heads, gh x gw, d]``: the first half of a head's pairs by the
    patch's row, the second by its column (module docstring)."""
    gh, gw = grid
    d = x.shape[-1]
    quarter = d // 4
    inv_freq = 1.0 / (base ** (np.arange(quarter, dtype=np.float32) * 2 / (d // 2)))
    rows, cols = np.divmod(np.arange(gh * gw), gw)
    angles = jnp.asarray(np.concatenate(
        [rows[:, None] * inv_freq, cols[:, None] * inv_freq], axis=-1),
        jnp.float32)                                     # [patches, d / 2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def encode(params: Dict[str, Any], cfg: VisionConfig, patches: jax.Array,
           grid: Tuple[int, int]) -> jax.Array:
    """``patches [F, gh x gw, patch_values]`` uint8 (row-major in a frame) ->
    ``[F, (gh / 2) x (gw / 2), out_dim]`` in ``cfg.dtype``: the rows that stand
    where a prompt's tokens hold the video placeholder."""
    gh, gw = grid
    F, N, _ = patches.shape
    m, H, hd, D = cfg.merge, cfg.heads, cfg.head_dim, cfg.d_model
    assert N == gh * gw and gh % m == 0 and gw % m == 0, (patches.shape, grid)
    ln = lambda t, w, b: layernorm(t, w, b, eps=cfg.eps)  # noqa: E731
    with jax.named_scope("vision.patch_embed"):
        x = ((patches.astype(jnp.float32) / 255.0 - 0.5) / 0.5).astype(cfg.dtype)
        pos = jnp.einsum(
            "ia,jb,abd->ijd", jnp.asarray(interpolation(gh, cfg.table)),
            jnp.asarray(interpolation(gw, cfg.table)),
            params["pos_table"].astype(jnp.float32))
        h = dense(x, params["patch_w"], params["patch_b"]) + pos.reshape(
            N, D).astype(cfg.dtype)

    def block(h, p):
        with jax.named_scope("vision.attention"):
            u = ln(h, p["ln1_w"], p["ln1_b"])
            q, k, v = (dense(u, p["w" + n], p["b" + n]).reshape(
                F, N, H, hd).transpose(0, 2, 1, 3) for n in "qkv")
            q, k = (_rope_2d(t, grid, cfg.rope_base) for t in (q, k))
            s = jnp.einsum("fhqd,fhkd->fhqk", q, k,
                           preferred_element_type=jnp.float32) * hd ** -0.5
            o = jnp.einsum("fhqk,fhkd->fhqd",
                           jax.nn.softmax(s, axis=-1).astype(v.dtype), v)
            h = h + dense(o.transpose(0, 2, 1, 3).reshape(F, N, D),
                          p["wo"], p["bo"])
        with jax.named_scope("vision.mlp"):
            u = ln(h, p["ln2_w"], p["ln2_b"])
            h = h + dense(jax.nn.gelu(dense(u, p["w1"], p["b1"]),
                                      approximate=True), p["w2"], p["b2"])
        return h, None

    h, _ = jax.lax.scan(block, h, params["blocks"])
    with jax.named_scope("vision.merge"):
        h = ln(ln(h, params["post_ln_w"], params["post_ln_b"]),
               params["merge_ln_w"], params["merge_ln_b"])
        h = h.reshape(F, gh // m, m, gw // m, m, D).transpose(
            0, 1, 3, 2, 4, 5).reshape(F, (gh // m) * (gw // m), m * m * D)
        h = jax.nn.gelu(dense(h, params["merge_w1"], params["merge_b1"]),
                        approximate=False)
        return dense(h, params["merge_w2"], params["merge_b2"])


def num_params(params: Dict[str, Any]) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
