"""Shared transformer core: stacked-layer params, scan-over-layers forward.

Design choices, all TPU-motivated:

- **Layer stacking**: every block parameter carries a leading ``[L, ...]``
  layer axis and the forward is one ``lax.scan`` over it — one compiled
  block body regardless of depth (fast compiles, friendly to pipeline
  sharding later).
- **Remat**: the scanned body is wrapped in ``jax.checkpoint`` so
  activations are recomputed in the backward pass — HBM for FLOPs.
- **bf16 compute, f32 master weights**: params live in f32; matmuls run in
  ``config.dtype`` (bfloat16 by default) with f32 accumulation inside the
  attention/softmax path.
- **Logical axes**: a parallel pytree of axis-name tuples feeds
  :mod:`ray_tpu.parallel.sharding` — ``embed``→fsdp, ``heads``/``mlp``→tp,
  sequence→sp (ring attention when the mesh has an ``sp`` axis).
- **FSDP**: at rest ``embed``→fsdp shards every weight; under an ``fsdp``
  mesh axis the scanned body gathers ITS layer's weights in ``config.dtype``
  and pins the activations to the batch, so chips exchange weights, not
  activations, and the layer ops sum parameter gradients over the batch
  (so across chips) in float32 (see :mod:`ray_tpu.parallel.sharding`).
- **One block per family, one seam for attention**: a family's block
  (:func:`apply_block` here, ``llama.block``) is the only place its
  projections, norms, residuals and FFN are written.  Training, prefill and
  decode differ in the attention middle alone, so the block takes it as an
  argument: ``attend(q [B, H, T, dh], k, v [B, KV, T, dh])`` returns the
  attention output ``[B, H, T, dh]`` and whatever its caller wants carried
  out of the layer (:func:`_attend`: nothing; prefill: the layer's k, v;
  decode: the chunk's K/V buffers — :mod:`ray_tpu.models.generate`).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import FLASH_RESIDUALS, attention, flash_plan
from ray_tpu.ops.layers import dense, layernorm
from ray_tpu.ops.moe import init_moe_params, moe_ffn, moe_logical_axes
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel.sharding import (
    ShardingRules,
    fsdp_engaged,
    gather_for_compute,
    shard_activations,
)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50304  # GPT-2's 50257 padded up to a multiple of 128
    n_layers: int = 12
    n_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq_len: int = 1024
    causal: bool = True
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "dots": save matmul outputs, recompute elementwise (the backward
    # re-reads saved MXU outputs instead of re-running them); "full":
    # recompute all.  train-gpt2-medium-1k runs "dots" and
    # train-gpt2-xl-fsdp4 "full" (what fits); no cell compares the two.
    remat_policy: str = "dots"
    # pre-LN (GPT-2 style) by default; post-LN matches original BERT so
    # HF checkpoints load faithfully.
    post_ln: bool = False
    # MoE: >0 replaces every block's FFN with a Switch-style top-1 MoE of
    # this many experts (expert axis shards over the mesh's ep axis).
    n_experts: int = 0
    capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01
    # pipeline parallelism: microbatch count when the mesh has pp > 1
    # (0 = one microbatch per stage).
    pp_microbatches: int = 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def init_block_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """Stacked block params, GPT-2 init (normal 0.02, residual projections
    scaled by 1/sqrt(2L))."""
    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    std, res_std = 0.02, 0.02 / (2 * L) ** 0.5
    p = {
        "ln1_w": jnp.ones((L, D)), "ln1_b": jnp.zeros((L, D)),
        "wqkv": jax.random.normal(ks[0], (L, D, 3 * D)) * std,
        "bqkv": jnp.zeros((L, 3 * D)),
        "wo": jax.random.normal(ks[1], (L, D, D)) * res_std,
        "bo": jnp.zeros((L, D)),
        "ln2_w": jnp.ones((L, D)), "ln2_b": jnp.zeros((L, D)),
    }
    if cfg.n_experts > 0:
        p.update(init_moe_params(ks[4], L, D, F, cfg.n_experts,
                                 std=std, res_std=res_std))
    else:
        p.update({
            "w1": jax.random.normal(ks[2], (L, D, F)) * std,
            "b1": jnp.zeros((L, F)),
            "w2": jax.random.normal(ks[3], (L, F, D)) * res_std,
            "b2": jnp.zeros((L, D)),
        })
    return p


def block_logical_axes(n_experts: int = 0) -> Dict[str, Tuple]:
    """Logical axis names for the stacked block params.  The leading
    ``layers`` axis is the scan axis; it shards over ``pp`` (and only
    ``pp``) when the mesh pipelines."""
    axes = {
        "ln1_w": ("layers", "embed"), "ln1_b": ("layers", "embed"),
        "wqkv": ("layers", "embed", "heads"),
        "bqkv": ("layers", "heads"),
        "wo": ("layers", "heads", "embed"),
        "bo": ("layers", "embed"),
        "ln2_w": ("layers", "embed"), "ln2_b": ("layers", "embed"),
    }
    if n_experts > 0:
        axes.update(moe_logical_axes())
    else:
        axes.update({
            "w1": ("layers", "embed", "mlp"),
            "b1": ("layers", "mlp"),
            "w2": ("layers", "mlp", "embed"),
            "b2": ("layers", "embed"),
        })
    return axes


def make_train_step_from_loss(loss_fn, cfg, optimizer, mesh: Optional[Mesh] = None,
                              rules: Optional[ShardingRules] = None, *,
                              counters=None, name: Optional[str] = None):
    """Shared train-step recipe for every model family: value_and_grad of
    ``loss_fn(params, batch, cfg, mesh)`` + optimizer update.  One place to
    fix donation/metrics for all models.  ``rules``: the table the caller
    placed the parameters with, when it is not ``rules_for_mesh(mesh)``
    (handed on as ``loss_fn(..., rules=rules)``).

    ``counters`` (a family with more to report than its loss):
    ``counters(grads) -> dict``, and ``loss_fn`` then returns ``(loss, dict)``;
    both dicts ride out in the step's metrics beside ``loss`` and ``step``
    (what a family counts on the device: routed pairs, the loss's terms, the
    norms of named gradients).  ``name``: the step function's, and so the
    compiled program's (``jit_<name>``: what a device trace lists it under);
    default ``train_step``."""
    import optax

    if rules is not None:
        loss_fn = partial(loss_fn, rules=rules)

    def train_step(state, batch):
        params, opt_state, step = state["params"], state["opt_state"], state["step"]
        if counters is None:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg, mesh)
            counted = {}
        else:
            (loss, counted), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, cfg, mesh)
            counted = {**counted, **counters(grads)}
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return ({"params": params, "opt_state": opt_state, "step": step + 1},
                {"loss": loss, "step": step + 1, **counted})

    if name:
        train_step.__name__ = train_step.__qualname__ = name
    return train_step


def _attend(q, k, v, *, causal: bool, mesh: Optional[Mesh], window: int = 0,
            scale: Optional[float] = None):
    """The attention middle of training and ``apply``: the whole sequence,
    each KV head serving ``H // KV`` query heads, sequence-parallel when the
    mesh has an sp axis; ``window``: a window layer's band (0: none; not
    under sp); ``scale``: a family's own (None: ``dh ** -0.5``; not under
    sp).  Carries nothing out of the layer.

    Under a mesh, ring attention and the Pallas pair (``ops.attention.
    flash_plan``: the shapes that go to it where the step is lowered for a
    TPU) run under ``jax.shard_map``, each chip on its own sequences (``dp``,
    ``fsdp``) and heads (``tp``): GSPMD cannot partition a ``pallas_call``,
    and would gather q, k and v onto every chip to run it whole."""
    if k.shape[1] != q.shape[1]:  # GQA
        k, v = (jnp.repeat(t, q.shape[1] // t.shape[1], axis=1) for t in (k, v))
    attend = partial(attention, causal=causal, window=window, scale=scale)
    if mesh is None or getattr(jax.typeof(q), "vma", None):
        return attend(q, k, v), None  # no mesh, or a manual region already
    ring = "sp" in mesh.axis_names and mesh.shape["sp"] > 1
    batch = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names) or None
    heads = "tp" if "tp" in mesh.axis_names else None
    if ring:
        assert not window and scale is None, "no band, no scale of its own under sp"
        attend = partial(ring_attention, axis_name="sp", causal=causal)
    elif (flash_plan(q.shape, k.shape, v.shape, causal=causal,
                     window=window) is None
          or q.shape[0] % math.prod(mesh.shape[a] for a in batch or ())
          or q.shape[1] % (mesh.shape["tp"] if heads else 1)):
        return attend(q, k, v), None
    spec = P(batch, heads, "sp" if ring else None, None)
    sm = jax.shard_map(attend, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                       check_vma=False)
    return sm(q, k, v), None


# block parameters used in the dtype they are stored in; every other one is
# cast to cfg.dtype at use (by the layer ops)
_USED_AS_STORED = ("router",)


def apply_block(
    x: jax.Array, p: Dict[str, jax.Array], cfg: TransformerConfig,
    attend=None, positions: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
) -> Tuple[jax.Array, jax.Array, Any]:
    """One transformer block, pre-LN or post-LN.  x: [B, T, D] in cfg.dtype.
    ``attend``: the attention middle (module docstring; default
    :func:`_attend`).  ``positions`` is not used: this family's are a table
    added at the embedding.  Returns ``(x, aux, carried)`` — aux is the MoE
    load-balance loss (0 when dense), carried what ``attend`` handed back."""
    B, T, D = x.shape
    H, dh = cfg.n_heads, cfg.head_dim
    attend = attend or partial(_attend, causal=cfg.causal, mesh=mesh)
    aux, carried = jnp.zeros((), jnp.float32), None
    # under fsdp the batch is spread over chips: sum the parameters'
    # gradients over it in float32
    f32g = fsdp_engaged(mesh, x)
    lin = partial(dense, f32_param_grads=f32g)
    norm = partial(layernorm, f32_param_grads=f32g)

    def attn(h):
        nonlocal carried
        qkv = lin(h, p["wqkv"], p["bqkv"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
        to_heads = lambda t: t.reshape(B, T, H, dh).transpose(0, 2, 1, 3)
        out, carried = attend(to_heads(q), to_heads(k), to_heads(v))
        out = out.transpose(0, 2, 1, 3).reshape(B, T, D)
        return lin(out, p["wo"], p["bo"])

    if cfg.n_experts > 0:
        def ffn(h):
            nonlocal aux
            y, a = moe_ffn(h, p["router"], p["ew1"], p["eb1"],
                           p["ew2"], p["eb2"],
                           capacity_factor=cfg.capacity_factor, mesh=mesh,
                           f32_param_grads=f32g)
            aux = aux + a
            return y
    else:
        def ffn(h):
            h = jax.nn.gelu(lin(h, p["w1"], p["b1"]), approximate=True)
            return lin(h, p["w2"], p["b2"])

    if cfg.post_ln:  # original-BERT residual->norm order
        x = norm(x + attn(x), p["ln1_w"], p["ln1_b"])
        x = norm(x + ffn(x), p["ln2_w"], p["ln2_b"])
    else:  # GPT-2 pre-LN
        x = x + attn(norm(x, p["ln1_w"], p["ln1_b"]))
        x = x + ffn(norm(x, p["ln2_w"], p["ln2_b"]))
    return x, aux, carried


def apply_stack(
    x: jax.Array, blocks: Dict[str, jax.Array], cfg: Any,
    mesh: Optional[Mesh] = None, rules: Optional[ShardingRules] = None,
    *, block=apply_block, axes: Optional[Dict[str, Tuple]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Run the stacked layers of a family's ``block`` (``axes``: the logical
    axes of ``blocks``; default: this module's); returns ``(x, aux)``.

    Without ``pp`` the stack is one remat'd ``lax.scan`` over the layer
    axis.  With a ``pp > 1`` mesh axis, the layer axis is sharded into
    stages and the scan runs inside the GPipe engine
    (:func:`ray_tpu.parallel.pipeline.gpipe`) — same math, microbatched.
    """

    axes = axes or block_logical_axes(cfg.n_experts)

    def body(x, layer_params):
        # FSDP proper (both helpers do nothing without an fsdp mesh axis):
        # the batch stays where it is and THIS layer's weights come to it,
        # moved in the dtype the block uses them in.  Inside the remat'd
        # body, so the gather is per layer and is recomputed in the
        # backward pass, not saved.
        x = shard_activations(x, mesh, rules)
        layer_params = {
            k: gather_for_compute(
                w, axes[k][1:], mesh, rules,
                w.dtype if k in _USED_AS_STORED else cfg.dtype)
            for k, w in layer_params.items()}
        x, aux, _ = block(x, layer_params, cfg, mesh=mesh)
        return x, aux

    if cfg.remat:
        if cfg.remat_policy == "dots":
            # the weight matmuls' outputs and, where attention ran as the
            # Pallas pair, its result and logsumexp (17.3 MB a layer at the
            # medium cell's shape): the backward pass then runs no forward
            # kernel again
            policies = jax.checkpoint_policies
            body = jax.checkpoint(
                body,
                policy=policies.save_from_both_policies(
                    policies.dots_with_no_batch_dims_saveable,
                    policies.save_only_these_names(*FLASH_RESIDUALS)),
            )
        elif cfg.remat_policy == "full":
            body = jax.checkpoint(body)
        else:
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r} (use 'dots' or 'full')"
            )

    def stage(local_blocks, h):
        h, auxs = lax.scan(body, h, local_blocks)
        return h, auxs.sum()

    from ray_tpu.parallel.pipeline import gpipe, pp_size

    if mesh is not None and pp_size(mesh) > 1:
        return gpipe(stage, blocks, x, mesh=mesh,
                     n_microbatches=cfg.pp_microbatches)
    return stage(blocks, x)
