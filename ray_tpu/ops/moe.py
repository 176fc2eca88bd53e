"""Mixture-of-Experts FFN with expert parallelism over the ``ep`` axis.

The reference has no MoE/expert-parallel code (SURVEY §2.5 row EP:
"Absent"); this is the TPU-native build target — "expert-axis sharding +
``all_to_all`` over ICI".  Switch-Transformer-style top-1 routing with a
fixed per-expert capacity, expressed as dense dispatch/combine einsums
(the GShard formulation): expert weights carry an ``expert`` logical axis
mapped to the mesh's ``ep`` axis, the token batch is sharded over
dp/fsdp, and XLA lowers the ``[tokens] x [experts]`` dispatch einsum into
the ep-axis all_to_all/all_gather pair — collectives ride ICI, nothing is
hand-scheduled.

Shapes are static (capacity = ceil(cf * tokens / E)), so the whole thing
jits once; dropped tokens (over capacity) fall through the residual
connection, as in Switch.  The load-balance auxiliary loss is the Switch
eq. (4): ``E * sum_e f_e * P_e``, minimized at uniform routing.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.layers import dense


def _constrain(x: jax.Array, mesh: Optional[Mesh], spec: P) -> jax.Array:
    if mesh is None:
        return x
    try:
        if jax.typeof(x).vma:
            # inside a manual region (e.g. the pp pipeline's shard_map):
            # constraints on varying arrays are rejected; sharding still
            # propagates from the ep-sharded expert weights.
            return x
    except AttributeError:
        pass
    # drop axes the mesh doesn't have
    parts = tuple(a if (a in mesh.axis_names) else None for a in spec)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))


def moe_ffn(
    x: jax.Array,
    router_w: jax.Array,
    w1: jax.Array,
    b1: jax.Array,
    w2: jax.Array,
    b2: jax.Array,
    *,
    capacity_factor: float = 2.0,
    mesh: Optional[Mesh] = None,
    f32_param_grads: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Top-1 (Switch) MoE feed-forward.

    Args:
        x: ``[B, T, D]`` activations (compute dtype).
        router_w: ``[D, E]`` router weights (kept f32 for stable softmax).
        w1, b1: ``[E, D, F]``, ``[E, F]`` expert up-projections.
        w2, b2: ``[E, F, D]``, ``[E, D]`` expert down-projections.
        capacity_factor: per-expert buffer = ``cf * tokens / E``.
        mesh: optional mesh; expert dims get an ``ep`` sharding constraint.
        f32_param_grads: accumulate the expert weights' gradients in float32
            (:func:`ray_tpu.ops.layers.dense`).

    Returns:
        ``(y, aux)`` — ``[B, T, D]`` output and the scalar load-balance
        loss (add ``aux_weight * aux`` to the training loss).
    """
    B, T, D = x.shape
    E = w1.shape[0]
    S = B * T
    C = max(1, math.ceil(capacity_factor * S / E))
    xf = x.reshape(S, D)

    logits = xf.astype(jnp.float32) @ router_w.astype(jnp.float32)  # [S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate = probs.max(axis=-1)          # [S] top-1 gate value
    expert = probs.argmax(axis=-1)     # [S] chosen expert

    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)       # [S, E]
    # arrival order within each expert's queue; tokens past C are dropped
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot            # [S, E]
    pos_tok = pos.sum(axis=-1)                                   # [S]
    keep = (pos_tok < C).astype(jnp.float32)
    dispatch = onehot * keep[:, None]                            # [S, E]
    pos_onehot = jax.nn.one_hot(pos_tok.astype(jnp.int32), C, dtype=jnp.float32)
    disp = dispatch[..., None] * pos_onehot[:, None, :]          # [S, E, C]

    # dispatch: tokens -> per-expert buffers.  With x sharded over
    # dp/fsdp and the E dim constrained to ep this einsum IS the ep
    # all_to_all (XLA inserts it under GSPMD).
    expert_in = jnp.einsum("sec,sd->ecd", disp.astype(x.dtype), xf)
    expert_in = _constrain(expert_in, mesh, P("ep", None, None))

    # per expert: [C, D] @ [D, F], then [C, F] @ [F, D]
    h = dense(expert_in, w1, b1[:, None, :], f32_param_grads=f32_param_grads)
    h = jax.nn.gelu(h, approximate=True)
    out = dense(h, w2, b2[:, None, :], f32_param_grads=f32_param_grads)
    out = _constrain(out, mesh, P("ep", None, None))

    # combine: per-expert buffers -> tokens, weighted by the gate (the
    # gate factor keeps the router differentiable — Switch eq. 2)
    combine = disp * (gate * keep)[:, None, None]                # [S, E, C]
    y = jnp.einsum("sec,ecd->sd", combine.astype(out.dtype), out)

    # Switch load-balance loss: E * sum_e (token fraction)_e * (prob mass)_e
    f = onehot.mean(axis=0)
    Pm = probs.mean(axis=0)
    aux = E * jnp.sum(f * Pm)
    return y.reshape(B, T, D).astype(x.dtype), aux


def init_moe_params(
    key: jax.Array, n_layers: int, d_model: int, d_ff: int, n_experts: int,
    *, std: float = 0.02, res_std: Optional[float] = None,
) -> Dict[str, jax.Array]:
    """Layer-stacked expert params ``[L, E, ...]`` (router kept f32)."""
    L, D, F, E = n_layers, d_model, d_ff, n_experts
    res_std = res_std if res_std is not None else std / (2 * L) ** 0.5
    kr, k1, k2 = jax.random.split(key, 3)
    return {
        "router": jax.random.normal(kr, (L, D, E)) * std,
        "ew1": jax.random.normal(k1, (L, E, D, F)) * std,
        "eb1": jnp.zeros((L, E, F)),
        "ew2": jax.random.normal(k2, (L, E, F, D)) * res_std,
        "eb2": jnp.zeros((L, E, D)),
    }


def moe_logical_axes() -> Dict[str, Tuple]:
    """Logical axes for :func:`init_moe_params` (expert -> ep)."""
    return {
        "router": ("layers", "embed", None),
        "ew1": ("layers", "expert", "embed", "mlp"),
        "eb1": ("layers", "expert", "mlp"),
        "ew2": ("layers", "expert", "mlp", "embed"),
        "eb2": ("layers", "expert", "embed"),
    }
