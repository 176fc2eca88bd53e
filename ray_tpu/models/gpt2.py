"""GPT-2: the flagship decoder LM (BASELINE config 3 — GPT-2 125M).

Pure-jax (init, apply, loss, train_step) over dict pytrees with logical
sharding axes; trains data/fsdp/tensor/sequence-parallel purely through
sharding annotations — the reference delegates all of this to torch
(``python/ray/train/torch/train_loop_utils.py:51`` prepare_model wraps
DDP/FSDP); here the sharding *is* the model's parallelism.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from ray_tpu.models.transformer import (
    TransformerConfig,
    apply_block as block,  # noqa: F401 — this family's block, by the name generate.py reads
    apply_stack,
    block_logical_axes,
    init_block_params,
)
from ray_tpu.ops.layers import cross_entropy_loss, dense, layernorm
from ray_tpu.parallel.sharding import (
    ShardingRules,
    fsdp_engaged,
    gather_for_compute,
    logical_to_sharding,
    shard_activations,
)


@dataclasses.dataclass(frozen=True)
class GPT2Config(TransformerConfig):
    causal: bool = True

    @staticmethod
    def gpt2_small(**kw) -> "GPT2Config":
        """The 124M-parameter headline model (any field overridable)."""
        base = dict(vocab_size=50304, n_layers=12, n_heads=12, d_model=768,
                    d_ff=3072, max_seq_len=1024)
        base.update(kw)
        return GPT2Config(**base)

    @staticmethod
    def tiny(**kw) -> "GPT2Config":
        """Test/dry-run sized (any field overridable)."""
        base = dict(vocab_size=512, n_layers=2, n_heads=4, d_model=64,
                    d_ff=256, max_seq_len=128, remat=False)
        base.update(kw)
        return GPT2Config(**base)


# the family table (ray_tpu.models.generate.FAMILIES) reads these two: the
# config class, and the presets ``size`` names
Config = GPT2Config
SIZES = {"small": GPT2Config.gpt2_small, "125m": GPT2Config.gpt2_small,
         "tiny": GPT2Config.tiny}


def init(cfg: GPT2Config, key: jax.Array) -> Dict[str, Any]:
    k_emb, k_pos, k_blocks = jax.random.split(key, 3)
    return {
        "wte": jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model)) * 0.02,
        "wpe": jax.random.normal(k_pos, (cfg.max_seq_len, cfg.d_model)) * 0.01,
        "blocks": init_block_params(cfg, k_blocks),
        "lnf_w": jnp.ones(cfg.d_model),
        "lnf_b": jnp.zeros(cfg.d_model),
    }


def logical_axes(cfg: Optional[GPT2Config] = None) -> Dict[str, Any]:
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": block_logical_axes(cfg.n_experts if cfg else 0),
        "lnf_w": ("embed",),
        "lnf_b": ("embed",),
    }


def param_shardings(mesh: Mesh, rules: ShardingRules, cfg: Optional[GPT2Config] = None):
    return logical_to_sharding(logical_axes(cfg), mesh, rules)


def kv_heads(cfg: GPT2Config) -> int:
    """K/V heads a cache holds for a position: one for each query head."""
    return cfg.n_heads


def embed(params: Dict[str, Any], tokens: jax.Array, cfg: GPT2Config,
          positions: Optional[jax.Array] = None,
          mesh: Optional[Mesh] = None,
          rules: Optional[ShardingRules] = None) -> jax.Array:
    """tokens [B, T] -> x [B, T, D] in cfg.dtype.  ``positions``: [T], or
    [B, 1] (a decode step's per-slot offsets); None: 0..T-1."""
    # under an fsdp mesh axis each parameter comes whole along fsdp in the
    # dtype it is used in and activations stay on the batch; else the two
    # helpers do nothing
    wpe = gather_for_compute(params["wpe"], logical_axes(cfg)["wpe"], mesh,
                             rules, params["wpe"].dtype)
    x = params["wte"][tokens] + (
        wpe[:tokens.shape[1]] if positions is None
        else jnp.take(wpe, positions, axis=0))
    return shard_activations(x.astype(cfg.dtype), mesh, rules)


def unembed(params: Dict[str, Any], x: jax.Array, cfg: GPT2Config,
            mesh: Optional[Mesh] = None,
            rules: Optional[ShardingRules] = None) -> jax.Array:
    """Final norm and the tied LM head: x [B, T, D] -> logits [B, T, V] f32
    (on the batch under fsdp, parameter gradients summed in float32)."""
    axes = logical_axes(cfg)
    whole = lambda w, axes: gather_for_compute(  # noqa: E731
        w, axes, mesh, rules, cfg.dtype)
    f32g = fsdp_engaged(mesh, x)
    x = layernorm(x, whole(params["lnf_w"], axes["lnf_w"]),
                  whole(params["lnf_b"], axes["lnf_b"]), f32_param_grads=f32g)
    head = whole(params["wte"].T, axes["wte"][::-1])
    logits = dense(x, head, f32_param_grads=f32g).astype(jnp.float32)
    return shard_activations(logits, mesh, rules, "vocab")


def apply(
    params: Dict[str, Any], tokens: jax.Array, cfg: GPT2Config,
    mesh: Optional[Mesh] = None, *, return_aux: bool = False,
    rules: Optional[ShardingRules] = None,
):
    """tokens [B, T] int32 -> logits [B, T, V] (f32).

    With ``return_aux=True`` returns ``(logits, aux)`` where aux is the
    MoE load-balance loss (0 for dense configs).  ``rules``: the table the
    parameters were placed with, when it is not ``rules_for_mesh(mesh)``."""
    x = embed(params, tokens, cfg, mesh=mesh, rules=rules)
    x, aux = apply_stack(x, params["blocks"], cfg, mesh, rules)
    logits = unembed(params, x, cfg, mesh, rules)
    return (logits, aux) if return_aux else logits


def loss_fn(
    params: Dict[str, Any], batch: Dict[str, jax.Array], cfg: GPT2Config,
    mesh: Optional[Mesh] = None, rules: Optional[ShardingRules] = None,
) -> jax.Array:
    """Next-token cross entropy. batch: {"tokens": [B, T+1]} or
    {"inputs": [B,T], "targets": [B,T]}."""
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    logits, aux = apply(params, inputs, cfg, mesh, return_aux=True, rules=rules)
    loss = cross_entropy_loss(logits, targets)
    if cfg.n_experts > 0:
        loss = loss + cfg.moe_aux_weight * aux
    return loss


def make_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                   warmup: int = 100, total_steps: int = 10000):
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(total_steps, warmup + 1), lr * 0.1
    )
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay,
                    mask=lambda p: jax.tree.map(lambda x: x.ndim >= 2, p)),
    )


def make_train_step(cfg: GPT2Config, optimizer, mesh: Optional[Mesh] = None,
                    rules: Optional[ShardingRules] = None):
    """Returns train_step(state, batch) -> (state, metrics); jit/pjit-able,
    donate state for in-place updates."""
    from ray_tpu.models.transformer import make_train_step_from_loss

    return make_train_step_from_loss(loss_fn, cfg, optimizer, mesh, rules)


def init_state(cfg: GPT2Config, key: jax.Array, optimizer) -> Dict[str, Any]:
    params = init(cfg, key)
    return {
        "params": params,
        "opt_state": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }


def num_params(params: Dict[str, Any]) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
