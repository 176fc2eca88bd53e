"""Llama-family decoder LM: RMSNorm + RoPE + SwiGLU + grouped-query
attention, pure jax.

Same design rules as :mod:`ray_tpu.models.gpt2` (the reference delegates
model parallelism to torch; here sharding annotations ARE the
parallelism): stacked ``[L, ...]`` block params scanned with one remat'd
body, bf16 compute over f32 master weights, logical axes feeding
:mod:`ray_tpu.parallel.sharding` (heads/mlp → tp, embed → fsdp, sequence →
sp ring attention when the mesh has an ``sp`` axis).  GQA shares each KV
head across ``n_heads // n_kv_heads`` query heads — the standard
long-context memory saver (KV cache and KV projections shrink by that
factor).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models.gpt2 import make_optimizer  # same AdamW recipe
from ray_tpu.models.transformer import (
    _attend,
    apply_stack,
    make_train_step_from_loss,
)
from ray_tpu.ops.layers import cross_entropy_loss, dense, rmsnorm, rope
from ray_tpu.parallel.sharding import (
    ShardingRules,
    fsdp_engaged,
    gather_for_compute,
    logical_to_sharding,
    shard_activations,
)

__all__ = [
    "LlamaConfig", "init", "apply", "block", "embed", "unembed", "kv_heads",
    "loss_fn", "make_train_step",
    "init_state", "num_params", "logical_axes", "param_shardings",
    "make_optimizer",
]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32_000
    n_layers: int = 12
    n_heads: int = 12
    n_kv_heads: int = 4
    d_model: int = 768
    d_ff: int = 2048
    max_seq_len: int = 2048
    rope_base: float = 10_000.0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "dots" saves matmul outputs and recomputes elementwise (the same
    # policy the shared transformer core uses); "full" recomputes
    # everything.  No benchmark cell runs this family.
    remat_policy: str = "dots"

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def q_per_kv(self) -> int:
        assert self.n_heads % self.n_kv_heads == 0
        return self.n_heads // self.n_kv_heads

    @staticmethod
    def llama_125m(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        base = dict(vocab_size=512, n_layers=2, n_heads=4, n_kv_heads=2,
                    d_model=64, d_ff=128, max_seq_len=128, remat=False)
        base.update(kw)
        return LlamaConfig(**base)


# the family table (ray_tpu.models.generate.FAMILIES) reads these two: the
# config class, and the presets ``size`` names
Config = LlamaConfig
SIZES = {"small": LlamaConfig.llama_125m, "125m": LlamaConfig.llama_125m,
         "tiny": LlamaConfig.tiny}


def _dense(key, n_in, n_out, scale=1.0):
    return jax.random.normal(key, (n_in, n_out)) * scale / jnp.sqrt(n_in)


def init(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Stacked block params: every leaf carries a leading [L] axis."""
    k_emb, k_blocks = jax.random.split(key)
    L, D, H, KV, hd, F = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                          cfg.n_kv_heads, cfg.head_dim, cfg.d_ff)
    ks = jax.random.split(k_blocks, 7)

    def stack(k, *shape, scale=1.0):
        keys = jax.random.split(k, L)
        return jnp.stack([_dense(kk, *shape, scale=scale) for kk in keys])

    blocks = {
        "wq": stack(ks[0], D, H * hd),
        "wk": stack(ks[1], D, KV * hd),
        "wv": stack(ks[2], D, KV * hd),
        "wo": stack(ks[3], H * hd, D, scale=0.02),
        # SwiGLU: gate + up fused side by side, then down
        "w_gate": stack(ks[4], D, F),
        "w_up": stack(ks[5], D, F),
        "w_down": stack(ks[6], F, D, scale=0.02),
        "attn_norm": jnp.ones((L, D)),
        "ffn_norm": jnp.ones((L, D)),
    }
    return {
        "tok_emb": jax.random.normal(k_emb, (cfg.vocab_size, D)) * 0.02,
        "blocks": blocks,
        "final_norm": jnp.ones(D),
    }


def logical_axes(cfg: Optional[LlamaConfig] = None) -> Dict[str, Any]:
    return {
        "tok_emb": ("vocab", "embed"),
        "blocks": {
            "wq": (None, "embed", "heads"),
            "wk": (None, "embed", "heads"),
            "wv": (None, "embed", "heads"),
            "wo": (None, "heads", "embed"),
            "w_gate": (None, "embed", "mlp"),
            "w_up": (None, "embed", "mlp"),
            "w_down": (None, "mlp", "embed"),
            "attn_norm": (None, "embed"),
            "ffn_norm": (None, "embed"),
        },
        "final_norm": ("embed",),
    }


def param_shardings(mesh: Mesh, rules: ShardingRules, cfg: Optional[LlamaConfig] = None):
    return logical_to_sharding(logical_axes(cfg), mesh, rules)


def kv_heads(cfg: LlamaConfig) -> int:
    """K/V heads a cache holds for a position (the GQA saving)."""
    return cfg.n_kv_heads


def block(x, p, cfg: LlamaConfig, attend=None, positions=None,
          mesh: Optional[Mesh] = None):
    """One Llama block (RMSNorm/RoPE/GQA/SwiGLU).  x: [B, T, D] in cfg.dtype;
    ``positions`` [T] or [B, T] for the rotary embedding (None: 0..T-1).
    ``attend``: the attention middle (:mod:`ray_tpu.models.transformer`),
    given post-rope q and k, v in the KV-head layout a cache stores.
    Returns ``(x, aux, carried)``; aux is 0 (no experts in this family)."""
    B, T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    attend = attend or partial(_attend, causal=True, mesh=mesh)
    positions = jnp.arange(T) if positions is None else positions
    # under fsdp the batch is spread over chips: sum the parameters'
    # gradients over it in float32
    f32g = fsdp_engaged(mesh, x)
    lin = partial(dense, f32_param_grads=f32g)
    norm = partial(rmsnorm, eps=cfg.rms_eps, f32_param_grads=f32g)

    h = norm(x, p["attn_norm"])
    q = lin(h, p["wq"]).reshape(B, T, H, hd)
    k = lin(h, p["wk"]).reshape(B, T, KV, hd)
    v = lin(h, p["wv"]).reshape(B, T, KV, hd)
    q = rope(q.transpose(0, 2, 1, 3), positions, base=cfg.rope_base)  # [B,H,T,hd]
    k = rope(k.transpose(0, 2, 1, 3), positions, base=cfg.rope_base)  # [B,KV,T,hd]
    o, carried = attend(q, k, v.transpose(0, 2, 1, 3))  # [B, H, T, hd]
    o = o.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
    x = x + lin(o, p["wo"])

    h = norm(x, p["ffn_norm"])
    gated = jax.nn.silu(lin(h, p["w_gate"])) * lin(h, p["w_up"])
    return x + lin(gated, p["w_down"]), jnp.zeros((), jnp.float32), carried


def embed(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig,
          positions: Optional[jax.Array] = None,
          mesh: Optional[Mesh] = None,
          rules: Optional[ShardingRules] = None) -> jax.Array:
    """tokens [B, T] -> x [B, T, D] in cfg.dtype (``positions`` is not used:
    this family's positions are the blocks' rotary embedding)."""
    return shard_activations(params["tok_emb"][tokens].astype(cfg.dtype),
                             mesh, rules)


def unembed(params: Dict[str, Any], x: jax.Array, cfg: LlamaConfig,
            mesh: Optional[Mesh] = None,
            rules: Optional[ShardingRules] = None) -> jax.Array:
    """Final norm and the tied LM head: x [B, T, D] -> logits [B, T, V] f32
    (under an fsdp mesh axis, as in transformer.apply_stack: parameters
    whole along fsdp in cfg.dtype, logits on the batch, parameter gradients
    summed in float32; else the helpers do nothing)."""
    axes = logical_axes(cfg)
    whole = lambda w, axes: gather_for_compute(  # noqa: E731
        w, axes, mesh, rules, cfg.dtype)
    f32g = fsdp_engaged(mesh, x)
    x = rmsnorm(x, whole(params["final_norm"], axes["final_norm"]),
                eps=cfg.rms_eps, f32_param_grads=f32g)
    head = whole(params["tok_emb"].T, axes["tok_emb"][::-1])
    logits = dense(x, head, f32_param_grads=f32g).astype(jnp.float32)
    return shard_activations(logits, mesh, rules, "vocab")


def apply(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig,
          mesh: Optional[Mesh] = None,
          rules: Optional[ShardingRules] = None) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, V] f32 (tied embeddings).
    ``rules``: the table the parameters were placed with, when it is not
    ``rules_for_mesh(mesh)``."""
    x = embed(params, tokens, cfg, mesh=mesh, rules=rules)
    x, _ = apply_stack(x, params["blocks"], cfg, mesh, rules, block=block,
                       axes=logical_axes(cfg)["blocks"])
    return unembed(params, x, cfg, mesh, rules)


def loss_fn(params, batch, cfg: LlamaConfig, mesh: Optional[Mesh] = None,
            rules: Optional[ShardingRules] = None):
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    return cross_entropy_loss(apply(params, inputs, cfg, mesh, rules), targets)


def make_train_step(cfg: LlamaConfig, optimizer, mesh: Optional[Mesh] = None,
                    rules: Optional[ShardingRules] = None):
    return make_train_step_from_loss(loss_fn, cfg, optimizer, mesh, rules)


def init_state(cfg: LlamaConfig, key: jax.Array, optimizer) -> Dict[str, Any]:
    params = init(cfg, key)
    return {"params": params, "opt_state": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def num_params(params: Dict[str, Any]) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
