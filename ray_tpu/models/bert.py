"""BERT-style bidirectional encoder classifier (BASELINE config 5).

The Serve-replica model: sequence classification with a [CLS] pooled head.
Same stacked-layer transformer core as GPT-2 but non-causal, plus
``from_hf`` to load real ``bert-base-uncased`` weights from a local
HuggingFace checkpoint when one is available.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models.transformer import (
    TransformerConfig,
    apply_stack,
    block_logical_axes,
    init_block_params,
)
from ray_tpu.ops.layers import layernorm
from ray_tpu.parallel.sharding import ShardingRules


@dataclasses.dataclass(frozen=True)
class BertConfig(TransformerConfig):
    vocab_size: int = 30592  # 30522 padded to a multiple of 128
    n_layers: int = 12
    n_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq_len: int = 512
    causal: bool = False
    post_ln: bool = True  # original BERT is post-LN; HF weights load faithfully
    num_classes: int = 2
    type_vocab_size: int = 2

    @staticmethod
    def base(**kw) -> "BertConfig":
        return BertConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "BertConfig":
        return BertConfig(
            vocab_size=512, n_layers=2, n_heads=4, d_model=64, d_ff=256,
            max_seq_len=128, remat=False, **kw,
        )


def init(cfg: BertConfig, key: jax.Array) -> Dict[str, Any]:
    ks = jax.random.split(key, 6)
    D = cfg.d_model
    return {
        "wte": jax.random.normal(ks[0], (cfg.vocab_size, D)) * 0.02,
        "wpe": jax.random.normal(ks[1], (cfg.max_seq_len, D)) * 0.02,
        "wtype": jax.random.normal(ks[2], (cfg.type_vocab_size, D)) * 0.02,
        "ln_emb_w": jnp.ones(D), "ln_emb_b": jnp.zeros(D),
        "blocks": init_block_params(cfg, ks[3]),
        "pool_w": jax.random.normal(ks[4], (D, D)) * 0.02,
        "pool_b": jnp.zeros(D),
        "cls_w": jax.random.normal(ks[5], (D, cfg.num_classes)) * 0.02,
        "cls_b": jnp.zeros(cfg.num_classes),
    }


def logical_axes(cfg: Optional["BertConfig"] = None) -> Dict[str, Any]:
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "wtype": (None, "embed"),
        "ln_emb_w": ("embed",), "ln_emb_b": ("embed",),
        "blocks": block_logical_axes(cfg.n_experts if cfg else 0),
        "pool_w": ("embed", None),  # one mesh axis shards one dimension
        "pool_b": ("embed",),
        "cls_w": ("embed", None),
        "cls_b": (None,),
    }


def apply(
    params: Dict[str, Any], tokens: jax.Array, cfg: BertConfig,
    token_types: Optional[jax.Array] = None, mesh: Optional[Mesh] = None,
    rules: Optional[ShardingRules] = None,
) -> jax.Array:
    """tokens [B, T] -> class logits [B, num_classes].  ``rules``: the table
    the parameters were placed with, when it is not ``rules_for_mesh(mesh)``."""
    B, T = tokens.shape
    x = params["wte"][tokens] + params["wpe"][:T]
    if token_types is not None:
        x = x + params["wtype"][token_types]
    x = layernorm(x, params["ln_emb_w"], params["ln_emb_b"]).astype(cfg.dtype)
    x, _ = apply_stack(x, params["blocks"], cfg, mesh, rules)
    cls = jnp.tanh(x[:, 0].astype(jnp.float32) @ params["pool_w"] + params["pool_b"])
    return cls @ params["cls_w"] + params["cls_b"]


def from_hf(model_name: str = "bert-base-uncased", num_classes: int = 2):
    """Load HF torch weights into this layout (requires a local checkpoint;
    the image has transformers but no network)."""
    import numpy as np
    from transformers import AutoModel

    hf = AutoModel.from_pretrained(model_name)
    sd = {k: np.asarray(v) for k, v in hf.state_dict().items()}
    cfg = BertConfig(num_classes=num_classes,
                     vocab_size=sd["embeddings.word_embeddings.weight"].shape[0])
    L, D = cfg.n_layers, cfg.d_model
    g = lambda k: jnp.asarray(sd[k])
    stack = lambda fmt, t=False: jnp.stack(
        [g(fmt.format(i)).T if t else g(fmt.format(i)) for i in range(L)]
    )
    params = {
        "wte": g("embeddings.word_embeddings.weight"),
        "wpe": g("embeddings.position_embeddings.weight"),
        "wtype": g("embeddings.token_type_embeddings.weight"),
        "ln_emb_w": g("embeddings.LayerNorm.weight"),
        "ln_emb_b": g("embeddings.LayerNorm.bias"),
        "blocks": {
            "ln1_w": stack("encoder.layer.{}.attention.output.LayerNorm.weight"),
            "ln1_b": stack("encoder.layer.{}.attention.output.LayerNorm.bias"),
            "wqkv": jnp.concatenate([
                stack("encoder.layer.{}.attention.self.query.weight", t=True),
                stack("encoder.layer.{}.attention.self.key.weight", t=True),
                stack("encoder.layer.{}.attention.self.value.weight", t=True),
            ], axis=-1),
            "bqkv": jnp.concatenate([
                stack("encoder.layer.{}.attention.self.query.bias"),
                stack("encoder.layer.{}.attention.self.key.bias"),
                stack("encoder.layer.{}.attention.self.value.bias"),
            ], axis=-1),
            "wo": stack("encoder.layer.{}.attention.output.dense.weight", t=True),
            "bo": stack("encoder.layer.{}.attention.output.dense.bias"),
            "ln2_w": stack("encoder.layer.{}.output.LayerNorm.weight"),
            "ln2_b": stack("encoder.layer.{}.output.LayerNorm.bias"),
            "w1": stack("encoder.layer.{}.intermediate.dense.weight", t=True),
            "b1": stack("encoder.layer.{}.intermediate.dense.bias"),
            "w2": stack("encoder.layer.{}.output.dense.weight", t=True),
            "b2": stack("encoder.layer.{}.output.dense.bias"),
        },
        "pool_w": g("pooler.dense.weight").T,
        "pool_b": g("pooler.dense.bias"),
        "cls_w": jnp.zeros((D, num_classes)),
        "cls_b": jnp.zeros(num_classes),
    }
    return cfg, params
