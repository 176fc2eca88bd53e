"""Model zoo: pure-jax pytree models with logical sharding axes.

Models are (init, apply) pairs over plain dict pytrees — no framework
classes on the hot path, so pjit sees exactly the arrays and the sharding
rules in :mod:`ray_tpu.parallel.sharding` apply mechanically.  Families:

- :mod:`ray_tpu.models.gpt2` — the flagship decoder LM (BASELINE config 3:
  GPT-2 125M, FSDP/TP/SP-shardable, ring attention for long context).
- :mod:`ray_tpu.models.bert` — bidirectional encoder classifier
  (BASELINE config 5: the Serve replica model).
- :mod:`ray_tpu.models.llama` — Llama-family decoder (RMSNorm/RoPE/
  SwiGLU/grouped-query attention; long-context + GQA KV savings).
- :mod:`ray_tpu.models.mlp` — MNIST-class MLP (BASELINE config 2).
- :mod:`ray_tpu.models.smallthinker` — a sparse decoder TRAINED (SmallThinker-
  21B-A3B): ReGLU experts in every layer, routed from the layer's input, a
  per-layer choice of rotary and window from two layouts; a loss with the
  load-balance term, a train step, logical axes, the experts spread over the
  chips and brought to each chip's tokens
  (:func:`ray_tpu.ops.moe.experts_ffn_train`).
  Trained, not served: it is not in ``generate.FAMILIES``.  Reference:
  ``benchmark/reference/smallthinker_ref.py``; cell:
  ``train-smallthinker-21b-a3b-ep4-8k``.

A transformer family writes its block once; training, prefill and decode
share it through one seam, the attention middle the block takes as an
argument (:mod:`ray_tpu.models.transformer`).  Which families can generate
is the table ``FAMILIES`` of :mod:`ray_tpu.models.generate`.
"""

from ray_tpu.models import bert, gpt2, llama, mlp  # noqa: F401
from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.models.bert import BertConfig
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.mlp import MLPConfig

__all__ = [
    "gpt2", "bert", "llama", "mlp",
    "GPT2Config", "BertConfig", "LlamaConfig", "MLPConfig",
]
