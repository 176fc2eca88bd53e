"""SmallThinker-family decoder LM (SmallThinker-21BA3B), TRAINED: a sparse
model with a loss, a train step and logical axes, its experts spread over the
chips.  Pure jax.  Not in ``generate.FAMILIES``: the engine's registry asks
for cache hooks, and served this family would be K-EXAONE's layers at other
numbers (:mod:`ray_tpu.models.exaone_moe`).

What it has that no trained family here had: EVERY layer's FFN is sparse
(``n_experts`` ReGLU experts of width ``d_expert``, ``experts_per_token``
active, no shared expert, no dense layer); the router reads the layer's INPUT,
before the attention norm, so a layer's routing is known before its attention
runs; and a layer's attention is chosen by TWO per-layer layouts:
``rope_layout[l]`` (rotary or no position encoding at all) and
``sliding_window_layout[l]`` (a band of ``sliding_window`` or every earlier
position).  The published pattern pairs them (a NoPE global layer, then three
rotary window layers), the block takes each on its own.  The layers are a
LIST (``params["layers"]``), run unrolled, each recomputed in the backward
pass (``remat``) but for its attention's result and its experts' sort.

Layer ``l`` with input ``x`` (``cfg.dtype`` stream, float32 accumulation;
router, softmax and loss in float32)::

    z   = x W_r                           # [E] logits from the layer's INPUT, float32 "highest"
    S   = top_k(z);  g = softmax(z[S])    # the choice carries no gradient, the gates do
    h   = RMSNorm(x; w_a)
    q, k, v = h W_q, h W_k, h W_v         # n_heads | n_kv_heads | n_kv_heads of head_dim, no bias
    if rope_layout[l]:  q, k = rotary(q, k)          # rotate-half, every dimension
    o   = causal softmax(q k^T / sqrt(head_dim)) v   # i - window < j <= i if sliding_window_layout[l]
    x   = x + o W_o
    h2  = RMSNorm(x; w_f)
    x   = x + sum_{e in S} g_e W_d,e (act(W_g,e h2) * W_u,e h2)     # act: relu

    L   = CE(RMSNorm(x_L; w) W_head, targets) + aux_weight * sum_l E * sum_e f_le P_le
    f_le: share of the step's (token, slot) pairs routed to e;  P_le: mean_t softmax(z_t)[e]

Under a mesh (:func:`make_train_step`), ONE axis carries the batch, the
non-expert parameters (``embed -> fsdp``: gathered a layer at a time,
:func:`ray_tpu.parallel.sharding.gather_for_compute`) and the experts
(``expert -> fsdp``: a chip holds 1 / fsdp of a layer's experts and is
brought the others for its own tokens, a layer at a time; their gradients go
home a chip's block at a time under the layer's backward matmuls,
:func:`ray_tpu.ops.moe.experts_ffn_train`).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models.exaone_moe import rope_half
from ray_tpu.models.gpt2 import make_optimizer  # noqa: F401 — the trained families' one optimizer
from ray_tpu.models.transformer import _attend, make_train_step_from_loss
from ray_tpu.ops.attention import FLASH_RESIDUALS
from ray_tpu.ops.layers import cross_entropy_loss, dense, rmsnorm
from ray_tpu.ops.moe import EXPERTS_SORT, experts_ffn_train
from ray_tpu.parallel.sharding import (
    ShardingRules,
    fsdp_engaged,
    gather_for_compute,
    logical_to_sharding,
    rules_for_mesh,
    shard_activations,
)

__all__ = [
    "SmallThinkerConfig", "init", "block", "embed", "unembed", "apply",
    "loss_fn", "logical_axes", "param_shardings", "sharding_rules",
    "make_train_step", "make_optimizer", "init_state", "num_params",
    "named_leaves", "pick",
]

# what the jitted step is called, and so its program in a device trace
STEP_NAME = "sparse_lm_step"


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151_936
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    d_model: int = 2560
    d_expert: int = 768
    n_experts: int = 64
    experts_per_token: int = 6
    activation: str = "relu"      # ReGLU; "silu": SwiGLU through the same matmuls
    # per layer, as published (1: rotary | a window layer); longer lists are
    # read up to n_layers.  Left empty: 0, 1, 1, 1 repeated, both
    rope_layout: tuple = ()
    sliding_window_layout: tuple = ()
    sliding_window: int = 4096
    rope_base: float = 1_500_000.0
    rms_eps: float = 1e-6
    max_seq_len: int = 16_384
    aux_weight: float = 0.01      # the load-balance term's
    dtype: Any = jnp.bfloat16
    # a layer's backward pass recomputes the layer, but for what costs most to
    # redo and little to hold: the flash pair's result and logsumexp and the
    # sort of the experts' pairs are kept, so it runs neither the forward
    # kernel nor the sorts again (nor the experts' down matmul and the way
    # back to the tokens: their backward pass needs the result of neither,
    # ``ops.moe._experts_block_train``)
    remat: bool = True

    def __post_init__(self):
        L = self.n_layers
        for name in ("rope_layout", "sliding_window_layout"):
            kinds = tuple(int(v) for v in getattr(self, name))[:L] or tuple(
                int(l % 4 != 0) for l in range(L))
            assert len(kinds) == L and set(kinds) <= {0, 1}, (name, kinds)
            object.__setattr__(self, name, kinds)  # a jit-closed config hashes

    @property
    def sliding_windows(self) -> tuple:
        """Per layer, the positions it attends (0: every earlier one)."""
        return tuple(self.sliding_window * w for w in self.sliding_window_layout)

    @staticmethod
    def smallthinker_21b(**kw) -> "SmallThinkerConfig":
        return SmallThinkerConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "SmallThinkerConfig":
        base = dict(vocab_size=256, n_layers=4, n_heads=4, n_kv_heads=2,
                    head_dim=16, d_model=32, d_expert=24, n_experts=8,
                    experts_per_token=2, sliding_window=8, max_seq_len=256,
                    dtype=jnp.float32, remat=False)
        base.update(kw)
        return SmallThinkerConfig(**base)


Config = SmallThinkerConfig
SIZES = {"21b-a3b": SmallThinkerConfig.smallthinker_21b,
         "tiny": SmallThinkerConfig.tiny}

_LAYER_AXES = {
    "attn_norm": ("embed",), "ffn_norm": ("embed",),
    "wq": ("embed", "heads"), "wk": ("embed", "kv"), "wv": ("embed", "kv"),
    "wo": ("heads", "embed"), "router": ("embed", None),
    # an expert's matrices stay whole on the chip that holds it
    "ew_gate_up": ("expert", None, None), "ew_down": ("expert", None, None),
}
_TOP_AXES = {"tok_emb": ("vocab", "embed"), "head": ("embed", "vocab"),
             "final_norm": ("embed",)}


def init(cfg: SmallThinkerConfig, key: jax.Array) -> Dict[str, Any]:
    """Float32 masters: ``{"tok_emb" [V, D], "head" [D, V] (untied),
    "final_norm", "layers": [one dict a layer]}``; a layer's experts as TWO
    leaves, ``ew_gate_up [E, D, 2F]`` (gate's columns then up's: the layout
    the grouped matmuls take) and ``ew_down [E, F, D]``.  Fan-in scaled
    normals, the two projections onto the stream at half of it; norm scales
    one."""
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, F = cfg.n_experts, cfg.d_expert
    k_emb, k_head, k_layers = jax.random.split(key, 3)

    def layer(l):
        keys = iter(jax.random.split(jax.random.fold_in(k_layers, l), 7))
        w = lambda *shape, scale=1.0: (  # noqa: E731
            jax.random.normal(next(keys), shape) * (scale * shape[-2] ** -0.5))
        return {
            "attn_norm": jnp.ones((D,)), "ffn_norm": jnp.ones((D,)),
            "wq": w(D, H * hd), "wk": w(D, KV * hd), "wv": w(D, KV * hd),
            "wo": w(H * hd, D, scale=0.5), "router": w(D, E),
            "ew_gate_up": w(E, D, 2 * F), "ew_down": w(E, F, D, scale=0.5),
        }

    return {
        "tok_emb": jax.random.normal(k_emb, (cfg.vocab_size, D)),
        "head": jax.random.normal(k_head, (D, cfg.vocab_size)) * D ** -0.5,
        "final_norm": jnp.ones((D,)),
        "layers": [layer(l) for l in range(cfg.n_layers)],
    }


def logical_axes(cfg: SmallThinkerConfig) -> Dict[str, Any]:
    return {**_TOP_AXES, "layers": [dict(_LAYER_AXES)] * cfg.n_layers}


def sharding_rules(mesh: Mesh) -> ShardingRules:
    """The mesh's default table with the experts on the axis that carries the
    batch and the non-expert parameters (``fsdp``), or on ``ep`` where the
    mesh has one: data, as every other placement here."""
    rules = rules_for_mesh(mesh)
    return rules if rules.rules.get("expert") else rules.update(
        expert=rules.rules.get("embed"))


def param_shardings(mesh: Mesh, rules: Optional[ShardingRules] = None,
                    cfg: Optional[SmallThinkerConfig] = None):
    return logical_to_sharding(
        logical_axes(cfg), mesh, rules or sharding_rules(mesh))


def _expert_axis(mesh: Optional[Mesh], rules: Optional[ShardingRules]):
    """The mesh axis the experts (and with them the step's tokens) are
    divided over, or None on one device."""
    if mesh is None:
        return None
    rules = rules or sharding_rules(mesh)
    axis = rules.rules.get("expert")
    assert axis is None or rules.rules.get("batch") == axis, (
        "the experts' axis carries the batch too", rules.rules)
    return axis


def block(x, p, cfg: SmallThinkerConfig, *, rope: bool, window: int,
          mesh: Optional[Mesh] = None, rules: Optional[ShardingRules] = None):
    """One layer.  ``x [B, T, D]`` in ``cfg.dtype``; ``rope``, ``window``: the
    layer's kind, each on its own (0: every earlier position).  Returns ``(x,
    routed)``: ``routed["pairs"] [E]`` the (token, slot) pairs each expert
    got, ``routed["aux"]`` the layer's load-balance term ``E * sum_e f_e
    P_e``."""
    B, T, D = x.shape
    H, KV, hd, E = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.n_experts
    f32g = fsdp_engaged(mesh, x)
    lin = partial(dense, f32_param_grads=f32g)
    norm = partial(rmsnorm, eps=cfg.rms_eps, f32_param_grads=f32g)
    x = shard_activations(x, mesh, rules)
    whole = lambda k, dtype=cfg.dtype: gather_for_compute(  # noqa: E731
        p[k], _LAYER_AXES[k], mesh, rules, dtype)

    with jax.named_scope("moe.router"):
        # from the layer's INPUT, un-normed; float32 at "highest" (a bf16 pass
        # over the logits moves the k-th place)
        logits = jnp.dot(x.reshape(B * T, D).astype(jnp.float32),
                         whole("router", jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        chosen, experts = jax.lax.top_k(logits, cfg.experts_per_token)
        gates = jax.nn.softmax(chosen, axis=-1)
        pairs = jnp.zeros((E,), jnp.float32).at[experts.reshape(-1)].add(1.0)
        share = jax.lax.stop_gradient(pairs / experts.size)
        aux = E * jnp.sum(share * jax.nn.softmax(logits, axis=-1).mean(0))

    h = norm(x, whole("attn_norm"))
    heads = lambda t, n: t.reshape(B, T, n, hd).transpose(0, 2, 1, 3)  # noqa: E731
    q, k, v = (heads(lin(h, whole(w)), n)
               for w, n in (("wq", H), ("wk", KV), ("wv", KV)))
    if rope:
        positions = jnp.arange(T)
        q, k = (rope_half(t, positions, cfg.rope_base) for t in (q, k))
    with jax.named_scope("attention.window" if window else "attention.full"):
        o, _ = _attend(q, k, v, causal=True, mesh=mesh, window=window)
    x = x + lin(o.transpose(0, 2, 1, 3).reshape(B, T, H * hd), whole("wo"))

    h = norm(x, whole("ffn_norm"))
    axis = _expert_axis(mesh, rules)
    y = experts_ffn_train(
        h.reshape(B * T, D), experts, gates, p["ew_gate_up"], p["ew_down"],
        activation=cfg.activation, mesh=mesh if axis else None, axis=axis)
    return x + y.reshape(B, T, D), {"pairs": pairs, "aux": aux}


def remat_policy():
    """What a replayed layer keeps (``cfg.remat``): the flash pair's result
    and logsumexp, and the sort of the experts' pairs."""
    return jax.checkpoint_policies.save_only_these_names(
        *FLASH_RESIDUALS, EXPERTS_SORT)


def embed(params: Dict[str, Any], tokens: jax.Array, cfg: SmallThinkerConfig,
          mesh: Optional[Mesh] = None,
          rules: Optional[ShardingRules] = None) -> jax.Array:
    """tokens [B, T] -> x [B, T, D] in cfg.dtype (positions are the rotary
    layers' own)."""
    return shard_activations(
        params["tok_emb"][tokens].astype(cfg.dtype), mesh, rules)


def unembed(params: Dict[str, Any], x: jax.Array, cfg: SmallThinkerConfig,
            mesh: Optional[Mesh] = None,
            rules: Optional[ShardingRules] = None) -> jax.Array:
    """Final norm and the untied head over the slice of the vocabulary held:
    x [B, T, D] -> logits [B, T, V] float32, on the batch."""
    f32g = fsdp_engaged(mesh, x)
    whole = lambda k: gather_for_compute(  # noqa: E731
        params[k], _TOP_AXES[k], mesh, rules, cfg.dtype)
    x = rmsnorm(x, whole("final_norm"), eps=cfg.rms_eps, f32_param_grads=f32g)
    logits = dense(x, whole("head"), f32_param_grads=f32g).astype(jnp.float32)
    return shard_activations(logits, mesh, rules, "vocab")


def apply(params: Dict[str, Any], tokens: jax.Array, cfg: SmallThinkerConfig,
          mesh: Optional[Mesh] = None, *, return_routed: bool = False,
          rules: Optional[ShardingRules] = None):
    """tokens [B, T] int32 -> logits [B, T, V] f32; with ``return_routed``
    also ``{"pairs" [L, E], "aux" [L]}``."""
    rules = rules or (sharding_rules(mesh) if mesh is not None else None)
    x = embed(params, tokens, cfg, mesh, rules)
    policy = remat_policy()
    routed = []
    for p, rope, window in zip(
            params["layers"], cfg.rope_layout, cfg.sliding_windows):
        layer = partial(block, cfg=cfg, rope=bool(rope), window=window,
                        mesh=mesh, rules=rules)
        if cfg.remat:
            layer = jax.checkpoint(layer, policy=policy)
        x, r = layer(x, p)
        routed.append(r)
    with jax.named_scope("head_loss"):
        logits = unembed(params, x, cfg, mesh, rules)
    if not return_routed:
        return logits
    return logits, {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}


def loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array],
            cfg: SmallThinkerConfig, mesh: Optional[Mesh] = None,
            rules: Optional[ShardingRules] = None):
    """``(loss, counted)``: next-token cross entropy over the vocabulary held
    plus ``aux_weight`` times the layers' load-balance terms; ``counted``:
    ``ce``, ``aux`` (the sum over layers, unweighted) and ``routed_pairs [L,
    E]``.  batch: ``{"inputs", "targets"}`` or ``{"tokens": [B, T + 1]}``."""
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    logits, routed = apply(params, inputs, cfg, mesh, return_routed=True,
                           rules=rules)
    with jax.named_scope("head_loss"):
        ce = cross_entropy_loss(logits, targets)
    aux = routed["aux"].sum()
    return ce + cfg.aux_weight * aux, {
        "ce": ce, "aux": aux, "routed_pairs": routed["pairs"]}


def named_leaves(cfg: SmallThinkerConfig) -> Dict[str, list]:
    """The handful of parameters whose gradient norms a step reports (and a
    reference is asked for): ``{name: path}``, a path the keys and indices
    from the tree's root (:func:`pick`).  Every layer's router; one expert's
    matrices in each quarter of the experts (a chip's block under four-way
    expert parallelism), a layer each; the first global layer's ``wq``, the
    first window layer's ``wk``; the final norm."""
    L, E = cfg.n_layers, cfg.n_experts
    paths = [["layers", l, "router"] for l in range(L)]
    for c in range(4):
        e = min(c * (E // 4) + c, E - 1)
        paths += [["layers", c % L, w, e] for w in ("ew_gate_up", "ew_down")]
    kinds = cfg.sliding_window_layout
    paths.append(["layers", kinds.index(0) if 0 in kinds else 0, "wq"])
    paths.append(["layers", kinds.index(1) if 1 in kinds else 0, "wk"])
    paths.append(["final_norm"])
    return {".".join(map(str, path)): path for path in paths}


def pick(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def make_train_step(cfg: SmallThinkerConfig, optimizer,
                    mesh: Optional[Mesh] = None,
                    rules: Optional[ShardingRules] = None):
    """``train_step(state, batch) -> (state, metrics)``: jit-able, donate the
    state.  ``metrics``: ``loss``, ``step``, ``ce``, ``aux``, ``routed_pairs
    [L, E]`` and ``grad_norms {name: norm}`` of :func:`named_leaves`."""
    if mesh is not None and rules is None:
        rules = sharding_rules(mesh)
    leaves = named_leaves(cfg)

    def counters(grads):
        return {"grad_norms": {
            name: jnp.sqrt(jnp.sum(jnp.square(
                pick(grads, path).astype(jnp.float32))))
            for name, path in leaves.items()}}

    return make_train_step_from_loss(
        loss_fn, cfg, optimizer, mesh, rules, counters=counters, name=STEP_NAME)


def init_state(cfg: SmallThinkerConfig, key: jax.Array, optimizer) -> Dict[str, Any]:
    params = init(cfg, key)
    return {"params": params, "opt_state": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def num_params(params: Dict[str, Any]) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
