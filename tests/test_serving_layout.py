"""What an engine lays out ONCE when it takes its parameters (``generate.
serving_layout``): a family's held experts' gate and up matrices side by side
as one leaf, ``ew_gate_up``, so that the experts' SwiGLU is two grouped
matmuls and not three.  ``init`` keeps making ``ew_gate`` and ``ew_up`` (what
a reference reads), an engine holds neither, and the expert layer over the
served layout is the SwiGLU written out over ``init``'s separate leaves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_harness import served_layer, sigmoid_top_k_by_hand, tiny_model

from ray_tpu.models import generate as gen
from ray_tpu.serve.llm import GenerationEngine

F32_TOL = 2e-4  # the families' own (tests/test_exaone_moe.py)


def _sigmoid(h, p, cfg):
    return sigmoid_top_k_by_hand(h, p, cfg.experts_per_token, cfg.routed_scale)


def _softmax(h, p, cfg):
    chosen, sel = jax.lax.top_k(h @ p["router"], cfg.experts_per_token)
    return sel, jax.nn.softmax(chosen, -1)


# a family: where its tree holds the expert layers (a dict each, a stack is
# one), the expert layer this case runs, its router written out
EXPERT_FAMILIES = {
    "exaone_moe": (lambda t: t["layers"], lambda t: t["layers"][1], _sigmoid),
    "kimi_k2": (lambda t: t["layers"], lambda t: t["layers"][1], _sigmoid),
    "dots3_note": (lambda t: t["layers"], lambda t: t["layers"][1], _sigmoid),
    "granite_hybrid": (lambda t: [t["mamba"], *t["attention"]],
                       lambda t: t["attention"][0], _softmax),
    # (every expert held, no shared expert)
    "keye_vl": (lambda t: t["layers"], lambda t: t["layers"][1], _softmax),
}


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


@pytest.mark.parametrize("family", list(EXPERT_FAMILIES))
def test_an_engine_serves_from_gate_and_up_side_by_side(family):
    layers, one, route = EXPERT_FAMILIES[family]
    cfg, params = tiny_model(family)
    mod = gen.FAMILIES[family]
    made = [dict(p) for p in layers(params)]  # init's leaves, as they stand
    eng = GenerationEngine(cfg, params, n_slots=2, max_new_tokens=4,
                           decode_chunk_steps=2, prefill_buckets=(8,))
    try:
        sparse = 0
        for p, q in zip(made, layers(eng.params)):
            assert "ew_gate" not in q and "ew_up" not in q
            if "ew_gate" not in p:  # a dense layer: the leaves init made
                assert set(q) == set(p)
                continue
            sparse += 1
            assert set(q) == set(p) - {"ew_gate", "ew_up"} | {"ew_gate_up"}
            np.testing.assert_array_equal(
                np.asarray(q["ew_gate_up"]), np.concatenate(
                    [np.asarray(p["ew_gate"]), np.asarray(p["ew_up"])], -1))
            assert q["ew_down"] is p["ew_down"]
        assert sparse >= 2
        # laid out already: nothing more to do, and nothing is copied
        again = gen.serving_layout(cfg, jax.tree.map(lambda a: a, eng.params))
        assert all(a is b for a, b in zip(
            jax.tree.leaves(again), jax.tree.leaves(eng.params)))
    finally:
        eng.stop()
    # init's own tree still holds what it made, and init makes it again
    for p, now in zip(made, layers(params)):
        assert set(now) == set(p) and all(now[k] is p[k] for k in p)
    fresh = mod.init(cfg, jax.random.PRNGKey(0))
    assert (jax.tree.structure(fresh) == jax.tree.structure(params))
    for a, b in zip(jax.tree.leaves(fresh), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the expert layer over the served layout, against the SwiGLU written out
    # over init's separate leaves: the held experts' part and the shared one
    p = one(params)
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 9, cfg.d_model))
    sparse_ffn = getattr(mod, "_sparse_ffn", None) or gen.FAMILIES[
        "exaone_moe"]._sparse_ffn
    got, routed = jax.jit(
        lambda h, p: sparse_ffn(h, p, cfg, None))(h, served_layer(p))
    sel, gates = route(h, p, cfg)
    first, n_held = cfg.experts_held
    want = _swiglu(h, p["sw_gate"], p["sw_up"], p["sw_down"]
                   ) if "sw_gate" in p else jnp.zeros_like(h)
    for e in range(n_held):
        g = jnp.where(sel == first + e, gates, 0.0).sum(-1)
        want = want + g[..., None] * _swiglu(
            h, p["ew_gate"][e], p["ew_up"][e], p["ew_down"][e])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < F32_TOL
    assert int(routed["tokens"].sum()) == int(
        ((sel >= first) & (sel < first + n_held)).sum()) > 0


@pytest.mark.parametrize("family", sorted(set(gen.FAMILIES) - set(EXPERT_FAMILIES)))
def test_a_family_without_experts_serves_from_inits_tree(family):
    """No hook, no step: the tree comes back as it went in, so the family's
    programs are built from what they were built from."""
    cfg, params = tiny_model(family)
    assert not hasattr(gen.FAMILIES[family], "serving_layout")
    assert gen.serving_layout(cfg, params) is params
