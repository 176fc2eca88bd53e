"""Mesh / sharding / in-jit collective tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel import MeshSpec, create_mesh
from ray_tpu.parallel import collective as col
from ray_tpu.parallel.sharding import (
    FSDP_TP_RULES,
    ShardingRules,
    infer_sharding,
    rules_for_mesh,
)


def test_mesh_spec_resolve():
    assert MeshSpec(dp=-1, tp=4).resolve(8) == {
        "pp": 1, "dp": 2, "fsdp": 1, "ep": 1, "sp": 1, "tp": 4
    }
    with pytest.raises(ValueError):
        MeshSpec(dp=3, tp=4).resolve(8)
    with pytest.raises(ValueError):
        MeshSpec(dp=-1, tp=-1).resolve(8)


def test_create_mesh_axis_order():
    mesh = create_mesh(MeshSpec(dp=2, tp=4))
    assert mesh.axis_names == ("dp", "tp")
    assert mesh.devices.shape == (2, 4)
    # tp is innermost: adjacent devices share a dp row
    flat = mesh.devices.reshape(-1)
    assert flat[0] is mesh.devices[0, 0] and flat[1] is mesh.devices[0, 1]


def test_create_mesh_single_axis_fallback():
    mesh = create_mesh(MeshSpec(), devices=jax.devices()[:1])
    assert mesh.axis_names == ("dp",)


def test_sharding_rules_spec():
    rules = ShardingRules(batch=("dp", "fsdp"), embed="fsdp", mlp="tp")
    assert rules.spec(("batch", None)) == P(("dp", "fsdp"), None)
    assert rules.spec(("embed", "mlp")) == P("fsdp", "tp")
    updated = rules.update(mlp=None)
    assert updated.spec(("embed", "mlp")) == P("fsdp", None)


def test_rules_for_mesh():
    mesh = create_mesh(MeshSpec(fsdp=2, tp=4))
    rules = rules_for_mesh(mesh)
    assert rules.rules["batch"] == "fsdp"
    assert rules.rules["mlp"] == "tp"
    assert rules.rules["seq"] is None


def test_infer_sharding_shards_largest_divisible_dim():
    mesh = create_mesh(MeshSpec(fsdp=8))
    params = {"w": jnp.zeros((16, 128)), "b": jnp.zeros((4,))}
    shardings = infer_sharding(params, mesh, FSDP_TP_RULES)
    assert shardings["w"].spec == P(None, "fsdp")
    assert shardings["b"].spec == P()  # too small -> replicated


def test_collectives_in_shard_map():
    mesh = create_mesh(MeshSpec(dp=8))
    x = jnp.arange(8.0)

    def body(x):
        s = col.allreduce(x, "dp")
        g = col.allgather(x, "dp")
        b = col.broadcast(x, "dp", root=3)
        r = col.ppermute_next(x, "dp", shift=1)
        return s, g, b, r

    f = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=P("dp"),
            out_specs=(P("dp"), P(None), P("dp"), P("dp")),
            check_vma=False,
        )
    )
    s, g, b, r = f(x)
    np.testing.assert_allclose(s, np.full(8, 28.0))
    np.testing.assert_allclose(g, np.arange(8.0))
    np.testing.assert_allclose(b, np.full(8, 3.0))
    # ring shift by 1: device i's value moves to device i+1
    np.testing.assert_allclose(r, np.roll(np.arange(8.0), 1))


def test_reducescatter_in_shard_map():
    mesh = create_mesh(MeshSpec(dp=8))
    x = jnp.ones((8, 8))

    # the DDP-gradient shape: every device holds the full tensor, each ends
    # up owning the reduced shard of its slice
    f = jax.jit(
        jax.shard_map(
            lambda x: col.reducescatter(x, "dp", scatter_axis=0),
            mesh=mesh, in_specs=P(None, None), out_specs=P("dp", None),
            check_vma=False,
        )
    )
    out = f(x)
    assert out.shape == (8, 8)
    np.testing.assert_allclose(out, np.full((8, 8), 8.0))


def test_grad_sync_pmean():
    mesh = create_mesh(MeshSpec(dp=8))
    grads = {"w": jnp.arange(8.0), "b": jnp.ones(8)}

    f = jax.jit(
        jax.shard_map(
            lambda g: col.grad_sync(g, "dp"),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        )
    )
    out = f(grads)
    np.testing.assert_allclose(out["w"], np.full(8, 3.5))
    np.testing.assert_allclose(out["b"], np.ones(8))


# a compiled program's text is its schedule: three forms of a collective as the
# TPU compiler writes them, and the one it fuses into the producer of its operand
_SCHEDULED = """
HloModule jit_step, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b)
}

%fused_computation.7 (p0: bf16[2,8]) -> (bf16[2,8], bf16[8,8]) {
  %p0 = bf16[2,8]{1,0} parameter(0)
  ROOT %all-gather.3 = bf16[8,8]{1,0} all-gather(%p0), channel_id=9, replica_groups=[1,4]<=[4], dimensions={0}, use_global_device_ids=true
}

%fused_computation.8 (p1: bf16[2,8]) -> bf16[8,8] {
  %p1 = bf16[2,8]{1,0} parameter(0)
  ROOT %all-gather.4 = bf16[8,8]{1,0} all-gather(%p1), channel_id=9, replica_groups=[1,4]<=[4], dimensions={0}, use_global_device_ids=true
}

%all-reduce-scatter.2 (p2: f32[8,8]) -> f32[2,8] {
  %p2 = f32[8,8]{1,0} parameter(0)
  %all-reduce.5 = f32[8,8]{1,0} all-reduce(%p2), channel_id=11, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add
  ROOT %slice.1 = f32[2,8]{1,0} slice(%all-reduce.5), slice={[0:2], [0:8]}
}

ENTRY %main (w: f32[2,8], g: f32[8,8]) -> f32[2,8] {
  %w = f32[2,8]{1,0} parameter(0)
  %g = f32[8,8]{1,0} parameter(1)
  %low = bf16[2,8]{1,0} convert(%w)
  %async-collective-start.1 = (bf16[2,8]{1,0}, bf16[8,8]{1,0}) fusion(%low), kind=kCustom, calls=%fused_computation.7
  %collective-permute-start.2 = (f32[2,8]{1,0}, f32[2,8]{1,0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%w), channel_id=1, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}, metadata={op_name="jit(step)/moe.exchange/ppermute"}
  %square = f32[8,8]{1,0} multiply(%g, %g)
  %root = f32[8,8]{1,0} sqrt(%square)
  %async-collective-done.1 = bf16[8,8]{1,0} fusion(%async-collective-start.1), kind=kCustom, calls=%fused_computation.8
  %half = f32[8,8]{1,0} multiply(%root, %root)
  %collective-permute-done.2 = f32[2,8]{1,0} collective-permute-done(%collective-permute-start.2), metadata={op_name="jit(step)/moe.exchange/ppermute"}
  %reduce_scatter.28 = f32[2,8]{1,0} reduce-scatter(%half), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add, metadata={op_name="jit(step)/moe.exchange/reduce_scatter"}
  %reduce_scatter.29 = f32[2,8]{1,0} reduce-scatter(%g), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add
  %fusion.4 = f32[2,8]{1,0} fusion(%g), kind=kCustom, calls=%all-reduce-scatter.2
  ROOT %out = f32[2,8]{1,0} add(%reduce_scatter.28, %collective-permute-done.2)
}
"""


def test_collective_profile_tells_a_pair_from_one_instruction():
    """Which collectives stand alone in the schedule and how far a start is
    from its done, in each spelling: ``-start`` / ``-done`` instructions, the
    TPU's ``async-collective-*`` pair of fusions (the collective cloned into
    both, counted once), the ``reduce-scatter`` that ``jax.lax.psum_scatter``
    names ``%reduce_scatter.N`` (two by hand on one channel are two), and the
    one fused into its producer, which is neither."""
    from ray_tpu.parallel.sharding import collective_profile

    profile = collective_profile(_SCHEDULED)
    assert not any(places["in_loop"]["count"] for places in profile.values())
    outside = {kind: places["outside"] for kind, places in profile.items()}
    counted = {kind: (e["count"], e["synchronous"], e["start_to_done"])
               for kind, e in outside.items() if e["count"]}
    assert counted == {
        "all-gather": (1, 0, [3]),
        "collective-permute": (1, 0, [4]),
        "reduce-scatter": (3, 2, []),
    }, counted
    assert outside["reduce-scatter"]["max_operand_bytes"] == 8 * 8 * 4
    assert "f32[2,8]" in outside["collective-permute"]["shapes"]
    assert outside["all-gather"]["shapes"] == ["bf16[2,8]", "bf16[8,8]"]


@pytest.mark.parametrize(
    "sizes,own_rules",
    [({"fsdp": 4}, None), ({"fsdp": 2, "tp": 2}, None), ({"dp": 2, "fsdp": 2}, None),
     # the caller's own table (fsdp shards the OTHER dimension of each
     # matrix), handed to the step as well as to param_shardings
     ({"fsdp": 4}, dict(embed=None, heads="fsdp", mlp="fsdp"))],
    ids=["fsdp4", "fsdp2xtp2", "dp2xfsdp2", "fsdp4-own-rules"])
def test_fsdp_step_collectives(sizes, own_rules):
    """Under an fsdp axis the compiled train step gathers WEIGHTS per layer
    and keeps activations on the batch: no collective inside the layer loop
    touches an array with the batch in its shape.  The step is compiled the
    way benchmark/drivers/train.py compiles the fsdp4 cell's.  (The dtypes
    that cross chips are the TPU compiler's to show: the CPU's widens every
    bf16 collective.  tests/test_chip_compile.py reads them.)"""
    import optax

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.sharding import collective_profile

    # sizes chosen so that no weight dimension (or shard of one) equals the
    # global batch 20, a chip's share of it, or the sequence length 48
    B, T = 20, 48
    cfg = gpt2.GPT2Config.gpt2_small(
        n_layers=3, n_heads=4, d_model=128, d_ff=512, vocab_size=1024,
        max_seq_len=T, remat_policy="full")
    optimizer = gpt2.make_optimizer(lr=3e-4, warmup=20)
    devices = jax.devices()[:4]
    mesh = create_mesh(dict(sizes), devices=devices)
    rules = rules_for_mesh(mesh)
    step_rules = ()
    if own_rules:
        rules = rules.update(**own_rules)
        step_rules = (rules,)
    replicated = NamedSharding(mesh, P())
    p_shard = gpt2.param_shardings(mesh, rules, cfg)
    make_state = lambda k: gpt2.init_state(cfg, k, optimizer)  # noqa: E731
    shapes = jax.eval_shape(make_state, jax.random.PRNGKey(0))
    o_shard = optax.tree_map_params(
        optimizer, lambda _, s: s, shapes["opt_state"], p_shard,
        transform_non_params=lambda _: replicated)
    s_shard = {"params": p_shard, "opt_state": o_shard, "step": replicated}
    step = jax.jit(gpt2.make_train_step(cfg, optimizer, mesh, *step_rules),
                   donate_argnums=(0,), out_shardings=(s_shard, None))
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        shapes, s_shard)
    tokens = jax.ShapeDtypeStruct(
        (B, T), jnp.int32,
        sharding=NamedSharding(mesh, P(rules.rules["batch"], None)))
    compiled = step.lower(state, {"inputs": tokens, "targets": tokens}).compile()
    profile = collective_profile(compiled)

    def dims(label):
        return [int(d) for d in label[label.index("[") + 1:-1].split(",") if d]

    fsdp, tp = mesh.shape["fsdp"], mesh.shape.get("tp", 1)
    n_batch = fsdp * mesh.shape.get("dp", 1)
    in_loop = {kind: places["in_loop"] for kind, places in profile.items()}
    assert in_loop["all-gather"]["count"] >= 4, profile  # wqkv, wo, w1, w2
    for kind, entry in in_loop.items():
        for label in entry["shapes"]:
            # (tp legitimately all-reduces a chip's OWN sequences)
            assert B not in dims(label), (
                f"{kind} of the whole batch in the layer loop: {label}")
            assert tp > 1 or T not in dims(label), (
                f"{kind} of an activation in the layer loop: {label}")
    # what the layer loop gathers is a layer's parameter, from the shard it
    # rests in to whole along fsdp (tp kept) -- with the caller's own table
    # too: pinned to another table's shards they would be re-sharded first
    def per_layer(x, spec):
        return tuple(NamedSharding(mesh, P(*spec)).shard_shape(x.shape)[1:])

    at_rest, whole = set(), set()
    for x, s in zip(jax.tree.leaves(shapes["params"]["blocks"]),
                    jax.tree.leaves(p_shard["blocks"])):
        spec = tuple(s.spec) + (None,) * (x.ndim - len(s.spec))
        at_rest.add(per_layer(x, spec))
        whole.add(per_layer(x, [None if e == "fsdp" else e for e in spec]))
    for label in in_loop["all-gather"]["shapes"]:
        shape = tuple(d for d in dims(label) if d != 1)
        assert shape in at_rest | whole, (label, at_rest, whole)
    assert not any({B, B // n_batch, T} & set(shape) for shape in at_rest | whole)

    # at rest nothing changed: a chip holds its share of the training state
    state_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    held = compiled.memory_analysis().argument_size_in_bytes
    if own_rules is None:
        assert state_bytes / (fsdp * tp) <= held <= 1.03 * state_bytes / fsdp, (
            held, state_bytes)
    placed = sum(
        int(np.prod(s.shard_shape(x.shape))) * x.dtype.itemsize
        for x, s in zip(jax.tree.leaves(shapes), jax.tree.leaves(s_shard)))
    assert held <= 1.03 * placed, (held, placed)
