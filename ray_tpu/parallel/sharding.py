"""Logical-axis sharding rules: the jax analog of ``prepare_model``.

The reference wraps a torch module in DDP/FSDP for the user
(``python/ray/train/torch/train_loop_utils.py:51,71-74`` ``prepare_model``).
The TPU-native equivalent is declarative: parameters carry *logical* axis
names (e.g. ``("embed", "mlp")``) and a rule table maps logical axes to
mesh axes, producing ``NamedSharding``s that pjit consumes.

Annotating the parameters alone is not FSDP.  ``embed -> fsdp`` puts the
matmuls' CONTRACTION dimension and the batch on the same mesh axis, and a
partitioner that is only asked keeps the weights where they are, computes
a quarter of every contraction for the WHOLE batch on every chip and
all-reduces the activations.  So under a mesh whose ``fsdp`` axis is
larger than 1 the models tell it, with the two helpers here:

- :func:`gather_for_compute` — a layer's weights are cast to the compute
  dtype on the shard and all-gathered along ``fsdp`` for the computation
  that uses them (per layer, inside the remat'd scan body, so the gather
  is recomputed in the backward pass and never saved); their gradients
  are summed over the batch, and so across the chips, in float32 (the
  layers produce them with ``ops.layers``' ``f32_param_grads``) and land
  back in the at-rest sharding (a reduce-scatter).
- :func:`shard_activations` — activations and logits stay on the batch.

At rest nothing changes: parameters and optimizer state keep
``rules.spec(logical_axes)``, a 1/fsdp share a chip.  ``tp``/``ep``/``sp``
axes keep their annotations and the partitioner still inserts THOSE
collectives.  :func:`collective_profile` reads from a compiled step which
collectives it holds, inside and outside the layer loop: the mechanism is
decided at compile time, so that is its counter.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxis = Union[None, str, Tuple[str, ...]]


class ShardingRules:
    """Maps logical axis names to mesh axes (or None = replicate).

    Example::

        rules = ShardingRules(
            batch=("dp", "fsdp"), seq="sp",
            embed="fsdp", mlp="tp", heads="tp", vocab="tp",
        )
        sharding = rules.spec(("embed", "mlp"))   # P("fsdp", "tp")
    """

    def __init__(self, **rules: MeshAxis):
        self.rules: Dict[str, MeshAxis] = dict(rules)

    def update(self, **rules: MeshAxis) -> "ShardingRules":
        new = dict(self.rules)
        new.update(rules)
        return ShardingRules(**new)

    def spec(self, logical_axes: Sequence[Optional[str]]) -> P:
        return P(*(self.rules.get(a) if a is not None else None for a in logical_axes))

    def sharding(self, mesh: Mesh, logical_axes: Sequence[Optional[str]]) -> NamedSharding:
        return NamedSharding(mesh, self.spec(logical_axes))


# Default rule tables for the canonical modes.  ``None`` replicates.
DP_RULES = ShardingRules(batch="dp", seq=None, embed=None, mlp=None, heads=None,
                         kv=None, vocab=None, expert=None)
FSDP_RULES = ShardingRules(batch=("dp", "fsdp"), seq=None, embed="fsdp", mlp=None,
                           heads=None, kv=None, vocab=None, expert=None)
TP_RULES = ShardingRules(batch="dp", seq=None, embed=None, mlp="tp", heads="tp",
                         kv="tp", vocab="tp", expert=None)
FSDP_TP_RULES = ShardingRules(batch=("dp", "fsdp"), seq=None, embed="fsdp",
                              mlp="tp", heads="tp", kv="tp", vocab="tp", expert=None)
# Long-context: sequence axis sharded over sp (ring attention), params fsdp+tp.
SP_RULES = ShardingRules(batch=("dp", "fsdp"), seq="sp", embed="fsdp", mlp="tp",
                         heads="tp", kv="tp", vocab="tp", expert=None)
# MoE: experts sharded over ep.
EP_RULES = ShardingRules(batch=("dp", "fsdp"), seq=None, embed="fsdp", mlp="tp",
                         heads="tp", kv="tp", vocab="tp", expert="ep")


def rules_for_mesh(mesh: Mesh) -> ShardingRules:
    """Pick a sensible default rule table from the mesh's axes."""
    axes = set(mesh.axis_names)
    batch = tuple(a for a in ("dp", "fsdp") if a in axes) or None
    if batch is not None and len(batch) == 1:
        batch = batch[0]
    return ShardingRules(
        batch=batch,
        seq="sp" if "sp" in axes else None,
        embed="fsdp" if "fsdp" in axes else None,
        mlp="tp" if "tp" in axes else None,
        heads="tp" if "tp" in axes else None,
        kv="tp" if "tp" in axes else None,
        vocab="tp" if "tp" in axes else None,
        expert="ep" if "ep" in axes else None,
        # the stacked layer axis becomes the pipeline-stage axis
        layers="pp" if "pp" in axes else None,
    )


def logical_to_sharding(
    logical_tree: Any, mesh: Mesh, rules: ShardingRules
) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda axes: rules.sharding(mesh, axes),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def infer_sharding(params: Any, mesh: Mesh, rules: ShardingRules) -> Any:
    """Heuristic sharding for an unannotated param pytree.

    FSDP-style: shard the largest divisible axis of each array over the
    param axes (``fsdp`` then ``tp`` if present), replicate small arrays.
    Good enough when a model doesn't carry logical axis metadata.
    """
    axes = [a for a in ("fsdp", "tp") if a in mesh.axis_names]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _spec(x) -> NamedSharding:
        if not hasattr(x, "shape") or not axes or x.ndim == 0 or x.size < 1024:
            return NamedSharding(mesh, P())
        ax = axes[0]
        n = sizes[ax]
        # shard the largest dim divisible by the axis size
        order = sorted(range(x.ndim), key=lambda i: -x.shape[i])
        for i in order:
            if x.shape[i] % n == 0:
                parts: list = [None] * x.ndim
                parts[i] = ax
                return NamedSharding(mesh, P(*parts))
        return NamedSharding(mesh, P())

    return jax.tree.map(_spec, params)


def with_sharding_constraint(x: Any, mesh: Mesh, spec: P) -> Any:
    """``lax.with_sharding_constraint`` under an explicit mesh."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# FSDP: what GSPMD is told, not asked
# ---------------------------------------------------------------------------

FSDP_AXIS = "fsdp"


def fsdp_engaged(mesh: Optional[Mesh], x: Any = None) -> bool:
    """Whether the two helpers below do anything: the mesh has an ``fsdp``
    axis larger than 1 and ``x`` (when given) is not inside a manual region
    (the pipeline engine's ``shard_map`` over ``pp``, where a constraint on
    the whole mesh is rejected: the stage keeps what propagation gives it).
    The helpers test it themselves; a model reads it only to choose how its
    layers accumulate parameter gradients (``ops.layers.dense``)."""
    if mesh is None or mesh.shape.get(FSDP_AXIS, 1) <= 1:
        return False
    return x is None or not getattr(jax.typeof(x), "vma", None)


def _without(entry: MeshAxis, axis: str) -> MeshAxis:
    if isinstance(entry, tuple):
        kept = tuple(a for a in entry if a != axis)
        return kept[0] if len(kept) == 1 else (kept or None)
    return None if entry == axis else entry


def gather_for_compute(
    w: jax.Array, logical_axes: Sequence[Optional[str]], mesh: Optional[Mesh],
    rules: Optional[ShardingRules], dtype: Any,
) -> jax.Array:
    """``w`` whole along ``fsdp`` for the computation that uses it, moved
    between chips in ``dtype``.

    ``w`` is a parameter, or the scanned per-layer slice of one, that rests
    sharded by ``rules.spec(logical_axes)`` (``rules``: the table the caller
    placed the parameters with; None means :func:`rules_for_mesh`).
    Forward: cast to ``dtype`` on the shard, THEN all-gather along ``fsdp``
    (bf16 crosses the ICI when the compute dtype is bf16); any ``tp``/``ep``
    axis stays.  The result holds those ``dtype`` values in ``w.dtype``
    again (exact, and the compiler drops the pair of casts around the
    layer's own cast at use), so that its cotangent is float32 like the
    master: backward, that cotangent is constrained to the at-rest sharding
    and the cross-chip gradient sum is a float32 reduce-scatter, never an
    all-reduce of whole weights and never a sum of bf16 partials.  (The
    partitioner sums across chips at the operation that PRODUCED the
    cotangent, in that operation's output dtype: the layers produce it in
    float32, ``ops.layers.dense(..., f32_param_grads=True)`` and the
    norms.)  Called inside a remat'd scan body the gather is per layer and
    is recomputed, never saved.  Without an ``fsdp`` axis larger than 1 (or
    inside a manual region) ``w`` comes back as it is.
    """
    if not fsdp_engaged(mesh, w):
        return w
    rules = rules or rules_for_mesh(mesh)
    at_rest = rules.sharding(mesh, logical_axes)
    whole = NamedSharding(
        mesh, P(*(_without(entry, FSDP_AXIS) for entry in at_rest.spec)))
    constrain = jax.lax.with_sharding_constraint

    def cast_then_gather(w):
        moved = constrain(constrain(w.astype(dtype), at_rest), whole)
        return moved.astype(w.dtype)

    gather = jax.custom_vjp(cast_then_gather)
    gather.defvjp(lambda w: (cast_then_gather(w), None),
                  lambda _, ct: (constrain(ct, at_rest),))
    return gather(w)


def shard_activations(
    x: jax.Array, mesh: Optional[Mesh], rules: Optional[ShardingRules],
    *trailing: Optional[str],
) -> jax.Array:
    """Constrain ``[B, T, ...]`` to the batch (and ``seq``) sharding, the
    other dimensions by their ``trailing`` logical axes (default: whole).
    The counterpart of :func:`gather_for_compute`: activations stay where
    their sequences are, weights come to them."""
    if not fsdp_engaged(mesh, x):
        return x
    rules = rules or rules_for_mesh(mesh)
    trailing = trailing or (None,) * (x.ndim - 2)
    return jax.lax.with_sharding_constraint(
        x, rules.sharding(mesh, ("batch", "seq") + tuple(trailing)))


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_HLO_CALLEES = re.compile(
    r"\b(calls|to_apply|body|condition|branch_computations)="
    r"(?:\{([^}]*)\}|(%?[\w.\-]+))")
_HLO_COLLECTIVE = re.compile(
    r"^(.*?)\s(%s)(-start)?\((.*?)\)(?:,|$)(.*)$" % "|".join(_COLLECTIVES))
# the TPU compiler's own asynchronous form: a pair of fusions by these names,
# the collective cloned into the computations they call
_HLO_ASYNC_FUSION = re.compile(r"^async-collective-(?:start|done)((?:\.\d+)?)$")
_HLO_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")


def _hlo_instructions(compiled: Any):
    """A compiled program's HLO text by computation: ``(comps, loops, rows)``.
    ``comps``: computation -> {instruction or parameter: the text of its
    shape}; ``loops``: computation -> the ``while`` bodies it runs under
    (itself, if it is one), empty outside every loop; ``rows``: every
    ``(computation, instruction, the text after "=")``."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    comps: Dict[str, Dict[str, str]] = {}
    calls: Dict[str, set] = {}
    loop_bodies: set = set()
    rows = []
    name = None
    for line in text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = dict(
                re.findall(r"([\w.\-]+):\s*(\(.*?\)|[^,()]+\[[\d,]*\])", m.group(2)))
            calls[name] = set()
            continue
        m = name and _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        instr, rest = m.groups()
        for how, several, one in _HLO_CALLEES.findall(rest):
            callees = {c.strip().lstrip("%") for c in (several or one).split(",")}
            calls[name] |= callees
            if how == "body":
                loop_bodies |= callees
        c = _HLO_COLLECTIVE.match(rest)
        comps[name][instr] = c.group(1) if c else rest.split(" ", 1)[0]
        rows.append((name, instr, rest))
    loops: Dict[str, set] = {n: set() for n in comps}
    for body in loop_bodies:
        stack = [body]
        while stack:
            n = stack.pop()
            if body not in loops.setdefault(n, set()):
                loops[n].add(body)
                stack.extend(calls.get(n, ()))
    return comps, loops, rows


def kernel_profile(compiled: Any) -> Dict[str, Dict[str, Any]]:
    """The Pallas kernels of a compiled program by the name their
    ``pallas_call`` was given (``flash_attention_fwd``, ...): ``{"count",
    "loops"}``, ``loops`` the ``while`` bodies a call of that name sits in
    (the scanned layers of the forward pass are one loop and those of the
    backward pass another; empty: outside every loop).  Which attention path
    a step runs is decided where it is lowered, so like
    :func:`collective_profile` this is the counter that says it engaged."""
    _, loops, rows = _hlo_instructions(compiled)
    profile: Dict[str, Dict[str, Any]] = {}
    for comp, instr, rest in rows:
        if 'custom_call_target="tpu_custom_call"' in rest:
            entry = profile.setdefault(
                re.sub(r"[.\d]*$", "", instr), {"count": 0, "loops": set()})
            entry["count"] += 1
            entry["loops"] |= loops[comp]
    return profile


_HLO_OPERATION = re.compile(r"^(\(.*?\)|\S+)\s+([\w\-]+)\((.*)$")


def operation_profile(compiled: Any, kinds) -> list:
    """The instructions of a compiled program whose opcode is one of
    ``kinds`` (``"dot"``, ``"broadcast"``, ``"concatenate"``, ...), those
    inside fusions among them: a dict each of ``kind``, ``shape`` (the result,
    as ``"f32[2,72,16]"``), ``operands`` (the text in the call's brackets
    and the attributes after them), ``op_name`` (the metadata's: the
    ``jax.named_scope`` path the operation was traced under) and
    ``runtime_loop``: whether it runs under a ``while`` whose trip count the
    compiler does NOT know (a ``lax.fori_loop`` / ``while_loop`` with a traced
    bound; a scanned loop's count is known).  What a program does for every
    position of a static bound, and what only for those a runtime value says
    are live, is decided where it is lowered: this is what shows it."""
    _, loops, rows = _hlo_instructions(compiled)
    found = [(comp, _HLO_OPERATION.match(rest)) for comp, _, rest in rows]
    counted = {c.strip().lstrip("%")
               for _, m in found if m and m.group(2) == "while"
               and "known_trip_count" in m.group(3)
               for c in re.findall(r"\bbody=(%?[\w.\-]+)", m.group(3))}
    profile = []
    for comp, m in found:
        if m and m.group(2) in kinds:
            name = re.search(r'op_name="([^"]*)"', m.group(3))
            profile.append({
                "kind": m.group(2),
                "shape": re.sub(r"\{[^}]*\}", "", m.group(1)),
                "operands": re.sub(r",? metadata=\{.*", "", m.group(3)),
                "op_name": name.group(1) if name else "",
                "runtime_loop": bool(loops[comp] - counted)})
    return profile


def collective_profile(compiled: Any) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """What a compiled step moves between chips, read from its HLO.

    ``compiled`` is a ``jax.stages.Compiled`` (``jit(f).lower(...).compile()``)
    or its ``as_text()``.  Returns, per collective kind (``all-reduce``,
    ``all-gather``, ``reduce-scatter``, ``all-to-all``,
    ``collective-permute``) and per place (``"in_loop"``: in a ``while``
    body or anything it calls, i.e. the scanned layers; ``"outside"``),
    ``{"count", "max_operand_bytes", "shapes", "synchronous",
    "start_to_done"}``.  ``shapes`` lists every distinct per-device array
    the collectives of that kind take or give, as ``"f32[4,256,768]"``.
    ``synchronous`` counts those that stand in a computation's schedule as
    ONE instruction (``reduce-scatter(...)``, whatever the instruction is
    called: ``jax.lax.psum_scatter`` names it ``%reduce_scatter.28``): nothing
    else runs on the chip meanwhile.  ``start_to_done`` lists, for each that is
    a start/done pair (``collective-permute-start`` / ``-done``, or the TPU
    compiler's ``async-collective-start`` / ``-done`` fusions), how many
    instructions are scheduled between the two (the text of a compiled
    program is its schedule); the rest of ``count`` are fused into the
    operation that produces their operand (``all-reduce-scatter``).  The
    FSDP mechanism above and the experts' exchange
    (:func:`ray_tpu.ops.moe.experts_ffn_train`) are decided at compile time,
    so this is the counter that says they engaged, and how.
    """
    comps, loops, rows = _hlo_instructions(compiled)
    in_loop = {n for n, bodies in loops.items() if bodies}
    position: Dict[str, Dict[str, int]] = {}  # computation -> instruction -> line
    fused_into = {}  # a fusion's computation -> (where the fusion stands, its name)
    done_of = {}     # (computation, a start) -> the instruction that waits for it
    for comp, instr, rest in rows:
        at = position.setdefault(comp, {})
        at[instr] = len(at)
        op = _HLO_OPERATION.match(rest)
        if op and op.group(2) == "fusion":
            for callee in re.findall(r"\bcalls=%?([\w.\-]+)", rest):
                fused_into[callee] = (comp, instr)
        elif op and op.group(2).endswith("-done"):
            for operand in re.findall(r"%([\w.\-]+)", op.group(3).split(")")[0]):
                done_of[comp, operand] = instr

    def between(comp, start, done):
        at = position[comp]
        return at[done] - at[start] - 1 if start in at and done in at else None

    profile = {kind: {place: {"count": 0, "max_operand_bytes": 0, "shapes": [],
                              "synchronous": 0, "start_to_done": []}
                      for place in ("in_loop", "outside")}
               for kind in _COLLECTIVES}
    seen = set()
    for comp, instr, rest in rows:
        c = _HLO_COLLECTIVE.match(rest)
        if not c:
            continue
        result, kind, start, operands, attrs = c.groups()
        operands, attrs = re.findall(r"%([\w.\-]+)", operands), instr + attrs
        # the TPU compiler writes a reduce-scatter as a fusion of an
        # all-reduce and a slice, and clones an asynchronous collective
        # into each fusion that continues it (same channel; the collectives
        # a ``shard_map`` body writes by hand all share channel 1, and stand
        # in no fusion)
        if kind == "all-reduce" and comp.startswith("all-reduce-scatter"):
            kind = "reduce-scatter"
        channel = re.search(r"channel_id=(\d+)", attrs)
        key = (kind, channel.group(1) if channel and comp in fused_into
               else (comp, attrs))
        if key in seen:
            continue
        seen.add(key)
        # None: one instruction; False: inside another operation's fusion;
        # else the instructions between its start and its done
        pair = None
        if start:
            pair = between(comp, instr, done_of.get((comp, instr)))
        elif comp in fused_into:
            caller, fusion = fused_into[comp]
            named = _HLO_ASYNC_FUSION.match(fusion)
            pair = between(caller, "async-collective-start" + named.group(1),
                           "async-collective-done" + named.group(1)) if named else False
        entry = profile[kind]["in_loop" if comp in in_loop else "outside"]
        entry["count"] += 1
        if pair is None:
            entry["synchronous"] += 1
        elif pair is not False:
            entry["start_to_done"].append(pair)
        taken = [s for o in operands
                 for s in _HLO_SHAPE.findall(comps[comp].get(o, ""))]
        for dt, dims in taken + _HLO_SHAPE.findall(result):
            label = f"{dt}[{dims}]"
            if label not in entry["shapes"]:
                entry["shapes"].append(label)
        for dt, dims in taken or _HLO_SHAPE.findall(result):
            size = _DTYPE_BYTES.get(dt, 4)
            for d in filter(None, dims.split(",")):
                size *= int(d)
            entry["max_operand_bytes"] = max(entry["max_operand_bytes"], size)
    return profile
