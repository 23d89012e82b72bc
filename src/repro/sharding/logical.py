"""Logical-axis sharding: every parameter / activation is labeled with logical
axis names; a rules table maps logical names onto physical mesh axes.

This is the mechanism that gives the polystore *location independence*
(DESIGN.md §2): model code never names a mesh axis, only logical roles.
The catalog's engine assignment for an object resolves to a rules table.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Canonical logical axis names used throughout the model zoo.
# ---------------------------------------------------------------------------
BATCH = "batch"            # global batch             -> (pod, data)
SEQ = "seq"                # sequence (activations)   -> None (or sp)
RESID = "resid_seq"        # block-boundary residual  -> model under SP
KV_SEQ = "kv_seq"          # KV-cache sequence        -> model iff heads don't divide
EMBED = "embed"            # d_model (PARAMS)         -> data (FSDP)
ACT_EMBED = "act_embed"    # d_model (ACTIVATIONS)    -> None (gathered)
HEADS = "heads"            # q heads                  -> model (TP)
KV_HEADS = "kv_heads"      # kv heads                 -> model iff divisible
HEAD_DIM = "head_dim"      # per-head dim             -> None
MLP = "mlp"                # ffn hidden               -> model (TP)
VOCAB = "vocab"            # vocab rows               -> model (TP)
EXPERT = "expert"          # MoE experts              -> model (EP)
CAPACITY = "capacity"      # MoE per-expert capacity  -> None
LAYER = "layer"            # stacked scan axis        -> None (never sharded)
STATE = "state"            # SSM state dim            -> None
CONV = "conv"              # conv kernel width        -> None


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Mapping logical axis -> mesh axis (or tuple of mesh axes, or None).

    Carries the mesh so ``constrain`` can build NamedShardings directly —
    bare-PartitionSpec with_sharding_constraint requires an ambient mesh
    context and otherwise raises; silently losing activation constraints
    was §Perf finding A1/A4 (SPMD propagation alone replicates S² scores).
    """

    rules: Tuple[Tuple[str, Union[None, str, Tuple[str, ...]]], ...]
    mesh: Optional[Mesh] = dataclasses.field(default=None, compare=False)

    def mesh_axes(self, logical: Optional[str]):
        if logical is None:
            return None
        for name, target in self.rules:
            if name == logical:
                return target
        return None

    def spec(self, logical_axes: Sequence[Optional[str]]) -> P:
        return P(*(self.mesh_axes(ax) for ax in logical_axes))

    def replace(self, **updates) -> "AxisRules":
        new = dict(self.rules)
        new.update(updates)
        return AxisRules(tuple(new.items()), mesh=self.mesh)


def default_rules(mesh: Mesh, *, shard_kv_seq: bool = False,
                  seq_parallel: bool = False) -> AxisRules:
    """Production rules for the (pod?, data, model) mesh.

    ``batch``/``embed`` ride the (pod,)data axes (DP + FSDP); head/mlp/vocab/
    expert dims ride model (TP/EP).  When an arch's kv_heads don't divide the
    model axis, the KV cache is sequence-sharded instead (``shard_kv_seq``);
    XLA SPMD inserts the softmax all-reduces.  ``seq_parallel`` shards the
    block-boundary residual stream over model (Megatron-SP expressed purely
    as a sharding constraint: XLA all-gathers at block entry and
    reduce-scatters at exit), dividing saved-activation memory by the TP
    degree (DESIGN.md §5).
    """
    axes = mesh.axis_names
    batch_axes: Union[str, Tuple[str, ...]]
    if "pod" in axes:
        batch_axes = ("pod", "data")
    else:
        batch_axes = "data"
    return AxisRules(
        (
            (BATCH, batch_axes),
            (SEQ, None),
            (RESID, "model" if seq_parallel else None),
            (ACT_EMBED, None),
        ) + _default_tail(shard_kv_seq), mesh=mesh)


def _default_tail(shard_kv_seq: bool):
    return (
            (KV_SEQ, "model" if shard_kv_seq else None),
            (EMBED, "data"),
            (HEADS, "model"),
            (KV_HEADS, "model" if not shard_kv_seq else None),
            (HEAD_DIM, None),
            (MLP, "model"),
            (VOCAB, "model"),
            (EXPERT, "model"),
            (CAPACITY, "data"),       # dispatch slots ride the FSDP axis
            (LAYER, None),
            (STATE, None),
            (CONV, None),
    )


def single_device_rules() -> AxisRules:
    """Rules that map everything to None — CPU smoke tests."""
    return AxisRules(tuple((name, None) for name in (
        BATCH, SEQ, RESID, KV_SEQ, EMBED, ACT_EMBED, HEADS, KV_HEADS,
        HEAD_DIM, MLP, VOCAB, EXPERT, CAPACITY, LAYER, STATE, CONV)))


# ---------------------------------------------------------------------------
# ParamSpec: declarative parameter description (shape, dtype, logical axes,
# initializer).  Model code builds pytrees of these; the launcher turns them
# into either real arrays (init) or ShapeDtypeStructs (dry-run).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.float32
    init: str = "normal"      # normal | zeros | ones | embed_normal |
    #                           ssm_a_log | ssm_dt_bias
    init_scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    def struct(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)

    def num_params(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


def spec_tree_structs(spec_tree) -> Any:
    return jax.tree.map(
        lambda s: s.struct(), spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def spec_tree_axes(spec_tree) -> Any:
    return jax.tree.map(
        lambda s: s.axes, spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def sharding_for(spec: ParamSpec, mesh: Mesh, rules: AxisRules
                 ) -> NamedSharding:
    """NamedSharding for a spec, dropping axes that don't divide evenly
    (e.g. 12 q-heads on a 16-wide model axis fall back to replicated;
    recorded as a hillclimb opportunity in EXPERIMENTS.md §Perf)."""
    parts = []
    for dim, ax in zip(spec.shape, spec.axes):
        target = rules.mesh_axes(ax)
        if target is None:
            parts.append(None)
            continue
        axes = target if isinstance(target, tuple) else (target,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        parts.append(target if dim % size == 0 else None)
    return NamedSharding(mesh, P(*parts))


def spec_tree_shardings(spec_tree, mesh: Mesh, rules: AxisRules):
    return jax.tree.map(
        lambda s: sharding_for(s, mesh, rules), spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def count_params(spec_tree) -> int:
    total = 0
    for leaf in jax.tree.leaves(
            spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec)):
        total += leaf.num_params()
    return total


_HEAD_AXES = (HEADS, KV_HEADS, HEAD_DIM)


def fan_in(spec: ParamSpec) -> int:
    """The input width of a ``"normal"`` weight: the product of its input
    axes.  A projection into heads, ``(d, H, hd)``, reads ``d``; one out
    of heads, ``(H, hd, d)``, reads ``H * hd``; any other weight reads
    its second-to-last axis (the leading axis of a 2-D weight, ``d`` of
    an expert stack ``(E, d, f)``).  A stacked layer axis is not an
    input."""
    dims = [(n, ax) for n, ax in zip(spec.shape, spec.axes) if ax != LAYER]
    if len(dims) < 2:
        return dims[-1][0] if dims else 1
    heads = [i for i, (_, ax) in enumerate(dims) if ax in _HEAD_AXES]
    if heads and heads[-1] == len(dims) - 1:          # into heads
        return dims[heads[0] - 1][0]
    if heads and heads[0] == 0:                       # out of heads
        out = 1
        for i in heads:
            out *= dims[i][0]
        return out
    return dims[-2][0]


def _init_one(key: jax.Array, spec: ParamSpec) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "normal":
        scale = spec.init_scale / max(1.0, float(fan_in(spec))) ** 0.5
        return (scale * jax.random.normal(key, spec.shape)).astype(spec.dtype)
    if spec.init == "embed_normal":
        return (spec.init_scale * 0.02
                * jax.random.normal(key, spec.shape)).astype(spec.dtype)
    # Mamba's selective-scan parameters (arXiv:2312.00752 §3.6): A_n =
    # -(n + 1) along the state axis (S4D-real), and a dt bias that puts
    # softplus(bias) log-uniformly in [1e-3, 1e-1]
    if spec.init == "ssm_a_log":
        n = spec.shape[-1]
        return jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1,
                                                   dtype=jnp.float32)),
                                spec.shape).astype(spec.dtype)
    if spec.init == "ssm_dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(key, spec.shape))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(spec.dtype)
    raise ValueError(f"unknown init {spec.init}")


def init_params(key: jax.Array, spec_tree):
    """Materialize a ParamSpec tree into arrays (CPU smoke / real training)."""
    leaves, treedef = jax.tree.flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    arrays = [_init_one(k, s) for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, arrays)


def constrain(x: jax.Array, rules: Optional[AxisRules],
              logical_axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op when rules is None.

    Axes whose dimension does not divide the mapped mesh-axis size fall
    back to replicated (same policy as ``sharding_for``)."""
    if rules is None:
        return x
    if rules.mesh is not None:
        parts = []
        for dim, ax in zip(x.shape, logical_axes):
            target = rules.mesh_axes(ax)
            if target is None:
                parts.append(None)
                continue
            axes = target if isinstance(target, tuple) else (target,)
            size = 1
            for a in axes:
                size *= rules.mesh.shape[a]
            parts.append(target if dim % size == 0 else None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(rules.mesh, P(*parts)))
    spec = rules.spec(logical_axes)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        # Outside a mesh context (CPU smoke tests) constraints are a no-op.
        return x
