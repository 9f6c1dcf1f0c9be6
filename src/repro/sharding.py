"""Logical-axis -> PartitionSpec rules for single-pod and multi-pod meshes.

The framework names tensor dimensions with *logical* axes ("batch", "d_ff",
"heads", ...).  A :class:`ShardingRules` instance maps logical axes onto the
physical mesh axes ("pod", "data", "model") and degrades gracefully: a
logical dimension whose size does not divide the assigned mesh axes is left
replicated (PartitionSpec entry ``None``) instead of failing at lower time.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Logical axis names used throughout the model code.
BATCH = "batch"
SEQ = "seq"
D_MODEL = "d_model"
D_FF = "d_ff"
HEADS = "heads"
KV_HEADS = "kv_heads"
KV_SEQ = "kv_seq"
HEAD_DIM = "head_dim"
VOCAB = "vocab"
EXPERTS = "experts"
CLIENTS = "clients"
STACK = "stack"  # leading scan-over-layers axis; never sharded
SCALAR = "scalar"  # logical marker for 0-dim tensors (P()); a plain () would
                   # be ambiguous with an empty pytree container


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Maps logical axis names to (tuples of) physical mesh axis names."""

    mesh: Mesh
    rules: Mapping[str, Any]

    def __hash__(self):
        # The frozen-dataclass default would hash the rules dict and fail;
        # an explicit hash lets a ShardingRules ride through jit as a static
        # argument (the client-stacked hot paths specialise on it).
        def _t(v):
            return tuple(v) if isinstance(v, (list, tuple)) else v
        return hash((self.mesh,
                     tuple(sorted((k, _t(v)) for k, v in self.rules.items()))))

    @classmethod
    def default(cls, mesh: Mesh) -> "ShardingRules":
        """The framework's standard layout.

        * batch / clients -> the full data-parallel product (pod, data)
        * model-parallel dims (d_ff, heads, vocab, experts) -> "model"
        * d_model -> FSDP over (pod, data): 2D-sharded params
        * kv_seq -> "model" (used when kv_heads is not divisible; the KV
          cache is then sequence-sharded instead of head-sharded)
        """
        has_pod = "pod" in mesh.shape
        dp = ("pod", "data") if has_pod else ("data",)
        return cls(
            mesh=mesh,
            rules={
                BATCH: dp,
                CLIENTS: dp,
                SEQ: None,
                D_MODEL: dp,  # FSDP axis for parameters
                D_FF: "model",
                HEADS: "model",
                KV_HEADS: "model",
                KV_SEQ: "model",
                HEAD_DIM: None,
                VOCAB: "model",
                EXPERTS: "model",
                STACK: None,
            },
        )

    def spec(self, logical: Sequence[str | None],
             dims: Sequence[int] | None = None) -> P:
        """PartitionSpec for a tensor whose dims carry the given logical axes.

        If ``dims`` (the concrete dimension sizes) is provided, any logical
        axis whose size does not divide its mesh-axis product is replicated.
        A mesh axis already consumed by an earlier dim is not reused (the
        later dim is replicated) — this gives e.g. MoE weights an automatic
        fallback from expert-parallel to within-expert tensor-parallel when
        the expert count does not divide the "model" axis.
        """
        if isinstance(logical, str):  # SCALAR marker
            return P()
        entries = []
        used: set[str] = set()
        for i, name in enumerate(logical):
            if name is None:
                entries.append(None)
                continue
            ax = self.rules.get(name)
            if ax is None:
                entries.append(None)
                continue
            ax_t = (ax,) if isinstance(ax, str) else tuple(ax)
            if any(a in used for a in ax_t):
                entries.append(None)
                continue
            if dims is not None:
                size = dims[i]
                if size % _axis_size(self.mesh, ax) != 0:
                    entries.append(None)
                    continue
            used.update(ax_t)
            entries.append(ax)
        return P(*entries)

    def named(self, logical: Sequence[str | None],
              dims: Sequence[int] | None = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical, dims))

    def data_axes(self) -> tuple[str, ...]:
        ax = self.rules[BATCH]
        return (ax,) if isinstance(ax, str) else tuple(ax)


def logical_to_sharding(tree_logical, tree_shapes, rules: ShardingRules):
    """Map a pytree of logical-axis tuples (+ matching ShapeDtypeStructs)
    to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda logical, sds: rules.named(logical, sds.shape),
        tree_logical,
        tree_shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def constrain(x, rules: ShardingRules, logical: Sequence[str | None]):
    """with_sharding_constraint by logical axes.

    A placement the mesh cannot honour raises: a silently unconstrained
    array would leave the client stack on one device."""
    return jax.lax.with_sharding_constraint(x, rules.named(logical, x.shape))


# ---------------------------------------------------------------------------
# Client-axis helpers: the federation data plane stacks every per-client
# quantity (data, masks, AE params, optimiser moments) with a leading CLIENTS
# axis; these map/constrain whole pytrees of such tensors in one call.
# ---------------------------------------------------------------------------

def client_axes(ndim: int) -> tuple:
    """Logical axes for a tensor whose leading dim is the client stack."""
    if ndim == 0:
        return ()
    return (CLIENTS,) + (None,) * (ndim - 1)


def shard_clients(tree, rules: ShardingRules | None):
    """device_put a pytree of leading-client-axis tensors onto the mesh.

    Every leaf's first dimension is placed per ``rules`` (CLIENTS -> the
    data-parallel mesh product, replicated when N does not divide it);
    remaining dims stay replicated.  ``rules=None`` is the identity, so
    single-device callers pay nothing.
    """
    if rules is None:
        return tree
    return jax.tree.map(
        lambda x: jax.device_put(x, rules.named(client_axes(x.ndim), x.shape)),
        tree)


def constrain_clients(tree, rules: ShardingRules | None):
    """In-jit sharding constraint pinning each leaf's leading client axis."""
    if rules is None:
        return tree
    return jax.tree.map(
        lambda x: constrain(x, rules, client_axes(x.ndim)), tree)


def client_map(fn, rules: ShardingRules | None):
    """``fn`` run shard-locally over the leading client axis of its args.

    Pallas (Mosaic) kernels cannot be partitioned automatically, so a
    client-stacked program that calls one under ``rules`` maps the call
    with ``jax.shard_map``: each device runs ``fn`` on its own clients and
    the outputs keep the leading client axis.  Every argument (a pytree of
    arrays) and every output must lead with the same client axis; a count
    that does not divide the mesh is replicated, as in
    :meth:`ShardingRules.spec`.  ``fn`` must be row-local (no cross-client
    reduction).  ``rules=None`` returns ``fn`` itself."""
    if rules is None:
        return fn

    def mapped(*args):
        in_specs = jax.tree.map(
            lambda a: rules.spec(client_axes(a.ndim), a.shape), args)
        lead = jax.tree.leaves(in_specs, is_leaf=lambda s: isinstance(s, P))
        return jax.shard_map(fn, mesh=rules.mesh, in_specs=in_specs,
                             out_specs=P(lead[0][0]), check_vma=False)(*args)

    return mapped


def client_sum(x, rules: ShardingRules | None):
    """Sum over the leading (possibly sharded) client axis with the result
    constrained replicated — the moment-aggregation collective of the
    stacked federated PCA (``core/pca.py``): per-shard partial sums of the
    first/second-moment sufficient statistics followed by a psum-style
    all-reduce, which is the only cross-client communication the shared
    basis needs.  ``rules=None`` degrades to a plain sum."""
    s = jnp.sum(x, axis=0)
    if rules is None:
        return s
    return constrain(s, rules, (None,) * s.ndim)


def client_mean(x, rules: ShardingRules | None):
    """Mean over the leading (possibly sharded) client/agent axis, with the
    result constrained replicated — on a mesh this is *the* collective of
    the discovery plane (a psum-style all-reduce of per-shard partial sums),
    the only cross-agent communication Algorithm 1 needs.  ``rules=None``
    degrades to a plain mean."""
    m = jnp.mean(x, axis=0)
    if rules is None:
        return m
    return constrain(m, rules, (None,) * m.ndim)
