"""Named-axis sharding for the multi-host serving plane.

The idiom, from haliax, adapted to the repo's plain-pytree models:
model code names *logical* axes ("embed", "vocab", "experts"); a
thread-local :class:`AxisMapping` resolves them to *physical* mesh axes
at placement time, so the same model runs replicated, tensor-sharded,
or expert-sharded by swapping one context, never editing model code.

The meshes themselves come from :func:`replica_meshes`, which
partitions the process's devices into disjoint per-replica groups; a
replica never shares a device with another.
"""
from __future__ import annotations

import dataclasses
import threading
from contextlib import contextmanager
from typing import List, Mapping, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["AxisMapping", "axis_mapping", "current_axis_mapping",
           "replica_meshes", "shard_lm_params"]

# logical axis names the LM param tree exposes, by leaf dimension:
# embed/lm_head are (vocab, d_model); per-unit stacks lead with "unit"
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class AxisMapping:
    """Logical-axis -> physical-mesh-axis resolution table.

    ``mapping["vocab"] == "model"`` means "partition logical axis
    *vocab* over mesh axis *model*"; a logical axis absent from the
    table (or mapped to None) is replicated.  Immutable so it can be
    stacked on the thread-local context without aliasing surprises.
    """

    mapping: Mapping[str, Optional[str]] = dataclasses.field(
        default_factory=dict)

    def __post_init__(self):
        object.__setattr__(self, "mapping", dict(self.mapping))

    def physical(self, logical: str) -> Optional[str]:
        return self.mapping.get(logical)

    def spec(self, *logical: Optional[str]) -> PartitionSpec:
        """PartitionSpec for a leaf whose dims carry these logical
        names (None = unnamed dim, always replicated)."""
        return PartitionSpec(*(self.physical(ax) if ax else None
                               for ax in logical))

    def merged(self, other: "AxisMapping") -> "AxisMapping":
        out = dict(self.mapping)
        out.update(other.mapping)
        return AxisMapping(out)


# replicate-everything default: correctness-first, matches the paper's
# observation that capacity (tiering) not FLOPs is the serving binder
_DEFAULT = AxisMapping({})
_tls = threading.local()


def current_axis_mapping() -> AxisMapping:
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else _DEFAULT


@contextmanager
def axis_mapping(mapping: "AxisMapping | Mapping[str, Optional[str]]"):
    """Install an axis mapping for the dynamic extent, haliax-style.

    Nested contexts merge (inner wins per logical axis), so a replica
    can overlay ``{"experts": "model"}`` on a plane-wide base.
    """
    if not isinstance(mapping, AxisMapping):
        mapping = AxisMapping(mapping)
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    merged = (stack[-1].merged(mapping) if stack else
              _DEFAULT.merged(mapping))
    stack.append(merged)
    try:
        yield merged
    finally:
        stack.pop()


def replica_meshes(n_replicas: int,
                   axis_name: str = MODEL_AXIS,
                   devices: Optional[List] = None) -> List[Mesh]:
    """Partition the process's devices into ``n_replicas`` disjoint
    1-D meshes.

    With ``d`` devices and ``n`` replicas each mesh gets ``d // n``
    devices (remainder unused, keeping replicas symmetric).  Fewer
    devices than replicas, or a device listed twice, raises: replicas
    sharing a chip would contend for its HBM and hide it.
    """
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    devs = list(devices if devices is not None else jax.devices())
    if len(set(devs)) != len(devs):
        raise ValueError(f"a device is listed twice in {devs}: two "
                         f"replicas would share it")
    if len(devs) < n_replicas:
        raise ValueError(f"{n_replicas} replicas need {n_replicas} "
                         f"devices, found {len(devs)}")
    per = len(devs) // n_replicas
    return [Mesh(np.array(devs[r * per:(r + 1) * per]), (axis_name,))
            for r in range(n_replicas)]


def _leaf_logical_axes(path: Tuple[str, ...], ndim: int) -> List[Optional[str]]:
    """Logical axis names for an LM param leaf, by its tree path.

    Only axes we ever shard get names; everything else is None
    (replicated).  ``embed``/``lm_head`` are (vocab, d_model) and
    vocab is the one big, cleanly-partitionable dimension of the
    decode path; MoE expert stacks lead with an ``experts`` dim.
    """
    axes: List[Optional[str]] = [None] * ndim
    if path and path[-1] in ("embed", "lm_head") and ndim >= 1:
        axes[0] = "vocab"
    if "moe" in path and ndim >= 2:
        # unit-stacked MoE leaves are (n_units, n_experts, ...)
        axes[1 if "units" in path else 0] = "experts"
    return axes


def _iter_with_path(tree, path=()):
    if isinstance(tree, Mapping):
        for k in tree:
            yield from _iter_with_path(tree[k], path + (k,))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _iter_with_path(v, path + (str(i),))
    else:
        yield path, tree


def shard_lm_params(params, mesh: Mesh,
                    mapping: Optional[AxisMapping] = None):
    """Place an LM param pytree on ``mesh`` under the axis mapping.

    Each leaf gets a :class:`NamedSharding`: dims whose logical axis
    the mapping routes to a mesh axis are partitioned *when evenly
    divisible* (otherwise silently replicated — a 50k vocab on a
    3-device mesh should not crash serving), all other dims
    replicated.  With the default empty mapping this is pure
    replication: every leaf committed to the mesh's device set, which
    is exactly what makes replica params and pool blocks jit-compatible.
    """
    mapping = mapping or current_axis_mapping()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def place(path, leaf):
        if not hasattr(leaf, "ndim"):
            return leaf
        spec_axes: List[Optional[str]] = []
        for dim, logical in zip(
                leaf.shape, _leaf_logical_axes(path, leaf.ndim)):
            phys = mapping.physical(logical) if logical else None
            ok = phys in sizes and dim % sizes[phys] == 0
            spec_axes.append(phys if ok else None)
        sh = NamedSharding(mesh, PartitionSpec(*spec_axes))
        return jax.device_put(leaf, sh)

    flat = {path: place(path, leaf)
            for path, leaf in _iter_with_path(params)}

    def rebuild(tree, path=()):
        if isinstance(tree, Mapping):
            return {k: rebuild(tree[k], path + (k,)) for k in tree}
        if isinstance(tree, tuple):
            return tuple(rebuild(v, path + (str(i),))
                         for i, v in enumerate(tree))
        if isinstance(tree, list):
            return [rebuild(v, path + (str(i),))
                    for i, v in enumerate(tree)]
        return flat[path]

    return rebuild(params)
