"""Sharded parameter state: partitioners, layout rules, pytree sharding.

Replaces the reference's distributed-values layer (SURVEY.md §2.1):
``PerReplica`` / ``MirroredVariable`` wrappers become plain ``jax.Array`` s
with a ``NamedSharding``; ``ShardedVariable`` + partitioners
(``sharded_variable.py:47-176``) become :class:`Partitioner` rules producing
``PartitionSpec`` s; the save/restore integration lives in
:mod:`distributedtensorflow_tpu.checkpoint`.

There is no runtime wrapper-object machinery: sharding is metadata attached to
arrays, and the XLA partitioner does variable placement — the design the
reference's experimental DTensor layer and Keras 3 ``keras.distribution``
point toward (SURVEY.md §7).
"""

from __future__ import annotations

import dataclasses
import logging
import math
import re
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import mesh as mesh_lib

logger = logging.getLogger("distributedtensorflow_tpu")

PyTree = Any


# --- Partitioners (reference parity: tf.distribute.experimental.partitioners,
#     sharded_variable.py:47-176). They decide HOW MANY shards a variable
#     gets; here that becomes a PartitionSpec on a named mesh axis.


class Partitioner:
    """Decide the number of shards for a variable of a given shape/dtype.

    Reference semantics: partition along axis 0 only (``sharded_variable``
    splits embedding rows).  ``num_shards`` is then clamped to the mesh axis
    size and to the dimension size by :func:`spec_for`.
    """

    def num_shards(self, shape: Sequence[int], dtype: np.dtype) -> int:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class FixedShardsPartitioner(Partitioner):
    """Always ``num_shards`` (reference ``FixedShardsPartitioner``)."""

    shards: int

    def num_shards(self, shape, dtype) -> int:
        return self.shards


@dataclasses.dataclass(frozen=True)
class MinSizePartitioner(Partitioner):
    """As many shards as possible keeping each shard >= min_shard_bytes.

    Reference ``MinSizePartitioner`` (``sharded_variable.py:115``).
    """

    min_shard_bytes: int = 256 << 10
    max_shards: int = 1 << 30

    def num_shards(self, shape, dtype) -> int:
        total = math.prod(shape) * np.dtype(dtype).itemsize
        return max(1, min(self.max_shards, total // max(1, self.min_shard_bytes)))


@dataclasses.dataclass(frozen=True)
class MaxSizePartitioner(Partitioner):
    """As few shards as possible keeping each shard <= max_shard_bytes.

    Reference ``MaxSizePartitioner`` (``sharded_variable.py:176``).
    """

    max_shard_bytes: int

    def num_shards(self, shape, dtype) -> int:
        total = math.prod(shape) * np.dtype(dtype).itemsize
        return max(1, -(-total // max(1, self.max_shard_bytes)))


def spec_for(
    partitioner: Partitioner,
    shape: Sequence[int],
    dtype: np.dtype,
    mesh: Mesh,
    axis: str = mesh_lib.AXIS_MODEL,
    *,
    dim: int = 0,
) -> P:
    """Turn a partitioner decision into a PartitionSpec on ``axis``.

    A NamedSharding can only split a dim over the *whole* mesh axis, so the
    partitioner's shard count is interpreted against that constraint: the
    variable is sharded ``axis_size``-ways iff the partitioner asks for at
    least that many shards (so per-shard size constraints like
    ``MinSizePartitioner.min_shard_bytes`` still hold) and ``dim`` divides
    evenly; otherwise it is replicated (the reference falls back to one
    shard too).
    """
    n = partitioner.num_shards(shape, np.dtype(dtype))
    axis_size = mesh.shape[axis]
    if n < axis_size or axis_size <= 1 or shape[dim] % axis_size != 0:
        if n >= axis_size > 1 and shape[dim] % axis_size != 0:
            # The partitioner *wanted* this variable sharded but the dim
            # doesn't divide the mesh axis — a large embedding silently
            # replicating would defeat the Wide&Deep sharded-embedding
            # path this exists for, so say it loudly (pad the vocab to a
            # multiple of the axis size to shard it).
            logger.warning(
                "spec_for: %s-byte variable shape=%s wants >=%d shards but "
                "dim %d (size %d) does not divide mesh axis %r (size %d); "
                "REPLICATING instead. Pad the dimension to a multiple of "
                "%d to shard it.",
                math.prod(shape) * np.dtype(dtype).itemsize, tuple(shape),
                n, dim, shape[dim], axis, axis_size, axis_size,
            )
        return P()
    spec = [None] * len(shape)
    spec[dim] = axis
    return P(*spec)


# --- Layout rules: path-regex → PartitionSpec (the Keras-3 LayoutMap /
#     GSPMD-rule pattern, SURVEY.md §2.3 "keras.distribution").


class LayoutMap:
    """Ordered mapping of path regexes to ``PartitionSpec``.

    Paths are '/'-joined pytree key paths (e.g. ``"encoder/layers_0/mlp/kernel"``).
    First matching rule wins (``re.search`` semantics); no match → replicated.
    """

    def __init__(self, rules: Sequence[tuple[str, P]] = ()):
        self._rules: list[tuple[re.Pattern[str], P]] = [
            (re.compile(pat), spec) for pat, spec in rules
        ]

    def add(self, pattern: str, spec: P) -> "LayoutMap":
        self._rules.append((re.compile(pattern), spec))
        return self

    def spec(self, path: str) -> P:
        for pat, spec in self._rules:
            if pat.search(path):
                return spec
        return P()

    def __call__(self, path: str) -> P:
        return self.spec(path)


def path_str(key_path: tuple) -> str:
    """Render a jax.tree_util key path as a '/'-joined string."""
    parts = []
    for k in key_path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def tree_paths(tree: PyTree) -> PyTree:
    """Pytree of '/'-joined path strings, same structure as ``tree``."""
    return jax.tree.map_with_path(lambda kp, _: path_str(kp), tree)


def auto_fsdp_spec(
    shape: Sequence[int],
    mesh: Mesh,
    *,
    axis: str = mesh_lib.AXIS_FSDP,
    min_size_to_shard: int = 2**14,
) -> P:
    """ZeRO-style weight sharding rule (SURVEY.md §7 step 3; PAPERS.md
    "Automatic Cross-Replica Sharding of Weight Update", arxiv 2004.13336).

    Shard the largest dimension divisible by the fsdp axis size; tiny params
    stay replicated (sharding them costs more in collectives than it saves).
    """
    axis_size = mesh.shape.get(axis, 1)
    if axis_size <= 1 or math.prod(shape) < min_size_to_shard:
        return P()
    candidates = [
        (dim_size, i)
        for i, dim_size in enumerate(shape)
        if dim_size % axis_size == 0 and dim_size > 1
    ]
    if not candidates:
        return P()
    _, dim = max(candidates)
    spec = [None] * len(shape)
    spec[dim] = axis
    return P(*spec)


def specs_for_tree(
    tree: PyTree,
    mesh: Mesh,
    rule: LayoutMap | Callable[[str, tuple[int, ...]], P] | None = None,
    *,
    fsdp: bool = False,
) -> PyTree:
    """PartitionSpec pytree for ``tree``.

    ``rule`` may be a LayoutMap (path-only) or a ``(path, shape) -> spec``
    callable.  With ``fsdp=True``, leaves that no rule shards fall back to
    :func:`auto_fsdp_spec`.
    """

    def leaf_spec(key_path, leaf) -> P:
        path = path_str(key_path)
        shape = tuple(getattr(leaf, "shape", ()))
        spec = P()
        if isinstance(rule, LayoutMap):
            spec = rule.spec(path)
        elif callable(rule):
            spec = rule(path, shape)
        if fsdp and spec == P():
            spec = auto_fsdp_spec(shape, mesh)
        return spec

    return jax.tree.map_with_path(leaf_spec, tree)


def named_shardings(mesh: Mesh, specs: PyTree) -> PyTree:
    """Map a PartitionSpec pytree to NamedShardings on ``mesh``."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_tree(tree: PyTree, mesh: Mesh, specs: PyTree) -> PyTree:
    """Place a pytree onto ``mesh`` with the given PartitionSpecs."""
    return jax.device_put(tree, named_shardings(mesh, specs))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_spec(mesh: Mesh, *, extra_dims: int = 0,
               leading_unsharded: int = 0) -> P:
    """PartitionSpec for a batch: leading dim sharded over all batch axes.

    ``leading_unsharded`` prepends that many replicated dims — e.g. the
    step dimension of a ``steps_per_call`` bundle ``(k, B, ...)``.
    """
    axes = mesh_lib.data_axes(mesh)
    return P(*([None] * leading_unsharded),
             axes if axes else None, *([None] * extra_dims))


def shard_batch(batch: PyTree, mesh: Mesh) -> PyTree:
    """Shard every leaf's leading (batch) dimension over the batch axes."""
    sharding = NamedSharding(mesh, batch_spec(mesh))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)


# --- Mosaic kernels under a mesh -------------------------------------------
#
# GSPMD cannot partition a Mosaic (Pallas TPU) custom call: lowering one in
# a multi-device jit raises "Mosaic kernels cannot be automatically
# partitioned".  The kernels in ``ops/`` therefore run per shard, inside a
# shard_map over the mesh the engine traces under (``jax.sharding.set_mesh``
# in train/engine.py and train/state.py).  The helpers below are all a
# kernel wrapper needs: which mesh axes may split a dimension, the spec of
# a per-token operand, and the shard_map itself.


def kernel_axes(candidates: Sequence[str], size: int) -> tuple[str, ...] | None:
    """The mesh axes a kernel operand's dimension of ``size`` is split over.

    Those of ``candidates`` that the context mesh has, larger than 1 and
    not already manual (an enclosing shard_map owns those) — provided
    their product divides ``size``; otherwise None (replicate the
    dimension: every shard computes all of it, which is what a two-row
    ``init`` batch on a four-way data mesh needs).
    """
    mesh = jax.sharding.get_abstract_mesh()
    axes = tuple(
        a for a in candidates
        if mesh.shape.get(a, 1) > 1 and a not in mesh.manual_axes
    )
    if not axes or size % math.prod(mesh.shape[a] for a in axes):
        return None
    return axes


def token_spec(token_shape: Sequence[int], trailing: int = 0) -> P:
    """Spec of a per-token kernel operand ``(*token_shape, *trailing dims)``:
    the first token dimension over the batch axes, the second (the
    sequence, when there is one) over ``seq``, the rest replicated.
    Per-token kernels (LayerNorm, the loss head) are indifferent to how
    their rows are dealt out, so any split that divides is correct."""
    dims: list = []
    if token_shape:
        dims.append(kernel_axes(mesh_lib.BATCH_AXES, token_shape[0]))
    if len(token_shape) >= 2:
        dims.append(kernel_axes((mesh_lib.AXIS_SEQ,), token_shape[1]))
    dims += [None] * (len(token_shape) - len(dims) + trailing)
    return P(*dims)


def shard_kernel(fn: Callable, in_specs, out_specs) -> Callable:
    """``fn`` run per shard over every automatic axis of the context mesh.

    Without a context mesh, on a one-device mesh, or inside a region
    that is already fully manual (ring attention, the pipeline stages),
    ``fn`` is returned as is.  Specs name only axes :func:`kernel_axes`
    returned; operands are replicated over the rest.
    """
    mesh = jax.sharding.get_abstract_mesh()
    auto = frozenset(mesh.axis_names) - frozenset(mesh.manual_axes)
    if not auto or mesh.size == 1:
        return fn
    return jax.shard_map(
        fn, in_specs=in_specs, out_specs=out_specs, axis_names=auto,
        check_vma=False,
    )
