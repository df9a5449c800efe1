"""Device-mesh core: the TPU-native replacement for the tf.distribute strategy zoo.

In the reference stack, parallelism is chosen by picking a *strategy object*
(``OneDeviceStrategy`` / ``MirroredStrategy`` / ``MultiWorkerMirroredStrategy``
/ ``ParameterServerStrategyV2`` — see SURVEY.md §2.1).  On TPU the idiomatic
equivalent is a single SPMD program parameterized by a ``jax.sharding.Mesh``:
each strategy is *just a mesh shape* (SURVEY.md §7 step 1, §2.4 matrix).

Canonical mesh axes (slowest-varying first — outer axes ride DCN between
slices, inner axes ride ICI within a slice, so keep bandwidth-hungry axes
innermost):

=========  ===========================================================
``data``   pure data parallelism (gradient all-reduce; replaces the
           MirroredStrategy / MultiWorkerMirroredStrategy replica axis)
``fsdp``   data parallelism with sharded params/optimizer state
           (ZeRO-style weight-update sharding)
``pipe``   pipeline-parallel stage axis (GPipe-style; absent from the
           reference stack — new capability)
``seq``    sequence/context parallelism (ring attention / Ulysses;
           absent from the reference stack — new capability)
``expert`` expert parallelism for MoE (new capability)
``model``  tensor/model parallelism (Megatron-style; generalizes the
           reference's PS ShardedVariable embedding sharding)
=========  ===========================================================
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

# Mesh-major order. ``data`` outermost (can span DCN), ``model`` innermost
# (needs the fastest ICI links for per-layer collectives).
AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_PIPE = "pipe"
AXIS_SEQ = "seq"
AXIS_EXPERT = "expert"
AXIS_MODEL = "model"

CANONICAL_AXES: tuple[str, ...] = (
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_PIPE,
    AXIS_SEQ,
    AXIS_EXPERT,
    AXIS_MODEL,
)

#: Axes over which gradients of replicated parameters are summed.
BATCH_AXES: tuple[str, ...] = (AXIS_DATA, AXIS_FSDP)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape over the canonical axes.

    Any single axis may be ``-1`` meaning "all remaining devices".  Axes of
    size 1 are kept in the mesh (size-1 collectives are no-ops that XLA
    removes), so downstream sharding rules can always name every canonical
    axis without caring which ones are active.
    """

    data: int = -1
    fsdp: int = 1
    pipe: int = 1
    seq: int = 1
    expert: int = 1
    model: int = 1

    def sizes(self) -> tuple[int, ...]:
        return (self.data, self.fsdp, self.pipe, self.seq, self.expert, self.model)

    def resolve(self, n_devices: int) -> tuple[int, ...]:
        """Concrete per-axis sizes for ``n_devices``, expanding a single -1."""
        sizes = list(self.sizes())
        wild = [i for i, s in enumerate(sizes) if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one axis may be -1, got spec {self}")
        fixed = math.prod(s for s in sizes if s != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh spec {self} needs {fixed} devices, have {n_devices}"
            )
        return tuple(sizes)

    def build(self, devices: Sequence[jax.Device] | None = None) -> Mesh:
        return build_mesh(self, devices)


def build_mesh(
    spec: MeshSpec, devices: Sequence[jax.Device] | None = None
) -> Mesh:
    """Build a ``jax.sharding.Mesh`` with ICI-topology-aware device order.

    ``mesh_utils.create_device_mesh`` assigns devices so that innermost mesh
    axes map to nearest-neighbor ICI links on the TPU torus (the role
    NcclManager's topology detection plays in the reference stack —
    SURVEY.md §5.8).
    """
    if devices is None:
        devices = jax.devices()
    sizes = spec.sizes()
    if -1 not in sizes:
        # fully-fixed spec: take a prefix of the available devices, so e.g.
        # OneDeviceStrategy semantics (data=1) work on a multi-device host.
        # Single-process only: in a multi-host job a prefix mesh would contain
        # devices other processes can't address — that needs an explicit
        # device list from the caller.
        needed = math.prod(sizes)
        if needed < len(devices):
            if jax.process_count() > 1:
                raise ValueError(
                    f"mesh spec {spec} uses {needed} of {len(devices)} global "
                    "devices; sub-mesh selection is single-process only — "
                    "pass an explicit `devices` list (or use -1 axes) in "
                    "multi-host jobs"
                )
            devices = list(devices)[:needed]
    shape = spec.resolve(len(devices))
    # On devices without a physical topology (the CPU test meshes) this is
    # a plain reshape; on a TPU a shape the torus cannot host raises here
    # rather than falling back to an order that ignores the ICI links.
    dev_array = mesh_utils.create_device_mesh(
        shape, devices=list(devices), allow_split_physical_axes=True
    )
    return Mesh(dev_array, CANONICAL_AXES)


def slice_count(devices: Sequence[jax.Device] | None = None) -> int:
    """Number of distinct TPU slices among ``devices`` (1 off-TPU).

    Multi-slice jobs see a ``slice_index`` on each device; collectives
    between slices ride DCN, within a slice ICI (SURVEY.md §5.8).
    """
    if devices is None:
        devices = jax.devices()
    return len({getattr(d, "slice_index", 0) for d in devices})


def build_hybrid_mesh(
    ici_spec: MeshSpec,
    dcn_spec: MeshSpec | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Multi-slice mesh: ``dcn_spec`` axes span slices (DCN), ``ici_spec``
    axes stay within a slice (ICI torus).

    The resulting mesh's axis sizes are the per-axis product of the two
    specs; keep bandwidth-hungry axes (``model``, ``seq``) in ``ici_spec``
    and put ``data`` (one gradient all-reduce per step, latency-tolerant)
    across DCN — the multi-slice recipe the reference's NcclManager never
    had to express (single-slice GPUs).

    ``dcn_spec`` defaults to ``data=<n_slices>``.  With only one slice
    visible (CPU test meshes, single-slice pods) the per-axis product of
    the two specs is built over all devices via :func:`build_mesh` — the
    same combined shape as the multi-slice case, so elastic restore onto
    one slice keeps the mesh shape.
    """
    if devices is None:
        devices = jax.devices()
    slice_sizes: dict[int, int] = {}
    for d in devices:
        idx = getattr(d, "slice_index", 0)
        slice_sizes[idx] = slice_sizes.get(idx, 0) + 1
    n_slices = len(slice_sizes)
    if n_slices == 1:
        if dcn_spec is not None:
            # Keep the combined shape identical to the multi-slice case
            # (elastic restore onto one slice must not halve the mesh):
            # per-axis product, -1 wildcards preserved.
            merged = MeshSpec(*(
                -1 if -1 in (d, i) else d * i
                for d, i in zip(dcn_spec.sizes(), ici_spec.sizes())
            ))
            return build_mesh(merged, devices)
        return build_mesh(ici_spec, devices)
    if len(set(slice_sizes.values())) != 1:
        raise ValueError(
            f"slices have unequal device counts {slice_sizes}; a hybrid "
            "mesh needs uniform slices (whole slices lie along DCN axes)"
        )
    per_slice = len(devices) // n_slices
    dcn_spec = dcn_spec or MeshSpec(data=n_slices)
    dcn_shape = dcn_spec.resolve(n_slices)
    ici_shape = ici_spec.resolve(per_slice)
    try:
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=list(devices),
            allow_split_physical_axes=True,
        )
    except (NotImplementedError, ValueError):
        # No physical-topology info.  Order devices slice-major, lay the
        # DCN axes over the slice dimension and the ICI axes within a
        # slice, then interleave (dcn_i, ici_i) per canonical axis — the
        # same layout create_hybrid_device_mesh produces, minus torus
        # awareness.  A plain reshape to the product shape would only be
        # correct when the DCN axes happen to be the outermost ones.
        n_axes = len(ici_shape)
        total = tuple(d * i for d, i in zip(dcn_shape, ici_shape))
        ordered = sorted(devices, key=lambda d: (getattr(d, "slice_index", 0),
                                                 getattr(d, "id", 0)))
        dev_array = np.empty(len(ordered), dtype=object)
        dev_array[:] = ordered
        dev_array = dev_array.reshape(*dcn_shape, *ici_shape)
        interleave = [ax for i in range(n_axes) for ax in (i, n_axes + i)]
        dev_array = dev_array.transpose(interleave).reshape(total)
    return Mesh(dev_array, CANONICAL_AXES)


# --- Strategy-zoo presets: each reference strategy is just a mesh shape. ---


def one_device_mesh(device: jax.Device | None = None) -> Mesh:
    """``OneDeviceStrategy`` equivalent: a 1×1×…×1 mesh on one device."""
    devices = [device] if device is not None else jax.local_devices()[:1]
    return build_mesh(MeshSpec(data=1), devices)


def mirrored_mesh(devices: Sequence[jax.Device] | None = None) -> Mesh:
    """``MirroredStrategy`` equivalent: all *local* devices on the data axis."""
    return build_mesh(MeshSpec(data=-1), devices or jax.local_devices())


def multi_worker_mesh() -> Mesh:
    """``MultiWorkerMirroredStrategy`` equivalent: all *global* devices on
    ``data`` — slice-aware: on a multi-slice job the data axis is laid out
    with whole slices contiguous so the gradient all-reduce's intra-slice
    phase rides ICI and only the inter-slice phase touches DCN."""
    return build_hybrid_mesh(MeshSpec(data=-1), devices=jax.devices())


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """The batch axes present in ``mesh`` (for gradient psum / batch sharding)."""
    return tuple(a for a in BATCH_AXES if a in mesh.axis_names)


def replica_count(mesh: Mesh) -> int:
    """Number of data-parallel replicas (product of batch-axis sizes)."""
    return math.prod(mesh.shape[a] for a in data_axes(mesh))
