"""Device-mesh construction from the Polyaxonfile `mesh:` block.

Replaces the reference's NCCL/MPI rendezvous wiring (SURVEY.md §5: env-var
plumbing like TF_CONFIG/MASTER_ADDR was the reference's whole comm backend)
with a `jax.sharding.Mesh`: axes named data/fsdp/model/pipeline/context/
expert; XLA chooses ICI vs DCN collectives from device placement.

Axis order is fixed so that the innermost axes (model, context) map to
adjacent devices — tensor-parallel and ring collectives then ride
nearest-neighbor ICI links instead of hopping the torus.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

# outer→inner: DCN-tolerant axes first, latency-critical axes innermost.
# `batch` is the serving twin of `data`: a replica's decode mesh splits
# concurrent sequences over it (no collectives), keeping `data`/`fsdp`
# free to mean what they mean in training specs.
AXIS_ORDER = ("batch", "pipeline", "data", "fsdp", "expert", "context", "model")

# batch-sharded axes: the global batch dim is split across these
BATCH_AXES = ("batch", "data", "fsdp")

# the serving mesh is deliberately 2-D — see decode_mesh()
DECODE_AXES = ("batch", "model")


def resolve_axis_sizes(
    spec_sizes: Optional[dict[str, int]], n_devices: int
) -> dict[str, int]:
    """Fill the -1 axis, default to pure DP, validate the product."""
    sizes = dict(spec_sizes or {})
    if not sizes:
        sizes = {"data": n_devices}
    fixed = math.prod(v for v in sizes.values() if v != -1)
    fill_axes = [k for k, v in sizes.items() if v == -1]
    if fill_axes:
        if n_devices % fixed != 0:
            raise ValueError(f"mesh {sizes} does not divide {n_devices} devices")
        sizes[fill_axes[0]] = n_devices // fixed
    elif fixed != n_devices:
        raise ValueError(
            f"mesh {sizes} multiplies to {fixed}, but {n_devices} devices present"
        )
    return {ax: sizes[ax] for ax in AXIS_ORDER if ax in sizes}


def build_mesh(
    spec_sizes: Optional[dict[str, int]] = None,
    devices: Optional[list] = None,
    *,
    slices: int = 1,
) -> Mesh:
    """One mesh over all devices; `slices > 1` builds a hybrid ICI×DCN mesh.

    Multi-slice (SURVEY.md §2:120-121 "ICI within a slice, DCN across
    slices"): the `data` axis is split DCN-major — its outer part strides
    across slices, its inner part and every other axis stay inside one
    slice. Gradient all-reduces then decompose into a fast intra-slice
    reduce-scatter over ICI plus a small cross-slice all-reduce over DCN,
    while tensor/context/expert collectives never leave the slice —
    `mesh_utils.create_hybrid_device_mesh` semantics. On hardware the real
    slice assignment comes from `device.slice_index`; on virtual/CPU
    slices the device list is treated as `slices` contiguous blocks."""
    devices = devices if devices is not None else jax.devices()
    sizes = resolve_axis_sizes(spec_sizes, len(devices))
    if slices > 1:
        return _build_hybrid_mesh(sizes, devices, slices)
    # mesh_utils knows the physical ICI topology (it reads device coords)
    # and lays logical axes onto it to keep inner axes on adjacent chips. A
    # shape it cannot lay out raises: reshaping the device list in
    # enumeration order instead would put tensor-parallel neighbours on
    # non-adjacent chips without a word.
    from jax.experimental import mesh_utils

    dev_array = mesh_utils.create_device_mesh(
        tuple(sizes.values()), devices=devices
    )
    return Mesh(dev_array, tuple(sizes.keys()))


def _build_hybrid_mesh(sizes: dict[str, int], devices, slices: int) -> Mesh:
    if len(devices) % slices:
        raise ValueError(
            f"{len(devices)} devices do not split into {slices} slices"
        )
    data = sizes.get("data", 1)
    if data % slices:
        raise ValueError(
            f"multi-slice meshes split the data axis across slices: "
            f"data={data} must be divisible by slices={slices} "
            f"(mesh {sizes})"
        )
    per_slice = dict(sizes)
    per_slice["data"] = data // slices
    axes = tuple(per_slice.keys())
    on_tpu = any(getattr(d, "platform", "") == "tpu" for d in devices)
    if on_tpu:
        # real hardware: the devices' slice assignment must MATCH the spec
        # — neither silently regrouping slices (model/context collectives
        # would cross DCN) nor silently flattening them is acceptable.
        # mesh_utils groups by slice_index.
        slice_ids = {getattr(d, "slice_index", None) for d in devices}
        if None in slice_ids or len(slice_ids) != slices:
            raise ValueError(
                f"tpu devices span {len(slice_ids)} distinct slice(s) but "
                f"the spec asks for slices={slices} — fix the job's slice "
                "request or the mesh"
            )
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_hybrid_device_mesh(
            tuple(per_slice.values()),
            dcn_mesh_shape=tuple(
                slices if ax == "data" else 1 for ax in axes
            ),
            devices=devices,
        )
    else:
        # virtual slices (CPU tests / dryrun): contiguous device blocks per
        # slice; the data axis is laid out slice-major so index i of the
        # global data axis maps to slice i // (data/slices)
        arr = np.asarray(devices).reshape(
            (slices,) + tuple(per_slice.values())
        )
        data_idx = list(axes).index("data")
        arr = np.moveaxis(arr, 0, data_idx)
        shape = list(per_slice.values())
        shape[data_idx] = data
        dev_array = arr.reshape(tuple(shape))
    return Mesh(dev_array, axes)


def decode_mesh(
    spec_sizes: Optional[dict[str, int]] = None,
    devices: Optional[list] = None,
) -> Mesh:
    """Named 2-D serving mesh (`batch` × `model`) over a replica's chips.

    Decode wants a fixed, explicit shape: `batch` splits concurrent
    sequences (pure data parallelism — nothing on the per-token critical
    path), `model` tensor-parallels the seven projection kernels so one
    token's matmuls span chips. Axes beyond these two are rejected so the
    serving compile-cache key stays 2-D. A replica may deliberately use
    fewer chips than visible (the sizes multiply to less than the device
    count): the mesh then takes the first prod(sizes) devices, which on
    hardware are ICI-adjacent. No spec means one device, fully replicated
    — the pre-mesh single-chip restore path, unchanged.
    """
    devices = list(devices) if devices is not None else list(jax.devices())
    sizes = {ax: int(n) for ax, n in (spec_sizes or {}).items()}
    # legacy serve specs spelled batch-parallelism as data/fsdp (the
    # training names); they fold into `batch` — same batch-dim split,
    # one canonical serving mesh shape
    folded = 1
    for legacy in ("data", "fsdp"):
        n = sizes.pop(legacy, 1)
        folded = -1 if (n == -1 or folded == -1) else folded * n
    if folded != 1:
        if sizes.get("batch", 1) != 1:
            raise ValueError(
                "decode mesh: give `batch` OR legacy data/fsdp, not both"
            )
        sizes["batch"] = folded
    bad = sorted(set(sizes) - set(DECODE_AXES))
    if bad:
        raise ValueError(
            f"decode mesh allows axes {DECODE_AXES}, got extra {bad}"
        )
    if not sizes:
        devices = devices[:1]
    sizes.setdefault("batch", 1)
    sizes.setdefault("model", 1)
    if -1 in sizes.values():
        sizes = resolve_axis_sizes(sizes, len(devices))
    need = math.prod(sizes.values())
    if need > len(devices):
        raise ValueError(
            f"decode mesh {sizes} needs {need} devices, "
            f"only {len(devices)} visible"
        )
    return build_mesh(sizes, devices[:need])


def local_batch_slice(mesh: Mesh) -> int:
    """How many ways the batch dimension is split on this mesh."""
    return math.prod(mesh.shape.get(ax, 1) for ax in BATCH_AXES)
