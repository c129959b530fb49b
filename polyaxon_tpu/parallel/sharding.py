"""Parameter/batch sharding: model-declared logical rules → NamedShardings.

Models declare `(param-path-regex, logical-axes)` rules (models/registry.py).
At setup the trainer matches each param's path against the rules and builds a
`NamedSharding` over the run's mesh. Logical axes not present in the mesh
degrade to replication, so one rule set serves pure-DP through full
TP+FSDP+EP meshes — the TPU-idiomatic replacement for per-strategy code
paths in the reference's delegated backends.
"""

from __future__ import annotations

import re
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import BATCH_AXES


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def live_axes(mesh: Mesh, axes, dim_size: int) -> tuple:
    """Subset of `axes` present in `mesh` whose joint product divides
    `dim_size` — the degrade-to-replication walk shared by the sharding
    resolver, `constrain`, and the attention shard_map dispatches. An axis
    that doesn't divide is dropped (replicate) while the rest keep
    sharding; correctness over parallelism."""
    live: list = []
    size = 1
    for a in axes:
        if not a or mesh.shape.get(a, 1) == 1:
            continue
        if dim_size % (size * mesh.shape[a]) == 0:
            live.append(a)
            size *= mesh.shape[a]
    return tuple(live)


def _as_spec_entry(live: tuple):
    if not live:
        return None
    return live[0] if len(live) == 1 else tuple(live)


def _spec_for(path: str, shape, rules, mesh: Mesh) -> P:
    for pattern, axes in rules:
        if re.search(pattern, path):
            resolved = []
            for i, ax in enumerate(axes[: len(shape)]):
                cands = ax if isinstance(ax, tuple) else (ax,)
                resolved.append(
                    _as_spec_entry(live_axes(mesh, cands, shape[i]))
                )
            while resolved and resolved[-1] is None:
                resolved.pop()
            return P(*resolved)
    return P()  # replicate by default


def shard_map_nocheck(body, **kwargs):
    """shard_map with the varying-axes check disabled — Pallas kernels
    inside the body don't declare varying mesh axes, so the check must be
    skipped."""
    return jax.shard_map(body, check_vma=False, **kwargs)


def param_shardings(params, rules: Sequence, mesh: Mesh):
    """Pytree of NamedShardings matching `params`' structure."""

    def one(path, leaf):
        return NamedSharding(mesh, _spec_for(_path_str(path), leaf.shape, rules, mesh))

    return jax.tree_util.tree_map_with_path(one, params)


def batch_sharding(mesh: Mesh, extra_axes: Optional[dict[str, str]] = None):
    """Batch dim over data(+fsdp); optionally e.g. {'1': 'context'} to shard
    the sequence dim for context parallelism."""
    batch_axes = tuple(ax for ax in BATCH_AXES if mesh.shape.get(ax, 1) > 1)
    dims: list = [batch_axes if batch_axes else None]
    if extra_axes:
        max_dim = max(int(d) for d in extra_axes)
        dims += [None] * (max_dim - len(dims) + 1)
        for d, ax in extra_axes.items():
            if mesh.shape.get(ax, 1) > 1:
                dims[int(d)] = ax
    while len(dims) > 1 and dims[-1] is None:
        dims.pop()
    return NamedSharding(mesh, P(*dims))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


import contextlib
import threading as _threading

_CONSTRAIN_STATE = _threading.local()


@contextlib.contextmanager
def suspend_constraints():
    """Disable `constrain` while tracing code that runs inside shard_map
    (per-device views must not re-apply global sharding constraints)."""
    prev = getattr(_CONSTRAIN_STATE, "suspended", False)
    _CONSTRAIN_STATE.suspended = True
    try:
        yield
    finally:
        _CONSTRAIN_STATE.suspended = prev


def constraints_suspended() -> bool:
    """True while tracing inside a shard_map body (pipeline stages etc.) —
    code that dispatches on 'is a global mesh in scope' must treat the
    per-device view as single-device."""
    return getattr(_CONSTRAIN_STATE, "suspended", False)


def constrain(x, *axes):
    """`with_sharding_constraint` against the trainer-bound mesh
    (parallel/ring.current_mesh). Axes name logical mesh axes (or tuples of
    them); axes missing from the mesh degrade to None, and outside any mesh
    the call is a no-op — so model code can annotate unconditionally.

    Pinning activation layouts stops GSPMD from picking inconsistent
    shardings between forward and backward (the 'involuntary full
    rematerialization' warnings on TP meshes — a real resharding on ICI)."""
    import jax

    from .ring import current_mesh

    mesh = current_mesh()
    if mesh is None or getattr(_CONSTRAIN_STATE, "suspended", False):
        return x
    resolved = []
    for i, ax in enumerate(axes[: x.ndim]):
        cands = ax if isinstance(ax, tuple) else (ax,)
        # indivisible dims degrade to replication (e.g. a module traced
        # directly with a small batch while a big-mesh is bound)
        resolved.append(_as_spec_entry(live_axes(mesh, cands, x.shape[i])))
    while resolved and resolved[-1] is None:
        resolved.pop()
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved))
    )


def make_global_batch(batch: dict, mesh: Mesh, sharding: NamedSharding):
    """Host-local numpy batch → global sharded jax.Arrays.

    Single-process: device_put with the sharding (XLA splits it). Multi-host:
    each host contributes its local shard of the global batch.
    """
    import jax.numpy as jnp  # noqa: F401

    if jax.process_count() == 1:
        return jax.device_put(batch, sharding)
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, x), batch
    )
