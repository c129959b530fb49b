"""Weights from the seed, made by the benchmark and not by the program.

The program under test and the plain reference both get their weights from
here, so that the reference takes nothing the program has made. A leaf is a
pure function of (seed, its path in the program's tree, its shape): the
reference asks for one leaf at a time, long after the program's copy is gone.

A configuration's file gives the rules (`init`): a list of
`[regex on the leaf's path, kind, std]`, first match wins. Kinds: `ones`,
`zeros`, `normal` (std as given), `fan_in` (std = 1/sqrt(shape[-2])).
Values are drawn in float32 and cast to the type asked for in the same
jitted call, one compiled program per (shape, kind, type), which is a dozen
small programs and not one per leaf.
"""

from __future__ import annotations

import functools
import re
import zlib

import jax
import jax.numpy as jnp

SEED_MOD = 2147483647  # seeds run past 2**31; PRNGKey takes a 32-bit int


def fold_seed(seed: int) -> int:
    return int(seed) % SEED_MOD


def path_str(path) -> str:
    """`layer_3/attention/q_proj/kernel` from a jax key path."""
    parts = []
    for k in path:
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
        else:
            parts.append(str(k))
    return "/".join(parts)


def rule_for(path: str, rules) -> tuple[str, float]:
    for pat, kind, *std in rules:
        if re.search(pat, path):
            return kind, float(std[0]) if std else 0.0
    raise KeyError(f"no init rule matches leaf {path!r}")


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _draw(seed, tag, shape, dtype, kind, std):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), tag)
    if kind == "fan_in":
        std = 1.0 / (shape[-2] ** 0.5)
    elif kind != "normal":
        raise ValueError(f"unknown init kind {kind!r}")
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def leaf(seed: int, path: str, shape, dtype, rules):
    """The leaf at `path`, on the default device."""
    kind, std = rule_for(path, rules)
    tag = zlib.crc32(path.encode()) & 0x7FFFFFFF
    return _draw(
        jnp.uint32(fold_seed(seed)), jnp.uint32(tag), tuple(int(s) for s in shape),
        jnp.dtype(dtype), kind, std,
    )


def tree(seed: int, abstract, rules, dtype=None, shardings=None):
    """A whole tree shaped like `abstract` (ShapeDtypeStructs). `dtype`
    overrides the float type (the type the weights are served in)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    shard = (
        jax.tree_util.tree_leaves(shardings) if shardings is not None else None
    )
    out = []
    for i, (path, a) in enumerate(flat):
        dt = a.dtype
        if dtype is not None and jnp.issubdtype(dt, jnp.floating):
            dt = dtype
        x = leaf(seed, path_str(path), a.shape, dt, rules)
        if shard is not None:
            x = jax.device_put(x, shard[i])
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)
