"""The arithmetic of the comparison that decides `correct`.

Every number compared is a gap, is printed beside its limit, and passes when
it is at or under the limit.
"""

from __future__ import annotations

import statistics

import numpy as np


def leaf_norms(leaves: dict) -> dict:
    return {
        k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
        for k, v in leaves.items()
    }


def worst_norm_gap(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """By the worst leaf: the gap between the program's norm and the
    reference's (not the norm of their difference), against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    pn, rn = leaf_norms(prog), leaf_norms(ref)
    if set(pn) != set(rn):
        raise KeyError(f"leaves differ: {sorted(set(pn) ^ set(rn))[:6]}")
    med = statistics.median(rn.values())
    worst, where = 0.0, ""
    for k in rn:
        if k in skip:
            continue
        gap = abs(pn[k] - rn[k]) / max(rn[k], med, 1e-30)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def one_minus_cos(prog: dict, ref: dict) -> float:
    """1 - cosine between the two sides over all leaves laid end to end:
    what the gap of norms cannot see, a gradient of the right length that
    points elsewhere. It grows with the square of the element-wise error,
    so a lower precision stands well clear of the stated one."""
    keys = sorted(ref)
    a = np.concatenate([np.asarray(prog[k], np.float64).ravel() for k in keys])
    b = np.concatenate([np.asarray(ref[k], np.float64).ravel() for k in keys])
    den = np.linalg.norm(a) * np.linalg.norm(b)
    if not np.isfinite(den) or den == 0:
        return float("inf")
    return float(1.0 - np.dot(a, b) / den)


def worst_rel_diff(prog: dict, ref: dict) -> tuple[float, str]:
    """By the worst leaf: the norm of the difference against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    rn = leaf_norms(ref)
    med = statistics.median(rn.values())
    worst, where = 0.0, ""
    for k in rn:
        diff = float(np.linalg.norm(
            np.asarray(prog[k], np.float64).ravel() - np.asarray(ref[k], np.float64).ravel()
        ))
        gap = diff / max(rn[k], med, 1e-30)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def tiny_gradient_leaves(ref_grads: dict, share: float = 1e-3) -> set:
    """Leaves whose gradient in the reference is nought to rounding: under
    `share` of the median leaf's norm. They move under Adam by round-off
    alone and are left out of the comparison of the parameters' change."""
    rn = leaf_norms(ref_grads)
    med = statistics.median(rn.values())
    return {k for k, n in rn.items() if n < share * med}


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def logit_gaps(ref_logits, tokens) -> np.ndarray:
    """For each position, by how much the token's logit lies below the
    reference's best. 0 where the token is the reference's own first."""
    ref_logits = np.asarray(ref_logits, np.float64)
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, np.asarray(tokens)[:, None], axis=-1)[:, 0]
    return best - got


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """`numbers` name -> value; `limits` name -> limit. The cell's limits say
    which numbers are compared (PERF.md says why the others are not: they
    have no upper reading). A limit without a number, or a value that is not
    finite, fails; so does a cell with no limit at all."""
    table, ok = {}, bool(limits)
    for name in sorted(limits):
        val, lim = numbers.get(name), limits.get(name)
        good = (
            val is not None and lim is not None
            and np.isfinite(val) and val <= lim
        )
        ok = ok and good
        table[name] = {"value": val, "limit": lim, "ok": bool(good)}
    return ok, table
