"""What the readers of the windowed-attention and grouped-product kernels
share: the events of named kernels inside the whole programs of a window."""

from __future__ import annotations


def whole_programs(dev: dict, lo, hi) -> tuple[list, int]:
    """(the chip's operations inside the programs that ran wholly in
    [lo, hi), how many such programs); inside [lo, hi) itself, and 0, where
    the trace names no program."""
    if lo is None:
        return dev["ops"], 0
    inside = [(s, s + d) for _, s, d in dev.get("modules") or [] if s >= lo and s + d <= hi]
    if inside:
        lo, hi = min(s for s, _ in inside), max(e for _, e in inside)
    return [e for e in dev["ops"] if e[1] >= lo and e[1] + e[2] <= hi], len(inside)


def kernel_seconds(ops, pattern) -> dict:
    """group -> [calls, seconds] of the events whose name `pattern` matches;
    the group is the pattern's first group (or the whole match)."""
    out: dict = {}
    for name, _, dur in ops:
        m = pattern.match(name)
        if m:
            c = out.setdefault(m.group(1) if m.groups() else m.group(0), [0, 0.0])
            c[0] += 1
            c[1] += dur * 1e-9
    return out


def window_ops(obs):
    """(operations, whole programs) of the first chip in the traced window,
    or None where there is no trace to read."""
    raw, red = obs.get("trace_raw"), obs.get("trace")
    if not raw or not red or not raw.get("devices") or not obs.get("peaks"):
        return None
    return whole_programs(raw["devices"][0], red.get("lo"), red.get("hi"))
