"""From the profiler's trace to numbers: device busy time as a union of
intervals, time per operation, and idle gaps named by what the host was
doing in them.

`read_xplane` turns an `.xplane.pb` into plain lists (nanoseconds); the
arithmetic below works on those lists alone, so it is tested on a small
extract of a recorded trace (`tests/data/`), not on the profiler.

Layout of a TPU trace as the profiler of jax 0.9 writes it: one plane per
chip named `/device:TPU:<n>`, with a line `XLA Ops` (one event per executed
HLO operation, named by the instruction) and a line `XLA Modules` (one event
per executed program, named `jit_<function>(<fingerprint>)`); host threads
are lines of the plane `/host:CPU`, where `TraceAnnotation` spans appear
under their own names. Device and host events share one clock.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str, host_prefixes=("cellbench.",)) -> dict:
    """{"devices": [{"plane", "ops": [[name, start, dur]], "modules": [...]}],
    "host": [[name, start, dur]]} with times in ns. Host events are kept only
    where their name starts with one of `host_prefixes` (the benchmark's own
    spans): a host plane holds millions of Python frames otherwise."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        name = plane.name
        if re.match(r"^/device:TPU:\d+$", name):
            dev = {"plane": name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events
                    ]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events
                    ]
            devices.append(dev)
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(tuple(host_prefixes)):
                        host.append([e.name, int(e.start_ns), int(e.duration_ns)])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def describe_xplane(path: str, top: int = 40) -> dict:
    """What is in a trace, for a first look by hand: planes, lines, event
    counts, and the commonest event names of each line."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            counts: dict = {}
            n = 0
            for e in line.events:
                n += 1
                c = counts.setdefault(e.name, [0, 0])
                c[0] += 1
                c[1] += int(e.duration_ns)
            names = sorted(counts.items(), key=lambda kv: -kv[1][1])[:top]
            lines.append({"line": line.name, "events": n,
                          "top": [[k, v[0], v[1]] for k, v in names]})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


# ---------------------------------------------------------------- arithmetic
def clip(events, lo: int, hi: int):
    """Events cut to [lo, hi)."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append([name, s, e - s])
    return out


def merged(events) -> list[list[int]]:
    """Union of the events' intervals as sorted disjoint [start, end]."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    out: list[list[int]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events) -> int:
    return sum(e - s for s, e in merged(events))


def totals(events, strip=None) -> dict:
    """name -> [count, total ns]. `strip` is a regex removed from each name
    first (an instruction's numeric suffix, a module's fingerprint)."""
    out: dict = {}
    for name, _, dur in events:
        if strip:
            name = re.sub(strip, "", name)
        c = out.setdefault(name, [0, 0])
        c[0] += 1
        c[1] += dur
    return out


_HLO = re.compile(r"^%?([\w.\-]+?)(?:\.\d+)? = .*? ([\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """`attention custom-call:tpu_custom_call` from an event named by the
    whole text of its HLO instruction (`%attention.177 = (bf16[...]) custom-call(
    ...), custom_call_target="tpu_custom_call", ...`): the instruction's name
    without its number, its opcode, and a custom call's target. Names that
    are not HLO text come back unchanged (cut to 120 characters)."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    stem, opcode = m.group(1), m.group(2)
    t = _TARGET.search(name)
    return f"{stem} {opcode}" + (f":{t.group(1)}" if t else "")


def by_kind(events) -> dict:
    """short_name -> [count, total ns]: the thousands of operations of a
    step folded into the few kinds a reader can take in."""
    out: dict = {}
    for name, _, dur in events:
        c = out.setdefault(short_name(name), [0, 0])
        c[0] += 1
        c[1] += dur
    return out


def matching_ns(events, pattern: str) -> tuple[int, int]:
    """(count, total ns) of the events whose name matches `pattern`."""
    rx = re.compile(pattern)
    hit = [d for n, _, d in events if rx.search(n)]
    return len(hit), sum(hit)


def gaps(events, lo: int, hi: int, floor_ns: int = 0) -> list[list[int]]:
    """Idle intervals of [lo, hi): where no event runs, at least floor_ns long."""
    out, at = [], lo
    for s, e in merged(clip(events, lo, hi)):
        if s - at >= max(floor_ns, 1):
            out.append([at, s])
        at = max(at, e)
    if hi - at >= max(floor_ns, 1):
        out.append([at, hi])
    return out


SMALL_GAP_NS = 1000
BETWEEN_OPS = "(between operations, each under 1 us)"
NO_SPAN = "(no span)"


def attribute(gap_list, host, outer: str = "cellbench.window") -> dict:
    """Idle ns by the host span that covers most of each gap. Gaps under a
    microsecond are the device passing from one operation to the next and
    go to one entry of their own. The `outer` span (the window itself) is no
    answer and is left out; a gap of which no span covers half goes to
    `(no span)`."""
    import bisect

    spans = sorted((hs, hs + hd, name) for name, hs, hd in host if name != outer)
    starts = [x[0] for x in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    out: dict = {}
    for s, e in gap_list:
        if e - s < SMALL_GAP_NS:
            out[BETWEEN_OPS] = out.get(BETWEEN_OPS, 0) + (e - s)
            continue
        best, best_cover = NO_SPAN, 0
        i = bisect.bisect_left(starts, e) - 1
        while i >= 0 and spans[i][0] >= s - longest:
            hs, he, name = spans[i]
            cover = min(e, he) - max(s, hs)
            if cover > best_cover:
                best, best_cover = name, cover
            i -= 1
        if best_cover * 2 < (e - s):
            best = NO_SPAN
        out[best] = out.get(best, 0) + (e - s)
    return out


def top(d: dict, n: int = 10, scale: float = 1e-9) -> list:
    """[[name, seconds], ...], largest first. Accepts name -> ns or
    name -> [count, ns]."""
    rows = [
        [k, (v[1] if isinstance(v, (list, tuple)) else v) * scale]
        for k, v in d.items()
    ]
    return sorted(rows, key=lambda r: -r[1])[:n]


def reduce(trace: dict, lo: int | None = None, hi: int | None = None) -> dict:
    """The summary every reader gets: per chip and averaged busy seconds in
    [lo, hi) (default: the span of the device's own events), time by
    operation and by program, and idle time by host span."""
    devs = trace["devices"]
    if not devs or not any(d["ops"] for d in devs):
        return {"busy_s": 0.0, "span_s": 0.0, "chips": 0, "ops": {}, "kinds": {},
                "modules": {}, "idle": {}, "lo": lo, "hi": hi}
    all_ops = [e for d in devs for e in d["ops"]]
    lo = min(e[1] for e in all_ops) if lo is None else lo
    hi = max(e[1] + e[2] for e in all_ops) if hi is None else hi
    busy = [busy_ns(clip(d["ops"], lo, hi)) for d in devs]
    first = devs[0]
    ops = clip(first["ops"], lo, hi)
    return {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "span_s": (hi - lo) * 1e-9,
        "chips": len(devs),
        "lo": lo,
        "hi": hi,
        "ops": totals(ops),
        "kinds": by_kind(ops),
        "modules": totals(clip(first["modules"], lo, hi), strip=r"\(\d+\)$"),
        "idle": attribute(gaps(ops, lo, hi), clip(trace["host"], lo, hi)),
    }


def summarise(trace: dict) -> tuple[dict, dict]:
    """(the reduction over the benchmark's own window span, the `breakdown`
    of a result line) of a traced run."""
    lo, hi = window_of(trace)
    red = reduce(trace, lo, hi)
    return red, {"device_ops": top(red["kinds"]), "idle_gaps": top(red["idle"])}


def window_of(trace: dict, span_name: str = "cellbench.window"):
    """[lo, hi) of the benchmark's own window span, or (None, None)."""
    for name, s, d in trace["host"]:
        if name == span_name:
            return s, s + d
    return None, None


# ------------------------------------------------------------ recorded sample
def save_sample(trace: dict, path: str, per_line: int = 400) -> None:
    """A small extract of a trace (the first `per_line` events of each list)
    as gzipped JSON: what the tests of the reduction run on."""
    small = {
        "devices": [
            {"plane": d["plane"], "ops": d["ops"][:per_line],
             "modules": d["modules"][:per_line]}
            for d in trace["devices"]
        ],
        "host": trace["host"][:per_line],
    }
    with gzip.open(path, "wt") as f:
        json.dump(small, f)


def load_sample(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
