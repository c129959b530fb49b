"""The decode-step programs' share of the HBM roofline (a file only: no
accepted cell reports it; see README.md beside this file).

A decode-only step must read every matmul weight once and every live key
and value once (`flops.decode_step_bytes`). Device time: the events of the
first chip's `XLA Modules` line inside the window whose program is the
decode step (`jit_decode_step(...)`: `models/generate.py::jit_paged_step`
names it). Live KV tokens: from the clients' records, the time average over
the window of the summed context lengths (prompt + tokens received so far)
of the requests that are decoding, i.e. between their first frame and their
last. /statsz `kv.pages_used` x the page size would count the pages the
prefix cache keeps for finished prompts too, and where the pool is full of
those (97 % used with 32 rows live; my chip run, PR 27) the share would read
half as high again.

    value = 100 x (calls x bytes / peak HBM bytes/s) / device seconds

None where no such program ran in the window or no request was decoding.
"""

import re

from cellbench import flops

PROGRAM = re.compile(r"^jit_decode_step(\(\d+\))?$")


def live_kv_tokens(records, t_open: float, t_close: float) -> float:
    """Time average over [t_open, t_close) of the context tokens held by
    the requests that are decoding."""
    total = 0.0
    for r in records:
        length, frames = r["prompt_len"], r["frames"]
        for k, (t, n) in enumerate(frames):
            length += n
            if k + 1 == len(frames):
                break  # the last frame ends the request: nothing reads it after
            lo, hi = max(t, t_open), min(frames[k + 1][0], t_close)
            if hi > lo:
                total += length * (hi - lo)
    return total / (t_close - t_open)


def read(obs):
    raw, peaks, red = obs.get("trace_raw"), obs.get("peaks"), obs.get("trace")
    records = obs.get("records")
    if not raw or not peaks or not red or not records or not raw.get("devices"):
        return None
    lo, hi = red.get("lo"), red.get("hi")
    mods = [
        d for name, s, d in raw["devices"][0].get("modules") or []
        if PROGRAM.match(name) and (lo is None or (s >= lo and s + d <= hi))
    ]
    live = live_kv_tokens(records, obs["t_open"], obs["t_close"])
    if not mods or live <= 0:
        return None
    kv_bytes = 1 if obs["cell"]["program"]["serving"].get("kvQuant") else 2
    need = flops.decode_step_bytes(obs["config"], live, kv_bytes=kv_bytes)
    return 100.0 * len(mods) * need / peaks["hbm_bytes_per_s"] / (sum(mods) * 1e-9)
