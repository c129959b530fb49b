"""The host's own milliseconds per scheduler step (a file only: no accepted
cell reports it; see README.md beside this file).

From /statsz at the window's two ends: `chunked.phase_s` is the cumulative
seconds of scheduler steps by phase (`intake`, `prepare`, `dispatch`,
`fetch`, `emit`; the program's own spans `polyaxon.sched.intake` and
`polyaxon.step.*`). `fetch` is the blocking read of a program's result, the
wait for the device; the rest is the host's.

    value = 1000 x (sum of the phases' differences - fetch's) / steps

None where the program reports no `phase_s` (a parent without these spans)
or no step ran.
"""


def read(obs):
    s0 = (obs.get("stats0") or {}).get("chunked") or {}
    s1 = (obs.get("stats1") or {}).get("chunked") or {}
    p0, p1 = s0.get("phase_s"), s1.get("phase_s")
    steps = s1.get("steps", 0) - s0.get("steps", 0)
    if not p0 or not p1 or steps <= 0:
        return None
    host = sum(p1[k] - p0.get(k, 0.0) for k in p1 if k != "fetch")
    return 1e3 * host / steps
