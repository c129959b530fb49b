"""Seeded, versioned traffic traces — the one workload substrate.

A trace is a header line plus one JSONL record per request:

    {"trace_version": 1, "name": ..., "seed": ..., "generator": ...,
     "params": {...}, "count": N}
    {"i": 0, "at": 0.0, "prompt_len": 16, "max_new": 8, ...}
    ...

Everything random about a trace — arrival times, lengths, tenants,
prompt content — is drawn from a `random.Random(f"{generator}:{seed}")`
stream at generation time, so the same (generator, seed, params) triple
reproduces the same trace byte-for-byte across processes (string
seeding hashes via sha512, no PYTHONHASHSEED dependence; pinned by
tests/test_scenarios.py).

Records carry a `prompt_seed`, not token ids: `prompt_tokens()` derives
the ids on demand, which keeps million-record traces cheap enough to
stream through the twin (the twin never needs tokens at all) and keeps
JSONL lines small. Shared-prefix cohorts derive their common prefix
from the cohort id, so two records in one cohort really do share prompt
bytes — the prefix cache sees real reuse, not a statistical fiction.

Generators are registered by name in `GENERATORS`; `generate(name,
seed, **params)` returns a lazy iterator so a million-user soak never
materializes a million dataclasses.
"""

from __future__ import annotations

import dataclasses
import json
import math
import random
from typing import Iterable, Iterator, Optional

TRACE_VERSION = 1


@dataclasses.dataclass(frozen=True)
class TraceRequest:
    """One request in a trace.

    at:       seconds since the trace epoch (non-decreasing within a
              trace) — the open-loop driver fires at epoch + at,
              regardless of how earlier requests fared.
    entropy:  "high" prompts are uniform-random token ids (adversarial
              for speculation and prefix caching); "low" prompts are
              cyclic and compressible (speculation-friendly).
    prefix_group: cohort id — records sharing it share a real token
              prefix (3/4 of the shorter prompt), so prefix-cache
              scenarios exercise actual KV reuse.
    disconnect_after_ms: the client abandons the stream this long after
              its first byte — the mid-stream disconnect ingredient.
    """

    i: int
    at: float
    prompt_len: int
    max_new: int
    temperature: float = 0.8
    top_k: Optional[int] = 40
    seed: int = 0  # sampling seed (rides the request body)
    prompt_seed: int = 0  # derives prompt token ids on demand
    tenant: str = "default"
    entropy: str = "high"
    prefix_group: Optional[int] = None
    disconnect_after_ms: Optional[float] = None
    deadline_ms: Optional[float] = None


def prompt_tokens(rec: TraceRequest, vocab_size: int) -> list[int]:
    """Derive the record's prompt token ids (deterministic per record).

    Low-entropy prompts are cyclic ramps — an n-gram drafter predicts
    them near-perfectly. Cohort records share a common prefix derived
    from the cohort id alone, so every member replays the same bytes."""
    n = int(rec.prompt_len)
    if rec.entropy == "low":
        base = rec.prompt_seed % vocab_size
        return [(base + j) % vocab_size for j in range(n)]
    out: list[int] = []
    if rec.prefix_group is not None:
        plen = max(1, (3 * n) // 4)
        prng = random.Random(f"trace-prefix:{rec.prefix_group}")
        out = [prng.randrange(vocab_size) for _ in range(plen)]
    rng = random.Random(f"trace-prompt:{rec.prompt_seed}")
    out += [rng.randrange(vocab_size) for _ in range(n - len(out))]
    return out


def body_for(rec: TraceRequest, vocab_size: int, *,
             tenancy: bool = False) -> dict:
    """The record as a POST /generate body (tokens derived on demand).

    `tenancy=True` stamps the record's tenant into the body — opt-in,
    because a server WITHOUT tenants configured rejects named tenants
    (400), and the historical generators label records with synthetic
    tenant names the classic rigs never declare."""
    body = {
        "tokens": [prompt_tokens(rec, vocab_size)],
        "maxNewTokens": int(rec.max_new),
        "temperature": float(rec.temperature),
        "seed": int(rec.seed),
    }
    if rec.top_k is not None:
        body["topK"] = int(rec.top_k)
    if rec.deadline_ms is not None:
        body["deadlineMs"] = float(rec.deadline_ms)
    if tenancy and rec.tenant:
        body["tenant"] = rec.tenant
    return body


# ------------------------------------------------------------------ io
def write_trace(path, header: dict, records: Iterable[TraceRequest]) -> int:
    """Stream a trace to JSONL; returns the record count (also stamped
    into the header's `count`). None-valued record fields are omitted to
    keep lines small."""
    recs = list(records)
    head = {
        "trace_version": TRACE_VERSION,
        **header,
        "count": len(recs),
    }
    with open(path, "w", encoding="utf-8") as f:
        f.write(json.dumps(head, sort_keys=True) + "\n")
        for r in recs:
            d = {
                k: v
                for k, v in dataclasses.asdict(r).items()
                if v is not None
            }
            f.write(json.dumps(d, sort_keys=True) + "\n")
    return len(recs)


def read_trace(path) -> tuple[dict, list[TraceRequest]]:
    """Read a JSONL trace back; validates the version stamp."""
    with open(path, encoding="utf-8") as f:
        head = json.loads(f.readline())
        ver = head.get("trace_version")
        if ver != TRACE_VERSION:
            raise ValueError(
                f"trace {path}: version {ver!r}, expected {TRACE_VERSION}"
            )
        recs = [
            TraceRequest(**json.loads(line))
            for line in f
            if line.strip()
        ]
    return head, recs


# ------------------------------------------------------------ samplers
def _lognormal_len(rng: random.Random, median: float, sigma: float,
                   lo: int, hi: int) -> int:
    """Heavy-tailed length: lognormal around `median`, clamped."""
    v = rng.lognormvariate(math.log(max(1.0, median)), sigma)
    return max(lo, min(hi, int(round(v))))


def _zipf_choice(rng: random.Random, values, s: float = 1.3):
    """Zipf-weighted pick: values[0] most likely, tail ~ rank^-s."""
    weights = [1.0 / (k + 1) ** s for k in range(len(values))]
    total = sum(weights)
    x = rng.random() * total
    for v, w in zip(values, weights):
        x -= w
        if x <= 0:
            return v
    return values[-1]


_TENANTS = ("alpha", "beta", "gamma")
_TENANT_WEIGHTS = (6, 3, 1)


def _tenant(rng: random.Random) -> str:
    return rng.choices(_TENANTS, weights=_TENANT_WEIGHTS, k=1)[0]


# ---------------------------------------------------------- generators
def diurnal(seed: int, *, n: int = 1000, duration_s: float = 60.0,
            base_rps: float = 20.0, amplitude: float = 0.8,
            periods: float = 2.0, median_prompt: float = 14.0,
            sigma: float = 0.5, max_prompt: int = 32,
            news=(4, 6, 8, 12, 16)) -> Iterator[TraceRequest]:
    """Diurnal load curve: a sinusoidal arrival rate (troughs at
    (1-amplitude)x base, peaks at (1+amplitude)x) with lognormal prompt
    lengths, Zipf-weighted output budgets, and a skewed tenant mix —
    the long-soak baseline."""
    rng = random.Random(f"diurnal:{seed}")
    t = 0.0
    for i in range(n):
        phase = 2.0 * math.pi * periods * (t / duration_s)
        rate = max(0.05 * base_rps, base_rps * (1.0 + amplitude * math.sin(phase)))
        t += rng.expovariate(rate)
        yield TraceRequest(
            i=i, at=t,
            prompt_len=_lognormal_len(rng, median_prompt, sigma, 4, max_prompt),
            max_new=_zipf_choice(rng, list(news)),
            seed=i, prompt_seed=rng.randrange(1 << 31),
            tenant=_tenant(rng),
        )


def bursts(seed: int, *, n: int = 600, duration_s: float = 30.0,
           base_rps: float = 15.0, burst_factor: float = 8.0,
           n_bursts: int = 3, burst_len_s: float = 2.0,
           median_prompt: float = 14.0, max_prompt: int = 32,
           news=(4, 6, 8)) -> Iterator[TraceRequest]:
    """Correlated bursts over a Poisson base: seed-chosen windows where
    the rate multiplies by `burst_factor` AND the traffic correlates —
    one tenant, longer prompts — the thundering-herd ingredient."""
    rng = random.Random(f"bursts:{seed}")
    starts = sorted(
        rng.uniform(0.1 * duration_s, 0.9 * duration_s)
        for _ in range(n_bursts)
    )
    burst_tenant = _tenant(rng)
    t = 0.0
    for i in range(n):
        in_burst = any(s <= t < s + burst_len_s for s in starts)
        rate = base_rps * (burst_factor if in_burst else 1.0)
        t += rng.expovariate(rate)
        yield TraceRequest(
            i=i, at=t,
            prompt_len=_lognormal_len(
                rng, median_prompt * (1.5 if in_burst else 1.0), 0.4,
                4, max_prompt,
            ),
            max_new=_zipf_choice(rng, list(news)),
            seed=i, prompt_seed=rng.randrange(1 << 31),
            tenant=burst_tenant if in_burst else _tenant(rng),
        )


def flood(seed: int, *, n: int = 400, rps: float = 60.0,
          prompt_len: int = 24, max_new: int = 12,
          temperature: float = 1.0) -> Iterator[TraceRequest]:
    """Adversarial high-entropy flood: a constant over-capacity rate of
    unique uniform-random prompts at temperature 1.0 — worst case for
    prefix caching AND speculation (nothing repeats, nothing drafts)."""
    rng = random.Random(f"flood:{seed}")
    for i in range(n):
        yield TraceRequest(
            i=i, at=i / rps,
            prompt_len=prompt_len, max_new=max_new,
            temperature=temperature,
            seed=i, prompt_seed=rng.randrange(1 << 31),
            tenant=_tenant(rng), entropy="high",
        )


def shared_prefix(seed: int, *, n: int = 300, rps: float = 20.0,
                  cohorts: int = 4, prompt_len: int = 24,
                  max_new: int = 8) -> Iterator[TraceRequest]:
    """Shared-prefix cohorts: each request joins a seed-chosen cohort
    whose members share 3/4 of their prompt — the prefix-cache and COW
    page-sharing workload."""
    rng = random.Random(f"shared_prefix:{seed}")
    for i in range(n):
        yield TraceRequest(
            i=i, at=i / rps,
            prompt_len=prompt_len, max_new=max_new,
            seed=i, prompt_seed=rng.randrange(1 << 31),
            tenant=_tenant(rng),
            prefix_group=seed * 1000 + rng.randrange(cohorts),
        )


def disconnect_storm(seed: int, *, n: int = 200, rps: float = 15.0,
                     disconnect_frac: float = 0.5, prompt_len: int = 16,
                     max_new: int = 48, after_ms_lo: float = 30.0,
                     after_ms_hi: float = 300.0) -> Iterator[TraceRequest]:
    """Mid-stream client disconnects: long streamed generations where a
    seed-chosen fraction of clients abandon the stream shortly after the
    first byte. The server must notice, cancel the rows, and release
    their KV pages promptly (serving_client_disconnects_total counts)."""
    rng = random.Random(f"disconnect_storm:{seed}")
    for i in range(n):
        dc = rng.random() < disconnect_frac
        yield TraceRequest(
            i=i, at=i / rps,
            prompt_len=prompt_len, max_new=max_new,
            seed=i, prompt_seed=rng.randrange(1 << 31),
            tenant=_tenant(rng),
            disconnect_after_ms=(
                rng.uniform(after_ms_lo, after_ms_hi) if dc else None
            ),
        )


def tenant_storm(seed: int, *, n: int = 400, noisy_frac: float = 0.85,
                 victim_rps: float = 5.0, noisy_rps: float = 50.0,
                 storm_start_s: float = 1.0, prompt_len: int = 16,
                 max_new: int = 8) -> Iterator[TraceRequest]:
    """Noisy-neighbor flood (ISSUE 19): a `victim` tenant's steady
    trickle overlaid with a `noisy` tenant's over-quota flood starting
    at `storm_start_s`. With per-tenant admission, the flood sheds as
    `tenant_quota` against the noisy tenant alone and the victim's tail
    latency stays flat; without it, the victim starves behind the
    flood's queue."""
    rng = random.Random(f"tenant_storm:{seed}")
    n_noisy = int(n * noisy_frac)
    arrivals: list[tuple[float, str]] = []
    t = 0.0
    for _ in range(n - n_noisy):
        t += rng.expovariate(victim_rps)
        arrivals.append((t, "victim"))
    t = storm_start_s
    for _ in range(n_noisy):
        t += rng.expovariate(noisy_rps)
        arrivals.append((t, "noisy"))
    arrivals.sort(key=lambda p: p[0])
    for i, (at, tenant) in enumerate(arrivals):
        yield TraceRequest(
            i=i, at=at,
            prompt_len=prompt_len, max_new=max_new,
            seed=i, prompt_seed=rng.randrange(1 << 31),
            tenant=tenant,
        )


GENERATORS = {
    "diurnal": diurnal,
    "bursts": bursts,
    "flood": flood,
    "shared_prefix": shared_prefix,
    "disconnect_storm": disconnect_storm,
    "tenant_storm": tenant_storm,
}


def generate(name: str, seed: int, **params) -> Iterator[TraceRequest]:
    """Lazy record stream for a named generator — the twin consumes a
    million-user soak without materializing a million records."""
    try:
        gen = GENERATORS[name]
    except KeyError:
        raise ValueError(
            f"unknown trace generator {name!r} "
            f"(have: {', '.join(sorted(GENERATORS))})"
        ) from None
    return gen(seed, **params)
