"""Traffic from the seed. One general generator per kind of cell, found by
the name in the cell's file (`traffic.generator`); the cell's file gives the
parameters and nothing here knows a cell.

Every seed gives the same *set* of sizes in another order where the mix
says `same_sizes` (the default for request mixes): the sizes are drawn from
the mix's own fixed seed and the run's seed shuffles them and draws the
token ids, so that no seed has more work than another.
"""

from __future__ import annotations

import math
import random

import numpy as np


def lognormal_len(rng: random.Random, median: float, sigma: float,
                  lo: int, hi: int) -> int:
    """Heavy-tailed length: lognormal around `median`, clamped. Copied from
    `polyaxon_tpu/scenarios/traces.py::_lognormal_len` (see PERF.md, Open
    questions: the original is for a later PR to delete)."""
    v = rng.lognormvariate(math.log(max(1.0, median)), sigma)
    return max(lo, min(hi, int(round(v))))


def token_batches(params: dict, vocab: int, seed: int):
    """Training batches: rows x (seq_len + 1) token ids uniform over the
    vocabulary, every row different; inputs are [:, :-1], labels [:, 1:]."""
    rows, seq = int(params["rows"]), int(params["seq_len"])
    rng = np.random.default_rng([int(seed), 0x7261696E])
    while True:
        toks = rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int32)
        yield {"inputs": toks[:, :-1].copy(), "labels": toks[:, 1:].copy()}


def request_sizes(params: dict, n: int, seed: int) -> list[tuple[int, int]]:
    """`n` (prompt_len, output_len) pairs. The set depends on the mix alone
    (`sizes_seed`), its order on the run's seed."""
    p, o = params["prompt_len"], params["output_len"]
    base = random.Random(int(params.get("sizes_seed", 20260930)))
    sizes = [
        (
            lognormal_len(base, p["median"], p["sigma"], p["min"], p["max"]),
            lognormal_len(base, o["median"], o["sigma"], o["min"], o["max"]),
        )
        for _ in range(n)
    ]
    random.Random(int(seed)).shuffle(sizes)
    return sizes


def prompt_tokens(vocab: int, length: int, seed: int, index: int) -> list[int]:
    """Token ids uniform over the vocabulary: no two prompts share a prefix."""
    rng = np.random.default_rng([int(seed), 0x70726F6D, int(index)])
    return rng.integers(0, vocab, size=length, dtype=np.int64).tolist()


def closed_loop(params: dict, vocab: int, seed: int):
    """`clients` callers, each sending its next request when the last token
    of the previous one has arrived, no think time. Yields the endless
    stream of requests; the driver hands them out in order."""
    block = int(params.get("sizes_block", 4096))
    index = 0
    while True:
        for plen, olen in request_sizes(params, block, seed + index):
            yield {
                "index": index,
                "tokens": prompt_tokens(vocab, plen, seed, index),
                "max_new": olen,
                "due_s": None,
            }
            index += 1


def open_loop(params: dict, vocab: int, seed: int):
    """Poisson arrivals at `rate` requests a second; each request carries
    the time it is due, from which it is timed. `burst` > 1 multiplies the
    rate in `burst_share` of the seconds, drawn from the seed."""
    rate = float(params["rate"])
    burst = float(params.get("burst", 1.0))
    share = float(params.get("burst_share", 0.0))
    arr = random.Random(int(seed) ^ 0x6F70656E)
    block = int(params.get("sizes_block", 4096))
    index, t = 0, 0.0
    while True:
        for plen, olen in request_sizes(params, block, seed + index):
            hot = burst > 1.0 and random.Random(
                (int(seed) << 20) ^ int(t)
            ).random() < share
            t += arr.expovariate(rate * (burst if hot else 1.0))
            yield {
                "index": index,
                "tokens": prompt_tokens(vocab, plen, seed, index),
                "max_new": olen,
                "due_s": t,
            }
            index += 1


GENERATORS = {
    "token_batches": token_batches,
    "closed_loop": closed_loop,
    "open_loop": open_loop,
}


def generator(name: str):
    if name not in GENERATORS:
        raise KeyError(f"unknown traffic generator {name!r}; known: {sorted(GENERATORS)}")
    return GENERATORS[name]
