"""The generators are functions of the seed, and every seed gets the same
set of request sizes in another order."""

import numpy as np

from cellbench import traffic

MIX = {
    "generator": "closed_loop", "clients": 4, "sizes_block": 64,
    "prompt_len": {"median": 512, "sigma": 0.8, "min": 64, "max": 2048},
    "output_len": {"median": 128, "sigma": 0.6, "min": 16, "max": 256},
}


def take(gen, n):
    return [next(gen) for _ in range(n)]


def test_token_batches_repeat_per_seed_and_rows_differ():
    p = {"rows": 4, "seq_len": 32}
    a = take(traffic.token_batches(p, 1000, 2**31 + 11), 3)
    b = take(traffic.token_batches(p, 1000, 2**31 + 11), 3)
    c = take(traffic.token_batches(p, 1000, 2**31 + 12), 3)
    for x, y in zip(a, b):
        assert np.array_equal(x["inputs"], y["inputs"]) and np.array_equal(x["labels"], y["labels"])
    assert not np.array_equal(a[0]["inputs"], c[0]["inputs"])
    assert np.array_equal(a[0]["inputs"][:, 1:], a[0]["labels"][:, :-1])
    assert len({row.tobytes() for row in a[0]["inputs"]}) == 4


def test_request_sizes_same_set_other_order():
    a = traffic.request_sizes(MIX, 64, 1)
    b = traffic.request_sizes(MIX, 64, 2**31 + 5)
    assert a != b and sorted(a) == sorted(b)
    assert all(64 <= p <= 2048 and 16 <= o <= 256 for p, o in a)
    assert a == traffic.request_sizes(MIX, 64, 1)


def test_closed_loop_repeats_and_shares_no_prefix():
    a = take(traffic.closed_loop(MIX, 32768, 7), 70)
    b = take(traffic.closed_loop(MIX, 32768, 7), 70)
    assert [r["tokens"] for r in a] == [r["tokens"] for r in b]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert len({tuple(r["tokens"][:4]) for r in a}) == 70
    assert all(r["due_s"] is None for r in a)


def test_open_loop_rate_and_due_times():
    mix = dict(MIX, generator="open_loop", rate=20.0)
    reqs = take(traffic.open_loop(mix, 32768, 3), 400)
    due = [r["due_s"] for r in reqs]
    assert due == sorted(due)
    assert 400 / due[-1] == __import__("pytest").approx(20.0, rel=0.2)
    assert due == [r["due_s"] for r in take(traffic.open_loop(mix, 32768, 3), 400)]


def test_lognormal_len_is_the_programs_sampler():
    import random

    from polyaxon_tpu.scenarios.traces import _lognormal_len

    for seed in range(5):
        assert traffic.lognormal_len(random.Random(seed), 512, 0.8, 64, 2048) == _lognormal_len(
            random.Random(seed), 512, 0.8, 64, 2048
        )
