"""Serving fast path (ISSUE 2): shape-bucketed decode + continuous batching.

Three layers, tested at three levels:
  * pure units — bucket ladders and the DecodeCoalescer worker loop with a
    fake executor (no jax);
  * model level — LEFT-padded bucketed decode must be row-for-row
    IDENTICAL to the unbucketed path, and per-row seeds must be
    reproducible and invariant to bucket width / batch composition;
  * server level — the compile cache must be bounded by the bucket ladder
    across a randomized shape sweep, which the exact-shape path is not,
    and concurrent HTTP requests must coalesce.
"""

import json
import threading
import time

import pytest

from polyaxon_tpu.serving.batching import (
    DecodeCoalescer,
    GroupKey,
    PendingRequest,
    ServingConfig,
    batch_bucket,
    bucket_for,
    bucket_ladder,
    choose_buckets,
)

pytestmark = pytest.mark.serving


# --------------------------------------------------------------- ladders
def test_bucket_ladder_geometric_capped():
    assert bucket_ladder(32, 128) == (32, 64, 128)
    assert bucket_ladder(32, 100) == (32, 64, 100)  # hi always included
    assert bucket_ladder(32, 8) == (8,)  # lo clamps down to hi
    assert bucket_ladder(1, 1) == (1,)
    with pytest.raises(ValueError):
        bucket_ladder(4, 0)


def test_bucket_for():
    assert bucket_for(1, (32, 64)) == 32
    assert bucket_for(33, (32, 64)) == 64
    assert bucket_for(65, (32, 64)) is None


def test_choose_buckets_never_overflows_cache():
    pl, nl = (32, 64), (16, 32, 64)
    assert choose_buckets(3, 5, pl, nl, 64) == (32, 16)
    # rounding both up would overflow seq_len 64: degrade prompt to exact
    assert choose_buckets(40, 10, pl, nl, 64) == (40, 16)
    # even exact prompt + bucketed new overflows: degrade new too
    assert choose_buckets(60, 4, pl, nl, 64) == (60, 4)
    for plen in range(1, 60):
        for new in range(1, 65 - plen):
            pb, nb = choose_buckets(plen, new, pl, nl, 64)
            assert pb >= plen and nb >= new
            assert pb + nb <= 64, (plen, new, pb, nb)


def test_batch_bucket_pow2_capped():
    assert [batch_bucket(n, 8) for n in (1, 2, 3, 5, 8, 9)] == [
        1, 2, 4, 8, 8, 8,
    ]
    assert batch_bucket(3, 1) == 1


# ------------------------------------------------------------- coalescer
KEY_A = GroupKey(32, 16, 0.8, 40, None)
KEY_B = GroupKey(64, 16, 0.8, 40, None)


def _req(key, plen=3, seed=0):
    return PendingRequest(
        tokens=[1] * plen, prompt_len=plen, max_new=4, seed=seed, key=key
    )


def _ok_executor(batches):
    def execute(batch):
        batches.append(batch)
        for r in batch:
            r.finish(result=list(r.tokens))

    return execute


def test_coalescer_full_batch_flushes_immediately():
    batches = []
    c = DecodeCoalescer(_ok_executor(batches), max_batch=2, max_wait_ms=5000)
    r1, r2 = _req(KEY_A, seed=1), _req(KEY_A, seed=2)
    c.start()
    t0 = time.monotonic()
    c.submit(r1)
    c.submit(r2)
    assert r1.done.wait(10) and r2.done.wait(10)
    # a full batch must NOT sit out the 5s window
    assert time.monotonic() - t0 < 2.0
    c.stop()
    assert len(batches) == 1 and batches[0] == [r1, r2]
    assert c.batches_run == 1 and c.rows_run == 2


def test_coalescer_flushes_partial_batch_on_max_wait():
    batches = []
    c = DecodeCoalescer(_ok_executor(batches), max_batch=8, max_wait_ms=50)
    r1, r2 = _req(KEY_A, seed=1), _req(KEY_A, seed=2)
    c.start()
    t0 = time.monotonic()
    c.submit(r1)
    c.submit(r2)
    assert r2.done.wait(10)
    elapsed = time.monotonic() - t0
    c.stop()
    # partial batch (2 < 8) waited for the window, then coalesced BOTH
    assert len(batches) == 1 and len(batches[0]) == 2
    assert elapsed >= 0.03, f"flushed after {elapsed * 1e3:.1f}ms, before max_wait"


def test_coalescer_groups_by_key_oldest_first():
    batches = []
    c = DecodeCoalescer(_ok_executor(batches), max_batch=8, max_wait_ms=0)
    reqs = [_req(KEY_A, seed=1), _req(KEY_B, seed=2), _req(KEY_A, seed=3)]
    for r in reqs:  # enqueue BEFORE the worker runs — deterministic drain
        c.submit(r)
    c.start()
    for r in reqs:
        assert r.done.wait(10)
    c.stop()
    assert [[r.seed for r in b] for b in batches] == [[1, 3], [2]]


def test_coalescer_scatters_executor_error_to_all_rows():
    def boom(batch):
        raise RuntimeError("device exploded")

    c = DecodeCoalescer(boom, max_batch=4, max_wait_ms=0)
    r1, r2 = _req(KEY_A), _req(KEY_A, seed=1)
    c.start()
    c.submit(r1)
    c.submit(r2)
    assert r1.done.wait(10) and r2.done.wait(10)
    c.stop()
    assert "exploded" in str(r1.error) and "exploded" in str(r2.error)
    assert r1.result is None


def test_coalescer_stop_fails_parked_requests():
    c = DecodeCoalescer(_ok_executor([]), max_batch=4, max_wait_ms=1000)
    r = _req(KEY_A)
    c.submit(r)  # worker never started — request is parked
    c.stop()
    assert r.done.is_set() and "shutting down" in str(r.error)
    with pytest.raises(RuntimeError):
        c.submit(_req(KEY_A))


# ------------------------------------------------- model-level equivalence
def _setup(**cfg_overrides):
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import build_model

    cfg = {
        "preset": "tiny", "seq_len": 64, "n_layers": 2, "dim": 64,
        "n_heads": 4, "n_kv_heads": 2, "vocab_size": 128,
    }
    cfg.update(cfg_overrides)
    b = build_model("transformer_lm", cfg)
    params = b.module.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((2, 64), jnp.int32),
        train=False,
    )["params"]
    return b.module, params


def _row(length, seed=0):
    import jax
    import jax.numpy as jnp

    return jax.random.randint(
        jax.random.PRNGKey(100 + seed), (length,), 0, 128, dtype=jnp.int32
    )


def _left_pad(rows, width):
    import numpy as np

    out = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        out[i, width - len(r):] = np.asarray(r)
    return out


@pytest.mark.parametrize(
    "scan",
    [False, pytest.param(True, marks=pytest.mark.slow)],
)
def test_bucketed_greedy_equals_unbucketed_per_length(scan):
    """The bucketing contract: LEFT-padding a row up to the bucket width
    (pad masked out of attention, positions offset) yields EXACTLY the
    unbucketed output — for every true length in the bucket, and for a
    mixed-length batch (each row independent of its neighbors)."""
    import jax.numpy as jnp
    import numpy as np

    from polyaxon_tpu.models.generate import generate

    module, params = _setup(scan_layers=scan)
    P, max_new = 8, 4
    lengths = [1, 5, 8]
    refs = {}
    for L in lengths:
        row = _row(L, seed=L)
        refs[L] = np.asarray(
            generate(
                module, params, row[None, :], max_new_tokens=max_new,
                temperature=0.0,
            )
        )[0]
        padded = jnp.asarray(_left_pad([row], P))
        out = np.asarray(
            generate(
                module, params, padded, max_new_tokens=max_new,
                temperature=0.0, prompt_lengths=jnp.asarray([L]),
            )
        )
        np.testing.assert_array_equal(out[0, P - L:], refs[L])
    # mixed batch: every row still matches its solo reference
    rows = [_row(L, seed=L) for L in lengths]
    out = np.asarray(
        generate(
            module, params, jnp.asarray(_left_pad(rows, P)),
            max_new_tokens=max_new, temperature=0.0,
            prompt_lengths=jnp.asarray(lengths),
        )
    )
    for i, L in enumerate(lengths):
        np.testing.assert_array_equal(out[i, P - L:], refs[L])


def test_per_row_seeds_reproducible_and_bucket_invariant():
    """Per-row seed contract: a [B] seed vector makes each row's sample
    stream a function of (its seed, generation index) ONLY — reproducible
    across calls, distinct across seeds, and identical regardless of
    bucket width or which rows share the batch. This is what lets the
    coalescer merge strangers' requests without changing anyone's output."""
    import jax.numpy as jnp
    import numpy as np

    from polyaxon_tpu.models.generate import generate

    module, params = _setup()
    L, max_new = 3, 4
    row = _row(L)

    def run(width, rows, lengths, seeds):
        return np.asarray(
            generate(
                module, params, jnp.asarray(_left_pad(rows, width)),
                max_new_tokens=max_new, temperature=0.8, top_k=40,
                seed=jnp.asarray(seeds, jnp.int32),
                prompt_lengths=jnp.asarray(lengths),
            )
        )

    solo = run(8, [row], [L], [7])
    again = run(8, [row], [L], [7])
    np.testing.assert_array_equal(solo, again)  # reproducible
    other = run(8, [row], [L], [8])
    assert not np.array_equal(solo, other)  # seed actually matters
    # bucket/batch invariance: same row+seed in a WIDER bucket, batched
    # with a stranger, generates the same tokens
    stranger = _row(6, seed=9)
    mixed = run(16, [row, stranger], [L, 6], [7, 11])
    np.testing.assert_array_equal(mixed[0, 16 - L:], solo[0, 8 - L:])


# ----------------------------------------------------- server compile cache
def test_compile_count_bounded_by_bucket_ladder():
    """Randomized shape sweep: the server must satisfy every request mix
    with at most |prompt ladder| x |max_new ladder| compiled programs
    (single-row direct calls — batch bucket is always 1). The exact-shape
    path (`batching=False`) builds one per distinct shape, so the same
    sweep takes it past the bucketed server's whole count."""
    import random

    from polyaxon_tpu.serving.server import ModelServer

    module, params = _setup()
    server = ModelServer(
        module, params, config=ServingConfig(max_wait_ms=0.0)
    )
    rng = random.Random(0)
    bodies = []
    for i in range(20):
        plen = rng.randint(1, 32)
        max_new = rng.randint(1, 12)
        bodies.append({
            "tokens": [[rng.randrange(128) for _ in range(plen)]],
            "maxNewTokens": max_new,
            "temperature": 0.7,
            "topK": 20,
            "seed": i,
        })
    shapes = {(len(b["tokens"][0]), b["maxNewTokens"]) for b in bodies}

    def serve(srv, body):
        out = srv.generate(body)
        assert len(out["tokens"][0]) == len(body["tokens"][0]) + body["maxNewTokens"]

    for body in bodies:
        serve(server, body)
    pl, nl = server._prompt_ladder, server._new_ladder
    bound = len(pl) * len(nl)
    assert len(shapes) > bound  # the sweep genuinely varied shapes
    assert 0 < server.compile_count <= bound, (
        f"{server.compile_count} compiles for {len(shapes)} distinct shapes "
        f"(ladder bound {bound})"
    )

    exact = ModelServer(module, params, config=ServingConfig(batching=False))
    for body in bodies:
        serve(exact, body)
        if exact.compile_count > server.compile_count:
            break
    assert exact.compile_count > server.compile_count


def test_server_batched_http_path_coalesces(tmp_home):
    """End-to-end over HTTP: concurrent same-signature requests coalesce
    into shared batches, outputs are correct per request, and /statsz
    reports the occupancy."""
    import urllib.request

    from polyaxon_tpu.serving.server import ModelServer

    module, params = _setup()
    server = ModelServer(
        module, params, config=ServingConfig(max_batch=4, max_wait_ms=200.0)
    )
    port = server.start(port=0)
    results = {}
    errors = []

    def post(i, plen):
        body = {
            "tokens": [[(i + j) % 128 for j in range(plen)]],
            "maxNewTokens": 3, "temperature": 0.5, "topK": 10, "seed": i,
        }
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=300) as r:
                results[i] = json.loads(r.read())["tokens"][0]
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    try:
        # same signature, two true lengths in one bucket → coalescable
        threads = [
            threading.Thread(target=post, args=(i, 3 + (i % 2)), daemon=True)
            for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert not errors, errors
        for i in range(4):
            assert len(results[i]) == 3 + (i % 2) + 3
        stats = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/statsz", timeout=30
            ).read()
        )
        assert stats["batching"] is True
        assert stats["requests"] == 4
        assert 1 <= stats["batches"] <= 4
        assert stats["compile_count"] >= 1
    finally:
        server.stop()
