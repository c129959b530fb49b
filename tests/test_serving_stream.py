"""Serving-path coverage for the paged KV cache + streamed decode
(ISSUE 6), over live HTTP against tiny models:

  * dense vs paged byte-identity end to end (`POST /generate`);
  * cross-request prefix reuse: a warm re-post hits the prefix cache and
    returns identical tokens;
  * `POST /generate?stream=1` SSE: prompt + concatenated chunks equals
    the non-streamed result, delivered incrementally;
  * TTFT / page-pool / prefix-cache series on /metricsz;
  * pool exhaustion sheds 503 with reason "kv_pages" through the PR 5
    admission path without crashing the worker, and never-fits is a 400;
  * no leaked pages or reservations once traffic drains.
"""

import http.client
import json
import threading
import urllib.request

import numpy as np
import pytest

pytestmark = pytest.mark.serving

CFG = {
    "preset": "tiny", "seq_len": 64, "n_layers": 2, "dim": 64,
    "n_heads": 4, "n_kv_heads": 2, "vocab_size": 128,
}


def _build():
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import build_model

    b = build_model("transformer_lm", CFG)
    params = b.module.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, 8), jnp.int32),
        train=False,
    )["params"]
    return b.module, params


def _server(module, params, **overrides):
    from polyaxon_tpu.serving.batching import ServingConfig
    from polyaxon_tpu.serving.server import ModelServer

    cfg = ServingConfig(**{
        "max_batch": 4, "max_wait_ms": 2.0, "kv_page_tokens": 8,
        "stream_chunk_tokens": 3, **overrides,
    })
    return ModelServer(module, params, model_name="tiny", config=cfg)


@pytest.fixture(scope="module")
def servers():
    module, params = _build()
    dense = _server(module, params)
    paged = _server(module, params, kv_pool_pages=64)
    pd, pp = dense.start(port=0), paged.start(port=0)
    yield {"dense": pd, "paged": pp, "module": module, "params": params}
    dense.stop()
    paged.stop()


def _post(port, body, path="/generate", timeout=120):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("POST", path, json.dumps(body))
    r = c.getresponse()
    out = r.read()
    c.close()
    return r.status, out


def _get(port, path):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=60
    ).read()


def _body(n_rows=3, prefix=16, suffix=6, max_new=10, seed=123):
    rng = np.random.RandomState(0)
    shared = rng.randint(1, 100, size=prefix).tolist()
    prompts = [
        shared + rng.randint(1, 100, size=suffix).tolist()
        for _ in range(n_rows)
    ]
    return prompts, {
        "tokens": prompts, "maxNewTokens": max_new, "temperature": 0.8,
        "topK": 40, "eosId": 5, "seed": seed,
    }


def test_paged_matches_dense_over_http(servers):
    _, body = _body()
    s1, o1 = _post(servers["dense"], body)
    s2, o2 = _post(servers["paged"], body)
    assert s1 == 200 and s2 == 200, (s1, s2, o1, o2)
    assert json.loads(o1)["tokens"] == json.loads(o2)["tokens"]
    # single-token decode exercises the prefill-only path
    one = dict(body, tokens=body["tokens"][:1], maxNewTokens=1)
    _, oa = _post(servers["dense"], one)
    _, ob = _post(servers["paged"], one)
    assert json.loads(oa)["tokens"] == json.loads(ob)["tokens"]


def test_warm_prefix_hits_and_identical_tokens(servers):
    _, body = _body(seed=321)
    s1, o1 = _post(servers["paged"], body)
    assert s1 == 200, o1
    st0 = json.loads(_get(servers["paged"], "/statsz"))["kv"]
    s2, o2 = _post(servers["paged"], body)
    assert s2 == 200 and json.loads(o2)["tokens"] == json.loads(o1)["tokens"]
    st1 = json.loads(_get(servers["paged"], "/statsz"))["kv"]
    assert st1["enabled"]
    assert st1["prefix"]["hits"] > st0["prefix"]["hits"]


def test_streamed_equals_non_streamed(servers):
    prompts, body = _body(seed=77)
    _, o = _post(servers["paged"], body)
    full = json.loads(o)["tokens"]

    c = http.client.HTTPConnection("127.0.0.1", servers["paged"], timeout=120)
    c.request("POST", "/generate?stream=1", json.dumps(body))
    r = c.getresponse()
    assert r.status == 200
    assert r.getheader("Content-Type") == "text/event-stream"
    chunks = {i: [] for i in range(len(prompts))}
    events, buf = [], b""
    while True:
        data = r.read(64)
        if not data:
            break
        buf += data
        while b"\n\n" in buf:
            frame, buf = buf.split(b"\n\n", 1)
            ev = json.loads(frame[len(b"data: "):])
            events.append(ev)
            if "row" in ev and "tokens" in ev:
                chunks[ev["row"]].extend(ev["tokens"])
    c.close()
    # every frame carries the request id (ISSUE 9) alongside the
    # terminal done marker
    done = events[-1]
    assert done["done"] is True and done["requestId"]
    assert all(
        ev["requestId"] == done["requestId"] for ev in events
    )
    assert not any("error" in ev for ev in events), events
    for i, p in enumerate(prompts):
        assert p + chunks[i] == full[i], (i, chunks[i], full[i])
    # incremental delivery: 10 new tokens at chunk size 3 means several
    # tokens-events per row, not one terminal blob
    assert sum(1 for e in events if e.get("row") == 0 and "tokens" in e) >= 3


def test_metricsz_exports_kv_series(servers):
    _, body = _body(seed=55)
    assert _post(servers["paged"], body)[0] == 200
    m = _get(servers["paged"], "/metricsz").decode()
    for series in (
        "serving_kv_pages_total",
        "serving_kv_pages_used",
        "serving_prefix_cache_hits_total",
        "serving_prefix_cache_misses_total",
        "serving_ttft_ms",
    ):
        assert series in m, f"missing {series} on /metricsz"
    st = json.loads(_get(servers["paged"], "/statsz"))["kv"]
    assert st["pages_total"] == 64
    assert st["ttft_ms"]["p50"] is not None  # TTFT actually observed


def test_no_leaked_pages_after_traffic(servers):
    _, body = _body(seed=99)
    assert _post(servers["paged"], body)[0] == 200
    st = json.loads(_get(servers["paged"], "/statsz"))["kv"]
    assert st["active_rows"] == 0
    assert st["pages_reserved"] == 0
    # prefix entries may hold pages; only the scratch page is otherwise live
    assert st["pages_used"] >= 1


def test_speculative_servers_byte_identical_over_http(servers):
    """ISSUE 8: ServingConfig(speculate=True) must be invisible in the
    payload — dense AND paged speculative servers return exactly the
    baseline servers' tokens, streamed and not, including a warm re-post
    whose shared prefix was prefilled by the earlier request."""
    module, params = servers["module"], servers["params"]
    spec_d = _server(module, params, speculate=True, draft_tokens=4)
    spec_p = _server(module, params, kv_pool_pages=64, speculate=True,
                     draft_tokens=4)
    pd, pp = spec_d.start(port=0), spec_p.start(port=0)
    try:
        prompts, body = _body(seed=888)
        s1, o1 = _post(servers["dense"], body)
        s2, o2 = _post(pd, body)
        assert s1 == 200 and s2 == 200, (s1, s2, o1, o2)
        assert json.loads(o1)["tokens"] == json.loads(o2)["tokens"]
        s3, o3 = _post(servers["paged"], body)
        s4, o4 = _post(pp, body)
        assert s3 == 200 and s4 == 200, (s3, s4, o3, o4)
        full = json.loads(o4)["tokens"]
        assert json.loads(o3)["tokens"] == full

        # warm re-post: the shared prefix was prefilled (and harvested)
        # by the request above — hit rate grows, tokens stay identical
        st0 = json.loads(_get(pp, "/statsz"))["kv"]
        s5, o5 = _post(pp, body)
        assert s5 == 200 and json.loads(o5)["tokens"] == full
        st1 = json.loads(_get(pp, "/statsz"))["kv"]
        assert st1["prefix"]["hits"] > st0["prefix"]["hits"]

        # streamed speculative decode delivers the same tokens in chunks
        c = http.client.HTTPConnection("127.0.0.1", pp, timeout=120)
        c.request("POST", "/generate?stream=1", json.dumps(body))
        r = c.getresponse()
        assert r.status == 200
        chunks = {i: [] for i in range(len(prompts))}
        buf, events = b"", []
        while True:
            data = r.read(64)
            if not data:
                break
            buf += data
            while b"\n\n" in buf:
                frame, buf = buf.split(b"\n\n", 1)
                ev = json.loads(frame[len(b"data: "):])
                events.append(ev)
                if "row" in ev and "tokens" in ev:
                    chunks[ev["row"]].extend(ev["tokens"])
        c.close()
        done = events[-1]
        assert done["done"] is True and done["requestId"]
        assert not any("error" in ev for ev in events), events
        for i, p in enumerate(prompts):
            assert p + chunks[i] == full[i], (i, chunks[i], full[i])

        # greedy too (the high-acceptance regime)
        g = dict(body, temperature=0.0)
        _, og = _post(servers["paged"], g)
        _, ogs = _post(pp, g)
        assert json.loads(og)["tokens"] == json.loads(ogs)["tokens"]

        # the new observability surface: counters on /metricsz, the
        # speculation block (with actual proposals) on /statsz
        m = _get(pp, "/metricsz").decode()
        for series in (
            "serving_spec_proposed_total",
            "serving_spec_accepted_total",
            "serving_spec_rollback_total",
            "serving_quant_bytes_saved",
        ):
            assert series in m, f"missing {series} on /metricsz"
        sp = json.loads(_get(pp, "/statsz"))["speculation"]
        assert sp["enabled"] and sp["draft_tokens"] == 4
        assert sp["proposed"] > 0 and sp["accept_rate"] is not None

        # no leaked pages once speculative traffic drains
        st = json.loads(_get(pp, "/statsz"))["kv"]
        assert st["active_rows"] == 0 and st["pages_reserved"] == 0
    finally:
        spec_d.stop()
        spec_p.stop()


def test_quantized_server_serves_and_reports_footprint(servers):
    """ISSUE 8: quantize-on-load — the server quantizes the fp params in
    __init__, serves greedy traffic, and reports the saved bytes on both
    /statsz and /metricsz."""
    module, params = servers["module"], servers["params"]
    q = _server(module, params, quantize=True)
    port = q.start(port=0)
    try:
        _, body = _body(seed=999)
        st, o = _post(port, dict(body, temperature=0.0))
        assert st == 200, o
        toks = json.loads(o)["tokens"]
        assert all(
            len(t) == len(p) + body["maxNewTokens"]
            for t, p in zip(toks, body["tokens"])
        )
        stats = json.loads(_get(port, "/statsz"))["quant"]
        assert stats["enabled"] and stats["bytes_saved"] > 0
        m = _get(port, "/metricsz").decode()
        assert "serving_quant_bytes_saved" in m
    finally:
        q.stop()


def test_pool_exhaustion_sheds_503_without_crashing():
    module, params = _build()
    # pool 4 = scratch + 3 usable; an 8-token prompt + 4 new reserves 2
    # pages, so two concurrent requests oversubscribe the pool
    srv = _server(
        module, params, max_batch=1, max_wait_ms=150.0, kv_pool_pages=4,
        prompt_buckets=(8,), max_new_buckets=(4,), prefix_cache=False,
    )
    port = srv.start(port=0)
    try:
        ok = {
            "tokens": [list(range(1, 9))], "maxNewTokens": 4,
            "temperature": 0.0,
        }
        assert _post(port, ok)[0] == 200
        res = [None, None]

        def go(i):
            res[i] = _post(port, ok)

        ts = [threading.Thread(target=go, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert sorted(r[0] for r in res) == [200, 503], res
        shed = json.loads([r for r in res if r[0] == 503][0][1])
        assert shed["reason"] == "kv_pages", shed
        # a request that could NEVER fit the pool is a client error, not
        # a shed
        big = {
            "tokens": [list(range(1, 40))], "maxNewTokens": 16,
            "temperature": 0.0,
        }
        assert _post(port, big)[0] == 400
        # worker survived both: same request serves again
        assert _post(port, ok)[0] == 200
        st = json.loads(_get(port, "/statsz"))["kv"]
        assert st["active_rows"] == 0 and st["pages_reserved"] == 0
    finally:
        srv.stop()
