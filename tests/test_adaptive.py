"""ISSUE 15 coverage: accept-rate-driven speculation control.

Two layers:

  * controller units — `AdaptiveSpecController` driven by fake counters:
    AIMD ramping (raise on high accept, halve on low), auto-disable at
    k_min, logical-step reprobe re-enabling, stale feedback while
    disabled, truncation-corrected vs raw rate accounting, and ctor
    validation;
  * live HTTP — a speculating server with `adaptive_draft` must flip
    `auto_disabled` under high-entropy traffic (accept → 0) and keep it
    false under copy-friendly cyclic traffic, while every response stays
    byte-identical to the plain server; the draft-model server is pinned
    byte-identical too.
"""

import json
import urllib.request

import numpy as np
import pytest

from polyaxon_tpu.serving.adaptive import AdaptiveSpecController

pytestmark = pytest.mark.serving

CFG = {
    "preset": "tiny", "seq_len": 64, "n_layers": 2, "dim": 64,
    "n_heads": 4, "n_kv_heads": 2, "vocab_size": 128,
}


# ---------------------------------------------------- controller units
def test_controller_raises_k_on_high_accept():
    c = AdaptiveSpecController(k_init=2, k_min=1, k_max=4, window=8)
    assert c.window_k() == 2
    c.observe(8, 8)  # one full window at accept 1.0
    assert c.window_k() == 3
    c.observe(8, 8)
    assert c.window_k() == 4
    c.observe(8, 8)  # capped at k_max
    assert c.window_k() == 4
    assert c.stats()["adjustments"] == 2


def test_controller_halves_k_on_low_accept():
    c = AdaptiveSpecController(k_init=8, k_min=1, k_max=8, window=10,
                               lower_at=0.3)
    c.observe(10, 1)  # rate 0.1 < lower_at
    assert c.window_k() == 4
    c.observe(10, 1)
    assert c.window_k() == 2
    # middling rate holds K steady
    c.observe(10, 4)
    assert c.window_k() == 2


def test_controller_auto_disables_only_at_k_min():
    c = AdaptiveSpecController(k_init=2, k_min=1, k_max=4, window=4,
                               lower_at=0.3, disable_at=0.1)
    c.observe(4, 0)  # rate 0 but k=2 > k_min: halve, don't disable
    assert c.window_k() == 1 and not c.auto_disabled
    c.observe(4, 0)  # rate 0 at k_min: off
    assert c.auto_disabled and c.window_k() == 0
    assert c.stats()["disables"] == 1
    assert c.stats()["effective_k"] == 0


def test_controller_window_accumulates_before_deciding():
    c = AdaptiveSpecController(k_init=1, k_min=1, k_max=4, window=16)
    c.observe(6, 0)
    c.observe(6, 0)
    assert c.window_k() == 1 and not c.auto_disabled  # 12 < window
    c.observe(6, 0)  # crosses 16: decision fires
    assert c.auto_disabled


def test_controller_reprobe_reenables_at_k_min():
    c = AdaptiveSpecController(k_init=4, k_min=1, k_max=8, window=4,
                               reprobe=10)
    c.observe(4, 0)  # 4 -> 2
    c.observe(4, 0)  # 2 -> 1
    c.observe(4, 0)  # off
    assert c.auto_disabled
    c.tick_plain(9)
    assert c.auto_disabled  # 9 < reprobe
    c.tick_plain(1)
    assert not c.auto_disabled
    assert c.window_k() == 1  # probes at k_min, not the old K
    assert c.stats()["reprobes"] == 1
    # ticks while enabled are ignored (no spurious state)
    c.tick_plain(100)
    assert not c.auto_disabled


def test_controller_ignores_stale_feedback_while_disabled():
    """In-flight spec groups finish after the disable decision; their
    counts must not flip state or pollute the next probe window."""
    c = AdaptiveSpecController(k_init=1, k_min=1, k_max=4, window=4)
    c.observe(4, 0)
    assert c.auto_disabled
    c.observe(400, 400)  # stale: lifetime totals only
    assert c.auto_disabled and c.window_k() == 0
    s = c.stats()
    assert s["accept_rate_corrected"] > 0.9  # totals did accumulate


def test_controller_raw_vs_corrected_rates():
    """The controller decides on the truncation-CORRECTED accepts;
    the raw committed count rides along for /statsz only."""
    c = AdaptiveSpecController(k_init=1, k_min=1, k_max=4, window=8,
                               raise_at=0.6)
    # judged 8/8 but only 5 committed (budget-truncated run): the
    # corrected rate (1.0) must drive K up even though raw is 0.625
    c.observe(8, 8, accepted_raw=5)
    assert c.window_k() == 2
    s = c.stats()
    assert s["accept_rate_corrected"] == 1.0
    assert s["accept_rate_raw"] == pytest.approx(0.625)


def test_controller_ctor_validation():
    with pytest.raises(ValueError, match="k_min"):
        AdaptiveSpecController(k_init=0)
    with pytest.raises(ValueError, match="k_min"):
        AdaptiveSpecController(k_init=9, k_max=8)
    with pytest.raises(ValueError, match="disable_at"):
        AdaptiveSpecController(disable_at=0.5, lower_at=0.2)


# --------------------------------------------------------- live HTTP
@pytest.fixture(scope="module")
def built():
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


def _server(built, **overrides):
    from polyaxon_tpu.serving.batching import ServingConfig
    from polyaxon_tpu.serving.server import ModelServer

    module, params = built
    cfg = ServingConfig(**{
        "max_batch": 4, "max_wait_ms": 2.0, "stream_chunk_tokens": 3,
        **overrides,
    })
    return ModelServer(module, params, model_name="tiny", config=cfg)


def _post(port, body, timeout=120):
    import http.client

    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("POST", "/generate", json.dumps(body))
    r = c.getresponse()
    out = r.read()
    c.close()
    return r.status, out


def _spec_stats(port):
    return json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/statsz", timeout=60
    ).read())["speculation"]


def _entropy_body(rows=4, plen=12, max_new=24, seed=0):
    """Random prompts, sampled at temperature 1: a random-weight model's
    GREEDY output settles into a loop the n-gram drafter copies, its
    sampled output is near-uniform over the vocabulary and repeats
    nothing."""
    rng = np.random.RandomState(seed)
    return {
        "tokens": [rng.randint(1, 128, size=plen).tolist()
                   for _ in range(rows)],
        "maxNewTokens": max_new, "temperature": 1.0, "seed": seed,
    }


def _cyclic_body(rows=4, max_new=24):
    cyc = np.tile(np.arange(1, 9, dtype=np.int32), 4).tolist()
    return {"tokens": [cyc] * rows, "maxNewTokens": max_new,
            "temperature": 0.0}


CYCLE = tuple(range(1, 9))


@pytest.fixture(scope="module")
def copy_built(built):
    """The copy-friendly regime: blocks zeroed to the residual identity,
    embed/lm_head crafted so greedy decode replays CYCLE verbatim — the
    repetitive-text workload speculation exists for."""
    import jax.numpy as jnp

    module, params = built

    def rebuild(tree):
        out = {}
        for k, v in tree.items():
            if hasattr(v, "items"):
                if k in ("o_proj", "down_proj") and "kernel" in v:
                    out[k] = {
                        n: (jnp.zeros_like(a) if n == "kernel" else a)
                        for n, a in v.items()
                    }
                else:
                    out[k] = rebuild(v)
            else:
                out[k] = v
        return out

    params = rebuild(dict(params))
    emb = np.zeros(params["embed"]["embedding"].shape, np.float32)
    head = np.zeros(params["lm_head"]["kernel"].shape, np.float32)
    p = len(CYCLE)
    for i, t in enumerate(CYCLE):
        emb[t, i] = 1.0
        head[i, CYCLE[(i + 1) % p]] = 1.0
    params["embed"]["embedding"] = jnp.asarray(
        emb, params["embed"]["embedding"].dtype
    )
    params["lm_head"]["kernel"] = jnp.asarray(
        head, params["lm_head"]["kernel"].dtype
    )
    return module, params


def test_high_entropy_traffic_flips_auto_disabled(built):
    """Random prompts and sampled outputs give the n-gram drafter nothing
    to copy: the accept rate collapses, K walks down to k_min and
    speculation turns itself off — while every response still matches the
    plain server."""
    plain = _server(built)
    pp = plain.start(port=0)
    adaptive = _server(built, speculate=True, draft_tokens=3,
                       adaptive_draft=True)
    pa = adaptive.start(port=0)
    try:
        st = _spec_stats(pa)
        assert st["adaptive"] is True
        assert st["auto_disabled"] is False
        # each request's group feeds one observe(); two decisions walk
        # K 3 -> 1 -> off (window=64 proposals per decision)
        for seed in (0, 1, 2):
            body = _entropy_body(seed=seed)
            s1, o1 = _post(pp, body)
            s2, o2 = _post(pa, body)
            assert s1 == 200 and s2 == 200, (o1, o2)
            assert json.loads(o1)["tokens"] == json.loads(o2)["tokens"]
        st = _spec_stats(pa)
        assert st["auto_disabled"] is True, st
        assert st["effective_k"] == 0
        assert st["controller"]["disables"] >= 1
        # lifetime rate, not the windowed one the decision used
        assert st["accept_rate_corrected"] < 0.3, st
        # disabled means later groups run plain — and still match
        body = _entropy_body(seed=9)
        _, o1 = _post(pp, body)
        _, o2 = _post(pa, body)
        assert json.loads(o1)["tokens"] == json.loads(o2)["tokens"]
    finally:
        plain.stop()
        adaptive.stop()


def test_cyclic_traffic_keeps_speculation_on(copy_built):
    """Copy-friendly traffic must NOT trip the kill switch: the accept
    rate stays high, the controller ramps K UP, auto_disabled stays
    false."""
    srv = _server(copy_built, speculate=True, draft_tokens=3,
                  adaptive_draft=True)
    port = srv.start(port=0)
    try:
        for _ in range(3):
            status, out = _post(port, _cyclic_body())
            assert status == 200, out
        st = _spec_stats(port)
        assert st["auto_disabled"] is False, st
        assert st["effective_k"] > 3, st  # additive raise engaged
        assert st["accept_rate_corrected"] > 0.5, st
        assert st["controller"]["disables"] == 0
    finally:
        srv.stop()


def test_draft_model_server_byte_identity(built):
    """The draft-model proposer over live HTTP: sampled and greedy
    responses are byte-identical to the plain server, and /statsz
    reports the draft topology."""
    plain = _server(built)
    pp = plain.start(port=0)
    srv = _server(built, speculate=True, draft_tokens=3,
                  draft_model=(("n_layers", 1),))
    pd = srv.start(port=0)
    try:
        rng = np.random.RandomState(0)
        shared = rng.randint(1, 100, size=16).tolist()
        body = {
            "tokens": [shared + rng.randint(1, 100, size=6).tolist()
                       for _ in range(3)],
            "maxNewTokens": 8, "temperature": 0.8, "topK": 40,
            "eosId": 5, "seed": 123,
        }
        for b in (body, dict(body, temperature=0.0)):
            s1, o1 = _post(pp, b)
            s2, o2 = _post(pd, b)
            assert s1 == 200 and s2 == 200, (o1, o2)
            assert json.loads(o1)["tokens"] == json.loads(o2)["tokens"]
        st = _spec_stats(pd)
        assert st["proposed"] > 0
        assert st["draft_model"] == {"n_layers": 1, "derived": True}, st
    finally:
        plain.stop()
        srv.stop()


def test_draft_model_composes_chunked_prefill_int8(built):
    """The acceptance stack in one pot: int8 WEIGHTS + int8 KV pool +
    chunked prefill + draft-model speculation must return exactly the
    bytes of a plain server on the same quantized model and pool,
    streamed and not."""
    common = {"kv_pool_pages": 64, "kv_page_tokens": 8,
              "quantize": "int8", "kv_quant": "int8"}
    plain = _server(built, **common)
    pp = plain.start(port=0)
    srv = _server(built, speculate=True, draft_tokens=3,
                  draft_model=(("n_layers", 1),), adaptive_draft=True,
                  chunked_prefill=True, prefill_chunk_tokens=8,
                  max_step_tokens=32, **common)
    pd = srv.start(port=0)
    try:
        rng = np.random.RandomState(1)
        shared = rng.randint(1, 100, size=16).tolist()
        prompts = [shared + rng.randint(1, 100, size=6).tolist()
                   for _ in range(3)]
        body = {"tokens": prompts, "maxNewTokens": 8, "temperature": 0.8,
                "topK": 40, "eosId": 5, "seed": 9}
        for b in (body, dict(body, temperature=0.0)):
            s1, o1 = _post(pp, b)
            s2, o2 = _post(pd, b)
            assert s1 == 200 and s2 == 200, (o1, o2)
            assert json.loads(o1)["tokens"] == json.loads(o2)["tokens"]
        # streamed == non-streamed through the speculative step lanes
        import http.client

        c = http.client.HTTPConnection("127.0.0.1", pd, timeout=120)
        c.request("POST", "/generate?stream=1", json.dumps(body))
        r = c.getresponse()
        raw = r.read().decode()
        c.close()
        assert r.status == 200, raw
        rows: dict[int, list[int]] = {}
        for line in raw.splitlines():
            if line.startswith("data: "):
                ev = json.loads(line[6:])
                if "tokens" in ev and "row" in ev:
                    rows.setdefault(ev["row"], []).extend(ev["tokens"])
        _, o2 = _post(pd, body)
        full = [prompts[i] + rows[i] for i in range(len(prompts))]
        assert full == json.loads(o2)["tokens"]
    finally:
        plain.stop()
        srv.stop()


# ------------------------------------------------------ config plumbing
def test_serving_spec_adaptive_fields_validate_and_plumb():
    from polyaxon_tpu.schemas.run_kinds import V1ServingSpec

    spec = V1ServingSpec(
        speculate=True, draftModel={"n_layers": 1}, adaptiveDraft=True,
        kvQuant="int8", kvPoolPages=64, kvPageTokens=8,
    )
    cfg = spec.to_config()
    assert cfg.draft_model == (("n_layers", 1),)
    assert cfg.adaptive_draft is True
    assert cfg.kv_quant == "int8"
    # {} means "auto": build the draft from the config's own defaults —
    # it must NOT collapse to None (= draft model off)
    auto = V1ServingSpec(speculate=True, draftModel={})
    assert auto.to_config().draft_model == ()
    # defaults stay off
    off = V1ServingSpec().to_config()
    assert off.draft_model is None
    assert off.adaptive_draft is False and off.kv_quant == "none"

    with pytest.raises(ValueError, match="speculate"):
        V1ServingSpec(draftModel={"n_layers": 1})
    with pytest.raises(ValueError, match="speculate"):
        V1ServingSpec(adaptiveDraft=True)
    with pytest.raises(ValueError, match="kvPoolPages"):
        V1ServingSpec(kvQuant="int8")


def test_serve_replica_argv_layers_adaptive_flags():
    """One replica flag must not drop the others: the child argv carries
    exactly the adaptive/draft/kv-quant pins the parent was given."""
    from polyaxon_tpu.cli.main import _serve_child_argv

    argv = _serve_child_argv(
        "uid", 9000, None,
        {"draft_model": (("n_layers", 1),), "adaptive_draft": True,
         "kv_quant": "int8"},
        None,
    )
    assert "--adaptive-draft" in argv
    assert argv[argv.index("--draft-model") + 1] == "n_layers=1"
    assert argv[argv.index("--kv-quant") + 1] == "int8"
    # the "auto" draft (empty overrides) serializes as --draft-model auto
    argv_auto = _serve_child_argv("uid", 9000, None,
                                  {"draft_model": ()}, None)
    assert argv_auto[argv_auto.index("--draft-model") + 1] == "auto"
    # flags not given do not appear (and so cannot reset spec pins)
    argv_off = _serve_child_argv("uid", 9000, None, {}, None)
    for flag in ("--draft-model", "--adaptive-draft", "--kv-quant"):
        assert flag not in argv_off


def test_server_rejects_combos_the_spec_would(built):
    """CLI overrides bypass V1ServingSpec, so the server itself must
    refuse the same invalid combos — a silently ignored kv_quant would
    have the operator capacity-planning on memory they don't have."""
    with pytest.raises(ValueError, match="kv_pool_pages"):
        _server(built, kv_quant="int8")
    with pytest.raises(ValueError, match="speculate"):
        _server(built, adaptive_draft=True)
    with pytest.raises(ValueError, match="speculate"):
        _server(built, draft_model=(("n_layers", 1),))
