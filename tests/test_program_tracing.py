"""ISSUE 27: the program names its own work on the device trace's clock
(and, ISSUE 38, tells its time to the first step: the finished-interval
record, compiled or loaded, the process's age, the first log point).

  * a real profiler capture of a tiny Trainer and a tiny step-engine
    server: every span the program opens is a `polyaxon.*` event on the
    host plane, nested as in the ring, with the ring's durations, around
    XLA's own events of the same capture (one clock);
  * the three flash-attention kernels and every jitted serving program
    have names of their own;
  * `/statsz` `chunked.phase_s` and `xla` count what they say;
  * `spans.jsonl` is written in batches and holds every span after
    `close()`;
  * the Trainer's MFU gauge counts required work as the benchmark does.
"""

import json
import re

import pytest

from polyaxon_tpu.telemetry import SpanTracer

from test_serving_chunked import CHUNKED, _body, _build, _post, _server, _stats
from test_telemetry import _mlp_program

pytestmark = pytest.mark.telemetry

TRAIN_SPANS = ("step", "data_wait", "compute", "dispatch", "emit")
STEP_SPANS = ("sched.intake", "step.prepare", "step.dispatch", "step.fetch", "step.emit")
# written once they are over (ISSUE 38): in the ring and `spans.jsonl`, and
# no annotations, since none can be opened in the past
AFTER_THE_FACT = {"build", "init", "first_step", "rung", "lower", "compile"}


def _opened(ring):
    """The ring's spans that a `with tracer.span(...)` opened."""
    return [r for r in ring if r["kind"] == "span" and r["name"] not in AFTER_THE_FACT]


# ------------------------------------------------------------ real capture
@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """Two steps of a tiny Trainer and four requests through a tiny
    step-engine server inside one profiler session."""
    import jax

    from cellbench import trace_reduce
    from polyaxon_tpu.runtime.trainer import Trainer

    out = tmp_path_factory.mktemp("capture")
    trainer = Trainer(
        _mlp_program(steps=2, logEvery=1),
        mesh_axes={"data": 1},
        devices=jax.devices()[:1],
    )
    module, params = _build()
    server = _server(module, params, **CHUNKED)
    port = server.start(port=0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        trainer.run()
        for seed in range(4):
            status, _ = _post(port, dict(_body(seed=seed)[1], stream=False))
            assert status == 200
    finally:
        jax.profiler.stop_trace()
        server.stop()
        trainer.close()
    trace = trace_reduce.read_xplane(
        trace_reduce.find_xplane(str(out)),
        host_prefixes=("polyaxon.", "PjitFunction("),
    )
    return {
        "host": trace["host"],
        "train": trainer.tracer.recent(512),
        "serve": server.spans.recent(4096),
    }


def _events(host, name):
    return sorted((s, s + d) for n, s, d in host if n == name)


def test_every_span_is_an_annotation_in_the_capture(capture):
    names = {n for n, _, _ in capture["host"]}
    for what in TRAIN_SPANS:
        assert f"polyaxon.train.{what}" in names
    for what in STEP_SPANS:
        assert f"polyaxon.{what}" in names
    # one event a span: the ring and the capture count alike
    for ring, prefix in ((capture["train"], "polyaxon.train."), (capture["serve"], "polyaxon.")):
        for what in {r["name"] for r in _opened(ring)}:
            in_ring = sum(1 for r in _opened(ring) if r["name"] == what)
            assert len(_events(capture["host"], prefix + what)) == in_ring, what
    # the Trainer's record of its own set-up is in its ring all the same
    assert {"build", "init"} <= {r["name"] for r in capture["train"] if r["kind"] == "span"}
    assert not _events(capture["host"], "polyaxon.train.build")


def test_children_lie_inside_parents_in_the_capture(capture):
    host = capture["host"]

    def inside(child, parent):
        outer = _events(host, parent)
        for s, e in _events(host, child):
            assert any(ps <= s and e <= pe for ps, pe in outer), (child, parent)

    inside("polyaxon.train.data_wait", "polyaxon.train.step")
    inside("polyaxon.train.compute", "polyaxon.train.step")
    inside("polyaxon.train.dispatch", "polyaxon.train.compute")
    inside("polyaxon.train.emit", "polyaxon.train.compute")
    # and as the ring has it: a child's parent_id is its enclosing span
    by_id = {r["span_id"]: r for r in capture["train"]}
    for r in capture["train"]:
        if r["name"] in ("dispatch", "emit"):
            assert by_id[r["parent_id"]]["name"] == "compute"
        if r["name"] in ("data_wait", "compute"):
            assert by_id[r["parent_id"]]["name"] == "step"


def test_spans_share_the_clock_of_xlas_own_events(capture):
    """XLA's own host events of the same capture lie inside the program's
    dispatch spans: the call of the train step inside
    `polyaxon.train.dispatch`, a decode step's inside
    `polyaxon.step.dispatch`."""
    host = capture["host"]
    for xla, span in (
        ("PjitFunction(step_fn)", "polyaxon.train.dispatch"),
        ("PjitFunction(decode_step)", "polyaxon.step.dispatch"),
        ("PjitFunction(prefill_slice)", "polyaxon.step.dispatch"),
    ):
        calls, outer = _events(host, xla), _events(host, span)
        assert calls, xla
        for s, e in calls:
            assert any(ps <= s and e <= pe for ps, pe in outer), (xla, span)


def test_ring_and_capture_agree_on_durations(capture):
    host = capture["host"]
    pairs = []
    for ring, prefix in ((capture["train"], "polyaxon.train."), (capture["serve"], "polyaxon.")):
        spans = _opened(ring)
        for what in {r["name"] for r in spans}:
            mine = [r["dur_s"] for r in spans if r["name"] == what]  # in start order per name
            theirs = [(e - s) * 1e-9 for s, e in _events(host, prefix + what)]
            assert len(mine) == len(theirs)
            # nested spans of one name close in another order than they
            # open: compare as sorted multisets
            pairs += [
                (what, a, b) for a, b in zip(sorted(mine), sorted(theirs)) if a >= 1e-3
            ]
    assert len(pairs) >= 5
    # the span reads its clock inside the annotation, a few instructions
    # apart: a thread switch in between (this suite runs six workers wide)
    # puts one span off by the switch, never many and never the sum
    off = [p for p in pairs if abs(p[1] - p[2]) > 0.05 * p[1]]
    assert len(off) <= max(1, len(pairs) // 10), off
    ring_s, capture_s = sum(p[1] for p in pairs), sum(p[2] for p in pairs)
    assert abs(ring_s - capture_s) <= 0.05 * ring_s
    for _, a, b in pairs:
        assert abs(a - b) <= 0.05 * a + 2e-3


# ------------------------------------------------------------------- names
def test_flash_attention_grad_holds_three_named_kernels():
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 16, 2, 8), jnp.float32)
    kv = jnp.zeros((1, 16, 1, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda q, k, v: flash_attention(q, k, v).sum(), argnums=(0, 1, 2))
    )(q, kv, kv)
    def pallas_calls(jaxpr):
        # each kernel sits in a jit of its own (one lowering for all layers)
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            elif "jaxpr" in eqn.params:
                yield from pallas_calls(eqn.params["jaxpr"].jaxpr)

    assert sorted(pallas_calls(jaxpr.jaxpr)) == [
        "flash_attention_dkv", "flash_attention_dq", "flash_attention_fwd",
    ]


def test_serving_programs_have_names_of_their_own():
    """Every builder of a jitted serving program names it: no `run`, no
    lambda, no name twice."""
    import importlib
    from types import SimpleNamespace

    # `polyaxon_tpu.models.generate` the attribute is the function
    generate, spec_decode, draft = (
        importlib.import_module(f"polyaxon_tpu.models.{m}")
        for m in ("generate", "spec_decode", "draft")
    )
    from polyaxon_tpu.serving.batching import ServingConfig
    from polyaxon_tpu.serving.kv import KVCacheManager
    from polyaxon_tpu.serving.server import ModelServer

    module, params = _build()
    layout = SimpleNamespace()
    common = dict(temperature=0.0, top_k=None)
    built = [
        generate.jit_paged_prefill(module, kv_layout=layout, prefix_len=0, **common),
        generate.jit_paged_chunk(module, steps=2, kv_layout=layout, prefix_len=0, eos_id=None, **common),
        generate.jit_paged_prefill_chunk(module, kv_layout=layout, final=False),
        generate.jit_paged_prefill_chunk(module, kv_layout=layout, final=True),
        generate.jit_paged_step(module, kv_layout=layout, eos_id=None, **common),
        spec_decode.jit_spec_prefill(module, **common),
        spec_decode.jit_spec_verify(module, eos_id=None, **common),
        spec_decode.jit_spec_verify_paged(module, kv_layout=layout, prefix_len=0, eos_id=None, **common),
        draft.jit_draft_prefill(module),
        draft.jit_draft_propose(module, steps=2, **common),
    ]
    server = ModelServer(
        module, params, model_name="tiny",
        config=ServingConfig(max_batch=2, kv_pool_pages=16, kv_page_tokens=8),
    )
    kv = server._kv
    assert isinstance(kv, KVCacheManager)
    built += [kv._harvest_fn(1, 1), kv._restore_fn(1)]
    with server._lock:
        built += [
            server._decode_fn(1, 8, 4, 0.0, None, None),
            server._decode_fn(1, 8, 4, 0.0, None, None, num_beams=2),
            server._bucketed_fn(1, 8, 4, 0.0, None, None),
        ]
    names = [fn.__name__ for fn in built]
    assert all(re.fullmatch(r"[a-z][a-z0-9_]+", n) for n in names), names
    assert "run" not in names and len(set(names)) == len(names), names
    assert {"decode_step", "prefill_slice", "prefill_slice_final", "kv_harvest",
            "kv_restore", "spec_draft", "spec_verify"} <= set(names)
    # and the name reaches the program XLA compiles
    import jax.numpy as jnp

    text = built[-1].lower(
        params, jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32),
        jnp.zeros((1,), jnp.int32),
    ).as_text()
    assert "module @jit_generate_bucketed" in text


# ----------------------------------------------------------------- /statsz
def test_statsz_phase_seconds_and_xla_programs():
    from polyaxon_tpu.telemetry import now

    module, params = _build()
    server = _server(module, params, **CHUNKED)
    port = server.start(port=0)
    try:
        t0 = now()
        first = _stats(port)
        assert set(first["chunked"]["phase_s"]) == {
            "intake", "prepare", "dispatch", "fetch", "emit",
        }
        assert set(first["xla"]) >= {
            "programs", "traces", "lowerings", "program_seconds",
            "trace_seconds", "lowering_seconds",
        }
        body = dict(_body(n_rows=1, max_new=6)[1], stream=False)
        seen = [first]
        for _ in range(3):
            assert _post(port, body)[0] == 200
            seen.append(_stats(port))
        window = now() - t0
        for a, b in zip(seen, seen[1:]):
            assert b["chunked"]["steps"] > a["chunked"]["steps"]
            for k, v in b["chunked"]["phase_s"].items():
                assert v >= a["chunked"]["phase_s"][k]
        spent = sum(seen[-1]["chunked"]["phase_s"].values()) - sum(
            first["chunked"]["phase_s"].values()
        )
        assert 0 < spent <= window
        # the first request built this shape's programs, the repeats none
        assert seen[1]["xla"]["programs"] > first["xla"]["programs"]
        assert seen[3]["xla"]["programs"] == seen[2]["xla"]["programs"]
        # a decode lane of two rows is a new bucket of the decode step:
        # exactly one program more, and none when it comes again
        two = dict(_body(n_rows=2, max_new=6)[1], stream=False)
        before = _stats(port)["xla"]["programs"]
        assert _post(port, two)[0] == 200
        after = _stats(port)
        grew = after["xla"]["programs"] - before
        assert _post(port, two)[0] == 200
        assert _stats(port)["xla"]["programs"] == after["xla"]["programs"]
        assert grew == 1
        assert after["xla"]["recent"][-1]["program"] == "jit(decode_step)"
        spans = [r for r in server.spans.recent(4096) if r["attrs"].get("compiled")]
        assert any(
            p["program"] == "jit(decode_step)"
            for r in spans for p in r["attrs"]["programs"]
        )
        # a program is built where it is first called: a lane's or a
        # slice's in dispatch, the prefix cache's harvest in emit
        assert {r["name"] for r in spans} <= {"step.dispatch", "step.emit"}
        for r in spans:
            if r["name"] == "step.emit":
                assert {p["program"] for p in r["attrs"]["programs"]} == {"jit(kv_harvest)"}
    finally:
        server.stop()


def test_a_phase_span_names_only_its_own_threads_programs():
    """`xla.programs` is the process's; `compiled` on a span is its
    thread's: a program another thread builds while the span is open
    stays off it."""
    import threading

    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.serving.server import ModelServer
    from polyaxon_tpu.telemetry import compiles

    module, params = _build()
    server = ModelServer(module, params, model_name="tiny")

    def build(name):
        fn = lambda x: x * 3 + 1  # noqa: E731
        fn.__name__ = name
        jax.jit(fn)(jnp.ones(3)).block_until_ready()

    with server._phase("dispatch"):
        other = threading.Thread(target=build, args=("built_elsewhere",))
        other.start()
        other.join()
    with server._phase("dispatch"):
        build("built_here")
    first, second = server.spans.recent(2)
    assert "compiled" not in first["attrs"]
    assert second["attrs"]["compiled"] is True
    assert [p["program"] for p in second["attrs"]["programs"]] == ["jit(built_here)"]
    both = {p["program"] for p in compiles.recent(64)}
    assert {"jit(built_elsewhere)", "jit(built_here)"} <= both


def test_xla_counts_in_the_process_global_registry():
    """A component handed the process-global registry finds the `xla.*`
    counters there already and does not register gauges over them."""
    from polyaxon_tpu.telemetry import MetricsRegistry, compiles, get_registry

    compiles.install()
    mirrored = compiles.mirror(get_registry())  # must not raise
    assert mirrored["programs"] == get_registry().snapshot()["xla.programs"]
    own = MetricsRegistry()
    assert compiles.mirror(own)["programs"] == own.snapshot()["xla.programs"]


def test_trainer_logs_xla_counts_where_they_moved():
    """The first log point says what the step's trace, lowering and
    compile (or cache load) took; a log point since which nothing was
    built does not repeat the process's totals."""
    import jax

    from polyaxon_tpu.runtime.trainer import Trainer

    trainer = Trainer(
        _mlp_program(steps=8, logEvery=2), mesh_axes={"data": 1},
        devices=jax.devices()[:1],
    )
    try:
        history = trainer.run().history
    finally:
        trainer.close()
    assert len(history) == 4
    assert history[0]["xla_programs"] >= 1 and history[0]["xla_program_seconds"] > 0
    assert not any(k.startswith("xla_") for k in history[-1])
    snap = trainer.telemetry.snapshot()
    assert snap["xla.programs"] >= history[0]["xla_programs"]


def test_trainer_logs_the_finished_set_up_once():
    """The first log point carries `startup_*` beside `xla_*` (ISSUE 38); no
    later one repeats them, and they are no `train.*` gauges."""
    import jax

    from polyaxon_tpu.runtime.trainer import Trainer

    events = []
    trainer = Trainer(
        _mlp_program(steps=6, logEvery=2), mesh_axes={"data": 1},
        devices=jax.devices()[:1], event_fn=lambda k, b: events.append((k, b)),
    )
    try:
        history = trainer.run().history
    finally:
        trainer.close()
    ((_, record),) = [e for e in events if e[0] == "startup"]
    logged = {k: v for k, v in history[0].items() if k.startswith("startup_")}
    assert logged == {
        f"startup_{k}": float(v) for k, v in record.items() if isinstance(v, (int, float))
    }
    assert {"startup_build_seconds", "startup_init_seconds"} <= set(logged)
    assert "xla_programs" in history[0]
    assert not any(k.startswith("startup_") for h in history[1:] for k in h)
    assert not any("startup_" in k for k in trainer.telemetry.snapshot())


# ------------------------------------------------------- compiled or loaded
def _cache_counts():
    from polyaxon_tpu.telemetry import compiles

    snap = compiles.snapshot()
    return {k: snap[k] for k in (
        "cache_hits", "cache_misses", "cache_retrieval_seconds", "compile_seconds_saved")}


@pytest.mark.parametrize("how", ["backend", "sent"])
def test_cache_hits_and_misses_reach_counters_events_and_answers(how, tmp_path):
    """Compiled, or loaded from the persistent cache: `xla.cache_hits` /
    `xla.cache_misses` and the two durations count JAX's own events, the
    `polyaxon.compile` event of a program and the answer of a rung say
    `hit`, `miss` or `off`. `backend`: a real compile cache directory, the
    same step built again after `jax.clear_caches()` (where the CPU backend
    does not cache, JAX's events are sent as in `sent`). `sent`: the events
    alone, through `jax.monitoring`."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from polyaxon_tpu.runtime.trainer import _RematLadder
    from polyaxon_tpu.telemetry import MetricsRegistry, compiles, get_registry

    compiles.install()

    def step_fn(state, batch):
        return state + jnp.tanh(batch @ batch.T).sum(), {"loss": batch.mean()}

    def build():
        """The step lowered and compiled as a rung is; what its answer and
        the program's `polyaxon.compile` event say of the cache."""
        jax.clear_caches()
        ladder = _RematLadder({"all": jax.jit(step_fn)}, lambda choice: None)
        answer, _ = ladder.attempt("all", jnp.zeros(()), jnp.ones((8, 8)))
        return answer["cache"], compiles.recent(1, mine=True)[0]

    def send(hit):
        record = jax.monitoring.record_event
        record("/jax/compilation_cache/compile_requests_use_cache")
        if hit:
            record("/jax/compilation_cache/cache_hits")
            jax.monitoring.record_event_duration_secs(
                "/jax/compilation_cache/compile_time_saved_sec", 2.5)
            jax.monitoring.record_event_duration_secs(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        else:
            record("/jax/compilation_cache/cache_misses")

    assert build()[0] == "off"  # no cache directory: asked or not, `off`
    keep = {
        k: getattr(jax.config, k) for k in (
            "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    }
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        start, mine = _cache_counts(), compiles.own()
        backend_caches = False
        if how == "backend":
            cold, cold_event = build()
            after_cold = _cache_counts()
            warm, warm_event = build()
            backend_caches = _cache_counts()["cache_hits"] > after_cold["cache_hits"]
        if not backend_caches:
            send(hit=False)
            cold = compiles.cache_since(mine)
            after_cold, mine = _cache_counts(), compiles.own()
            send(hit=True)
            warm = compiles.cache_since(mine)
        else:
            assert cold_event["program"] == warm_event["program"] == "jit(step_fn)"
            assert (cold_event["cache"], warm_event["cache"]) == ("miss", "hit")
        end = _cache_counts()
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert (cold, warm) == ("miss", "hit")
    assert after_cold["cache_misses"] > start["cache_misses"]
    assert after_cold["cache_hits"] == start["cache_hits"]
    assert end["cache_hits"] > after_cold["cache_hits"]
    assert end["cache_retrieval_seconds"] > after_cold["cache_retrieval_seconds"]
    assert end["compile_seconds_saved"] >= after_cold["compile_seconds_saved"]
    if how == "sent":
        assert end["cache_hits"] - start["cache_hits"] == 1
        assert end["compile_seconds_saved"] - start["compile_seconds_saved"] == pytest.approx(2.5)
        assert compiles.own()["cache_retrieval_seconds"] - mine.get(
            "cache_retrieval_seconds", 0) == pytest.approx(0.25)
    # the process's registry holds the counters, a component's own mirrors them
    snap = get_registry().snapshot()
    assert all(snap[f"xla.{k}"] == pytest.approx(v, abs=1e-5) for k, v in end.items())
    own = MetricsRegistry()
    compiles.mirror(own)
    assert {f"xla.{k}" for k in end} <= set(own.snapshot())


# ------------------------------------------------------------- spans.jsonl
def test_spans_jsonl_is_written_in_batches_and_whole_after_close(tmp_path):
    from polyaxon_tpu.telemetry.spans import _BATCH

    path = tmp_path / "t" / "spans.jsonl"
    tr = SpanTracer(path=str(path))
    for i in range(_BATCH - 1):
        with tr.span("s", i=i):
            pass
    assert not path.exists()  # nothing per span
    with tr.span("s", i=_BATCH - 1):
        pass
    assert len(path.read_text().splitlines()) == _BATCH  # the batch filled
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        tr.event("mark")
    assert len(path.read_text().splitlines()) == _BATCH
    tr.close()
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["name"] for r in recs] == ["s"] * _BATCH + ["inner", "mark", "outer"]
    assert recs == tr.recent(_BATCH + 3)
    tr.close()  # nothing twice
    assert len(path.read_text().splitlines()) == _BATCH + 3

    blocked = tmp_path / "file"
    blocked.write_text("")
    bad = SpanTracer(path=str(blocked / "spans.jsonl"))
    for _ in range(5):
        with bad.span("s"):
            pass
    bad.close()  # must not raise
    assert bad._broken and len(bad.recent()) == 5


def test_a_finished_interval_is_the_record_a_span_writes(tmp_path):
    """`record_span` writes what `span()` writes at its exit (same keys), to
    the ring and to `spans.jsonl`, from a start and a duration read
    elsewhere; it returns its id for its children and leaves the thread's
    open spans alone."""
    import time

    path = tmp_path / "spans.jsonl"
    tr = SpanTracer(path=str(path))
    with tr.span("live", a=1):
        pass
    t0 = time.time() - 5.0
    with tr.span("open") as still_open:
        parent = tr.record_span("first_step", t0, 3.0, rung="all")
        child = tr.record_span("lower", t0 + 0.5, 2.0, parent)
    live, first, lower, opened = tr.recent(4)
    assert set(first) == set(live) == set(lower)
    assert first == {
        "kind": "span", "name": "first_step", "span_id": parent, "parent_id": None,
        "ts": t0, "dur_s": 3.0, "attrs": {"rung": "all"},
    }
    assert (lower["span_id"], lower["parent_id"], lower["attrs"]) == (child, parent, {})
    assert opened["span_id"] == still_open.span_id and opened["parent_id"] is None
    assert len({live["span_id"], parent, child, opened["span_id"]}) == 4
    tr.close()
    assert [json.loads(line) for line in path.read_text().splitlines()] == tr.recent(4)


def test_telemetry_imports_without_jax():
    import subprocess
    import sys

    code = (
        "import sys; import polyaxon_tpu.telemetry as t; "
        "tr = t.SpanTracer(); s = tr.span('x'); s.__enter__(); s.__exit__(None, None, None); "
        "assert 'jax' not in sys.modules; print('ok')"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert out.stdout.strip() == "ok", out.stderr


# --------------------------------------------------------------------- mfu
def test_process_age_and_a_threads_own_counts_without_jax():
    """What the `startup` record reads needs no jax either: the process's
    age from `/proc` (None off Linux), a thread's own counts, `off`."""
    import subprocess
    import sys

    code = (
        "import sys, time; import polyaxon_tpu.telemetry as t; "
        "a = t.process_age(); time.sleep(0.05); b = t.process_age(); "
        "assert a is None or 0 < a < b < 60, (a, b); "
        "assert t.compiles.own() == {} and t.compiles.mine() == 0; "
        "assert t.compiles.cache_since({}) == 'off' and t.compiles.snapshot() == {}; "
        "assert 'jax' not in sys.modules; print('ok')"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert out.stdout.strip() == "ok", out.stderr


def test_process_age_grows_with_the_clock():
    import os
    import time

    from polyaxon_tpu.telemetry import process_age

    if not os.path.exists("/proc/self/stat"):
        pytest.skip("no /proc: the process's age is not told")
    a, t0 = process_age(), time.monotonic()
    time.sleep(1.5)
    b, waited = process_age(), time.monotonic() - t0
    assert 0 < a < b
    assert abs((b - a) - waited) < 1.0  # about the sleep; no tolerance under a second


@pytest.mark.parametrize("broken", ["/proc/self/stat", "/proc/uptime"])
def test_process_age_is_none_where_proc_does_not_say(broken, monkeypatch):
    import builtins

    from polyaxon_tpu.telemetry import process_age

    real = builtins.open

    def no_proc(path, *a, **k):
        if path == broken:
            raise FileNotFoundError(path)
        return real(path, *a, **k)

    monkeypatch.setattr(builtins, "open", no_proc)
    assert process_age() is None


@pytest.mark.parametrize("stacking", [{}, {"scan_layers": True}, {"pipeline_stages": 2}])
def test_trainer_mfu_counts_required_work_as_the_benchmark_does(stacking):
    """At the training cell's `rehearse` size the Trainer's operations a
    step equal `cellbench/flops.py::train_step_flops`: 4 per frozen weight
    and token, 6 per LoRA weight, causal attention forward and backward.
    Stacked layers (`[L, dim]` norm scales under `scan_layers`,
    `[P, L/P, dim]` under pipeline stages) count the same."""
    import jax

    from cellbench import flops
    from cellbench.common import load_cell
    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.schemas.run_kinds import V1Program

    _, _, cell, config = load_cell("internlm2-1.8b.lora-train-2k", rehearse=True)
    spec = cell["program"]
    program = V1Program.model_validate({
        "model": {"name": config["model_name"],
                  "config": {**config["model"], **spec["model_extra"], **stacking}},
        "data": spec["data"], "optimizer": spec["optimizer"],
        "train": dict(spec["train"], steps=1, logEvery=1),
    })
    trainer = Trainer(program, mesh_axes={"data": 1}, devices=jax.devices()[:1])
    try:
        trainer._init_throughput_facts()
        rows, seq = cell["traffic"]["rows"], cell["traffic"]["seq_len"]
        lora = spec["model_extra"]["lora"]
        want = flops.train_step_flops(
            config, rows, seq, lora_rank=lora["rank"],
            lora_targets=cell["reference"]["lora"]["targets"],
        )["total"]
        assert trainer._tokens_per_step == rows * seq
        assert trainer._flops_per_step == pytest.approx(want, rel=1e-12)
    finally:
        trainer.close()
