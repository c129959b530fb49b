"""Driver for serving cells: a `ModelServer` built in this process from
weights the benchmark makes on the device from the seed, started with
`start()` and driven over `POST /generate?stream=1` on localhost by the load
generator (`cellbench/loadgen.py`, one child process that never touches JAX).

Set-up: weights, the server, then every program the cell's traffic can need
(`warm_waves`: slice widths x table widths x row counts, driven through the
server's own admission), then the cell's own traffic from the run's seed for
`ramp_seconds`, so that the window opens on a system in steady state.
Nothing may compile inside the window; the count is printed and a run in
which it is not 0 is not correct.

After the window: the memory peak is read, the server is stopped and its
state freed, and the plain reference runs once over a sample, drawn from the
seed and with the longest in it, of the requests that finished: each prompt
with its served tokens. The number compared is the widest gap by which a
served token's logit lies below the reference's best.
"""

from __future__ import annotations

import json
import os
import random
import statistics
import subprocess
import sys
import threading
import time

import numpy as np

from cellbench import compare, flops, weights
from cellbench.common import ref_to_program_paths

FAULTS = ("token_altered",)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    s = sorted(values)
    k = max(0, min(len(s) - 1, int(np.ceil(q / 100.0 * len(s))) - 1))
    return float(s[k])


def pages_for(tokens: int, page: int) -> int:
    return -(-tokens // page)


def pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def bucket(n: int, ladder) -> int:
    return next(b for b in ladder if b >= n)


def warm_waves(stats: dict, traffic_: dict, serving: dict) -> list:
    """The fewest requests that make the server build every program the
    traffic's lengths can need, from the bucket ladders it reports in
    /statsz. A decode lane's program depends on the lane's row count
    (rounded up to a power of two) and on its widest page table; a prefill
    slice's on its width, its row's table width, and whether it is the last.

    For every table width W: a lead request whose buckets give W, sent
    alone, and once it decodes, followers of the smallest buckets, which
    join its lane one by one: row counts 1, 2, 3, ... past half of maxBatch
    (the top bucket), all at width W. Then one request for each (slice width,
    table width) no lead had, and, where the prefix cache is on, one for each
    count of full prompt pages (its copy into the cache is a program per
    count)."""
    page = int(serving.get("kvPageTokens", 128))
    chunk = int(serving.get("prefillChunkTokens", 64))
    rows = int(serving.get("maxBatch", 8))
    p, o = traffic_["prompt_len"], traffic_["output_len"]
    pls = sorted({bucket(n, stats["prompt_buckets"]) for n in range(p["min"], p["max"] + 1)})
    nls = sorted({bucket(n, stats["max_new_buckets"]) for n in range(o["min"], o["max"] + 1)})
    width = lambda pb, nb: pow2_at_least(pages_for(pb + nb - 1, page))  # noqa: E731
    n_follow = rows // 2  # lead + rows/2 followers: one more than half, the top bucket
    need = n_follow + 8  # decode steps the lead must last while they join

    def new_for(nb):  # the fewest new tokens that land in bucket nb and last
        lo = max((b for b in stats["max_new_buckets"] if b < nb), default=0)
        return min(nb, max(lo + 1, need))

    combos = {(min(chunk, pb), width(pb, nb)): (pb, nb) for pb in pls for nb in nls}
    by_width: dict = {}
    for pb in pls:
        for nb in nls:
            if nb >= need or nb == nls[-1]:
                by_width.setdefault(width(pb, nb), (pb, nb))
    small = (min(pls[0], p["max"]), new_for(next(nb for nb in nls if nb >= need or nb == nls[-1])))
    waves, led = [], set()
    for w, (pb, nb) in sorted(by_width.items()):
        waves.append({"lead": [min(pb, p["max"]), new_for(nb)],
                      "followers": [list(small)] * n_follow})
        led.add((min(chunk, pb), w))
    led.add((min(chunk, pls[0]), width(pls[0], bucket(small[1], stats["max_new_buckets"]))))
    singles = [[min(pb, p["max"]), new_for(nb) if nb >= need else nb]
               for key, (pb, nb) in sorted(combos.items()) if key not in led]
    if serving.get("prefixCache", True):
        # when a row finishes, the prefix cache copies its full prompt pages
        # with a program of its own for every (count of those pages, length
        # of the row's page table): one request for each pair. The table's
        # length is the row's page budget here (the page size divides the
        # buckets, so a row's own need at its end equals its budget).
        seen = set()
        for n in range(p["min"], p["max"] + 1):
            k, pb = n // page, bucket(n, stats["prompt_buckets"])
            for nb in nls:
                key = (k, pages_for(pb + nb - 1, page))
                if k and key not in seen:
                    seen.add(key)
                    lo = max((b for b in stats["max_new_buckets"] if b < nb), default=0)
                    singles.append([n, max(lo + 1, o["min"])])
    if singles:
        waves.append({"lead": None, "followers": singles})
    return waves


def start_child(ctx, job: dict, name: str, timeout: float) -> tuple:
    """Start the load generator on a job; `wait_child` reads its record."""
    ctx.scratch.mkdir(parents=True, exist_ok=True)
    job_path, out_path = ctx.scratch / f"{name}.job.json", ctx.scratch / f"{name}.out.json"
    job = dict(job, out=str(out_path))
    job_path.write_text(json.dumps(job))
    proc = subprocess.Popen(
        [sys.executable, str(ctx.here / "loadgen.py"), str(job_path)],
        env=child_env(),
    )
    return proc, out_path, timeout


def child_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # it never imports JAX; and if it did, not the chip
    return env


def wait_child(handle) -> list:
    proc, out_path, timeout = handle
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise RuntimeError("the load generator did not end in time")
    if rc != 0:
        raise RuntimeError(f"the load generator exited {rc}")
    records = json.loads(out_path.read_text())["records"]
    out_path.unlink(missing_ok=True)
    return records


def build_server(ctx):
    """Model, weights from the seed in the type they are served in, and the
    server under the cell's own serving spec."""
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import build_model
    from polyaxon_tpu.runtime.trainer import make_param_init
    from polyaxon_tpu.schemas.run_kinds import V1ServingSpec
    from polyaxon_tpu.serving.server import ModelServer

    cfg, spec = ctx.config, ctx.cell["program"]
    bundle = build_model(cfg["model_name"], {**cfg["model"], **spec.get("model_extra", {})})
    dtype = jnp.dtype(cfg.get("weights_dtype", "bfloat16"))
    init_fn = make_param_init(bundle, dtype, bundle.example_inputs(1))
    abstract, _ = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    t = time.perf_counter()
    params = weights.tree(ctx.seed, abstract, cfg["init"], dtype=dtype)
    jax.block_until_ready(params)
    ctx.log(f"weights from the seed ({dtype.name}) in {time.perf_counter() - t:.1f} s")
    shapes = {
        weights.path_str(p): (tuple(a.shape), a.dtype)
        for p, a in jax.tree_util.tree_flatten_with_path(abstract)[0]
    }
    config = V1ServingSpec.model_validate(spec["serving"]).to_config()
    t = time.perf_counter()
    server = ModelServer(
        bundle.module, params, model_name=cfg["model_name"], config=config,
        sharding_rules=bundle.sharding_rules,
    )
    port = server.start()
    ctx.log(f"server built and started in {time.perf_counter() - t:.1f} s on port {port}")
    return server, port, shapes


class StatsSampler:
    """/statsz (the server's own `stats()`), once a second in the window."""

    def __init__(self, server, period: float = 1.0):
        self.server, self.period = server, period
        self.samples: list = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.wait(self.period):
            self.samples.append(self.server.stats())

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=5)
        return False


def reduce_records(records, t_open: float, t_close: float) -> dict:
    """The window's requests: sent in it, completed in it, and the
    client-side times of each. A failed or refused request counts as the
    worst: the window and the minute a request is waited for."""
    worst_ms = 1e3 * (t_close - t_open + 60.0)
    sent = [r for r in records if t_open <= r["t_send"] < t_close]
    ok = lambda r: r["error"] is None and r["t_done"] is not None and r["frames"]  # noqa: E731
    completed = [r for r in records if ok(r) and t_open <= r["t_done"] < t_close]
    failed = [r for r in sent if not ok(r)]
    ttft = []
    for r in sent:
        start = r["due"] if r["due"] is not None else r["t_send"]
        # a failed or refused request counts as the worst
        ttft.append(1e3 * (r["frames"][0][0] - start) if ok(r) else worst_ms)
    tpot = []
    for r in completed:
        n_rest = len(r["tokens"]) - r["frames"][0][1]
        if n_rest > 0:
            tpot.append(1e3 * (r["frames"][-1][0] - r["frames"][0][0]) / n_rest)
    lateness = [1e3 * (r["t_send"] - r["due"]) for r in sent if r["due"] is not None]
    return {
        "sent": sent, "completed": completed, "failed": failed,
        "ttft_ms": ttft, "tpot_ms": tpot, "lateness_ms": lateness,
        "out_tokens": sum(len(r["tokens"]) for r in completed),
    }


def window_work(records, t_open: float, t_close: float, cfg: dict) -> dict:
    """Tokens processed inside the window, by when their frame arrived: a
    request's prompt counts with its first frame, each later token with its
    own. Operations from the shapes (cellbench/flops.py)."""
    prefilled = decoded = 0
    ops = 0.0
    for r in records:
        pos = r["prompt_len"]
        for k, (t, n) in enumerate(r["frames"]):
            if t_open <= t < t_close:
                if k == 0:
                    prefilled += r["prompt_len"]
                    ops += flops.serve_span_flops(cfg, 0, r["prompt_len"], sampled=1)
                    extra = n - 1
                else:
                    extra = n
                if extra > 0:
                    decoded += extra
                    ops += flops.serve_span_flops(cfg, pos, pos + extra, sampled=extra)
            pos += n if k else n - 1
    return {"prefilled": prefilled, "decoded": decoded, "flops": ops}


def sample_for_reference(completed, seed: int, spec: dict) -> list:
    """A sample of the requests the window finished, drawn from the seed,
    with the longest in it."""
    if not completed:
        return []
    rng = random.Random(int(seed) ^ 0x73616D70)
    by_len = sorted(completed, key=lambda r: (r["prompt_len"] + len(r["tokens"]), r["index"]))
    picked = [by_len[-1]]
    rest = by_len[:-1]
    rng.shuffle(rest)
    picked += rest[: max(0, int(spec["requests"]) - 1)]
    return picked


def run_reference(ctx, shapes: dict, sample, products: str = "float32"):
    """The reference's logits at every served position of the sample."""
    import jax
    import jax.numpy as jnp

    ref = ctx.reference()
    d = ref.Dims.from_published(ctx.config)
    paths = ref_to_program_paths(ctx.config, d.layers)
    rules, seed = ctx.config["init"], ctx.seed
    served = jnp.dtype(ctx.config.get("weights_dtype", "bfloat16"))

    spent = {"weights_s": 0.0}

    def get(name):
        shape, _ = shapes[paths[name]]
        t0 = time.perf_counter()
        # the weights as they are served (rounded to that type), in float32
        x = weights.leaf(seed, paths[name], shape, served, rules).astype(jnp.float32)
        spent["weights_s"] += time.perf_counter() - t0
        return x

    seqs = [r["prompt"] + r["tokens"][:-1] for r in sample]
    rows = [list(range(len(r["prompt"]) - 1, len(r["prompt"]) + len(r["tokens"]) - 1))
            for r in sample]
    t0 = time.perf_counter()
    tr = ctx.cell["traffic"]
    # one padded length for every sequence (the mix's longest request): the
    # reference's layer compiles once, whatever the sample holds
    longest = int(tr["prompt_len"]["max"]) + int(tr["output_len"]["max"])
    with jax.default_matmul_precision("highest"):
        logits = ref.logits_for(
            get, d, seqs, rows, products=products, pad_to=longest,
            rows_to=int(tr["output_len"]["max"]),
        )
    out = [np.asarray(x) for x in logits]
    ctx.log(
        f"reference ({products}): {len(seqs)} sequences of {sorted(len(s) for s in seqs)} tokens "
        f"in {time.perf_counter() - t0:.1f} s, {spent['weights_s']:.1f} s of it dispatching weights"
    )
    return out


def serve_numbers(sample, ref_logits) -> tuple[dict, dict]:
    gaps = [compare.logit_gaps(lg, r["tokens"]) for r, lg in zip(sample, ref_logits)]
    flat = np.concatenate(gaps) if gaps else np.zeros((0,))
    if flat.size == 0:
        inf = float("inf")
        return {"served_logit_gap_max": inf, "served_logit_gap_mean": inf}, {"tokens_compared": 0}
    return (
        {"served_logit_gap_max": float(flat.max()), "served_logit_gap_mean": float(flat.mean())},
        {"tokens_compared": int(flat.size), "requests_compared": len(sample),
         "gap_median": float(np.median(flat)),
         "tokens_off_reference_best": int((flat > 0).sum())},
    )


def attach_prompts(ctx, records) -> None:
    from cellbench import traffic

    for r in records:
        r["prompt"] = traffic.prompt_tokens(
            int(ctx.config["vocab_size"]), r["prompt_len"], ctx.seed, r["index"]
        )


def run(ctx, devices, fault: str | None = None) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    from cellbench.common import Tracer, cache_everything, free_device, memory_peak

    cell, cfg = ctx.cell, ctx.config
    tr, serving = cell["traffic"], cell["program"]["serving"]
    vocab = int(cfg["vocab_size"])

    # ------------------------------------------------------------- set-up
    server, port, shapes = build_server(ctx)
    if devices[0].platform != "cpu":
        cache_everything()
    base = f"http://127.0.0.1:{port}"
    if fault == "token_altered":
        plant_token_fault(server, vocab)
    t = time.perf_counter()
    waves = warm_waves(server.stats(), tr, serving)
    warm = wait_child(start_child(
        ctx, {"base": base, "mode": "waves", "waves": waves, "vocab": vocab,
              "seed": ctx.seed + 1, "traffic": tr}, "warm", timeout=1100.0,
    ))
    bad = [r for r in warm if r["error"]]
    ctx.log(
        f"warm-up: {len(waves)} waves {json.dumps(waves)} in {time.perf_counter() - t:.1f} s; "
        f"requests={len(warm)} failed={len(bad)}; programs so far "
        f"{ctx.compiles.snapshot()['programs']}"
        + (f"; first failure: {bad[0]['error']}" if bad else "")
    )
    seconds = ctx.seconds
    if ctx.trace:
        seconds = min(seconds, float(cell.get("trace_seconds", seconds)))
    ramp = float(cell.get("ramp_seconds", 5.0))
    t_open = time.monotonic() + ramp
    t_close = t_open + seconds
    handle = start_child(
        ctx, {"base": base, "mode": tr["generator"], "traffic": tr, "vocab": vocab,
              "seed": ctx.seed, "t_open": t_open, "seconds": seconds},
        "window", timeout=ramp + seconds + 90.0,
    )
    # the profiler takes a moment to start: it starts inside the ramp
    time.sleep(max(0.0, t_open - 2.0 - time.monotonic()))

    # ------------------------------------------------------------- window
    with Tracer(ctx) as tracer:
        time.sleep(max(0.0, t_open - time.monotonic()))
        before, tc0 = ctx.compiles.snapshot(), time.perf_counter()
        setup_s = time.perf_counter() - ctx.t_process
        stats0 = server.stats()
        ctx.log("window opens")
        with StatsSampler(server) as sampler, TraceAnnotation("cellbench.window"):
            time.sleep(max(0.0, t_close - time.monotonic()))
        stats1 = server.stats()
        after, tc1 = ctx.compiles.snapshot(), time.perf_counter()
        ctx.log("window closed")
    in_window, compiled = ctx.compiles.window_report(before, after, tc0, tc1)
    records = wait_child(handle)
    peak = memory_peak(devices)
    trace = tracer.read()

    red = reduce_records(records, t_open, t_close)
    work = window_work(records, t_open, t_close, cfg)
    n_sent, n_done, n_failed = len(red["sent"]), len(red["completed"]), len(red["failed"])
    finite = red["ttft_ms"]
    ctx.log(
        f"window {seconds:.3f} s: sent={n_sent} completed={n_done} failed={n_failed} "
        f"out_tokens={red['out_tokens']} prefilled={work['prefilled']} decoded={work['decoded']} "
        f"ttft_p50_ms={statistics.median(finite) if finite else float('nan'):.2f} "
        f"tpot_p50_ms={statistics.median(red['tpot_ms']) if red['tpot_ms'] else float('nan'):.2f}"
    )
    if red["lateness_ms"]:
        ctx.log(f"generator lateness ms: median={statistics.median(red['lateness_ms']):.2f} "
                f"max={max(red['lateness_ms']):.2f}")
    steps = stats1["chunked"].get("steps", 0) - stats0["chunked"].get("steps", 0)
    kv_samples = [s["kv"] for s in sampler.samples if s["kv"].get("enabled")]
    ctx.log(
        f"/statsz over the window: steps={steps} "
        f"prefill_chunks={stats1['chunked'].get('prefill_chunks', 0) - stats0['chunked'].get('prefill_chunks', 0)} "
        f"mean_batch_occupancy={stats1.get('mean_batch_occupancy')} "
        f"queue_wait_ms={stats1.get('queue_wait_ms')} shed={stats1.get('shed')}"
    )
    if kv_samples:
        last = kv_samples[-1]
        ctx.log("kv at the last sample: " + json.dumps(
            {k: last.get(k) for k in ("pages_total", "pages_used", "pages_reserved",
                                      "active_rows", "page_refs", "prefix_entries")}
        ))
    ctx.log(compiled)
    ctx.log(f"peak_bytes_in_use={peak} (the allocator's counter: a floor, not the fit)")

    # ---------------------------------------- free the program, then compare
    server.stop()
    del server, sampler
    left = free_device(devices)
    ctx.log(f"server stopped and its arrays deleted; bytes_in_use={left}")
    t = time.perf_counter()
    sample = sample_for_reference(red["completed"], ctx.seed, cell["reference"])
    attach_prompts(ctx, sample)
    ref_logits = run_reference(ctx, shapes, sample)
    nums, notes = serve_numbers(sample, ref_logits)
    ok, table = compare.verdict(nums, cell["limits"])
    ctx.log(f"reference over {notes} in {time.perf_counter() - t:.1f} s; numbers {nums}")
    ok = ok and in_window["programs"] == 0 and n_failed == 0 and n_done > 0

    e2e = {"setup_s": setup_s}
    if n_done:
        e2e["serve_tokens_per_s"] = red["out_tokens"] / seconds
    if red["ttft_ms"]:
        e2e["ttft_p95_ms"] = percentile(red["ttft_ms"], 95)
    if red["tpot_ms"]:
        e2e["tpot_p95_ms"] = percentile(red["tpot_ms"], 95)
    obs = {
        "cell": cell, "config": cfg, "window_s": seconds, "steps": steps,
        "work": work, "stats0": stats0, "stats1": stats1, "kv_samples": kv_samples,
        "records": records, "t_open": t_open, "t_close": t_close,
        "peaks": getattr(ctx, "peaks", None), "chips": int(ctx.entry["chips"]),
    }
    breakdown = None
    if trace is not None:
        from cellbench import trace_reduce

        obs["trace"], breakdown = trace_reduce.summarise(trace)
        obs["trace_raw"] = trace
        ctx.log(
            f"trace: busy_s={obs['trace']['busy_s']:.4f} of window_s={seconds:.4f}; "
            f"programs {trace_reduce.top(obs['trace']['modules'], 6)}"
        )
    return {
        "end_to_end": e2e, "observations": obs, "breakdown": breakdown,
        "correct": ok, "compared": table, "attempted": n_sent, "failed": n_failed,
        "memory_peak_bytes": peak, "shapes": shapes, "numbers": nums,
    }


def plant_token_fault(server, vocab: int) -> None:
    """For the tests: every 7th token is altered where it is produced (the
    frame that carries it to the client), the decode itself untouched."""
    engine_cls = type(server._coalescer._engine)
    real, count = engine_cls._emit, [0]

    def emit(self, r, toks):
        toks = [int(t) for t in toks]
        for i in range(len(toks)):
            count[0] += 1
            if count[0] % 7 == 0:
                toks[i] = (toks[i] + 1) % vocab
        return real(self, r, toks)

    engine_cls._emit = emit
    _PLANTED.append(lambda: setattr(engine_cls, "_emit", real))


_PLANTED: list = []


def unplant_faults() -> None:
    while _PLANTED:
        _PLANTED.pop()()


def readings(ctx, devices, seeds, control_seeds) -> list[dict]:
    """For setting limits (cellbench/calibrate.py): on each seed a short
    window at the cell's own load (long enough to finish the mix's longest
    requests), the program's number against the reference; on the control
    seeds also the control's: at each position of the same prompts and
    tokens, the gap of the token that the 8-bit reference puts first."""
    out = []
    for seed in seeds:
        ctx.seed = seed
        ctx.t_process = time.perf_counter()
        res = run(ctx, devices)
        row = {"seed": seed, "program": res["numbers"],
               "correct": res["correct"], "end_to_end": res["end_to_end"]}
        if seed in control_seeds:
            obs = res["observations"]
            red = reduce_records(obs["records"], obs["t_open"], obs["t_close"])
            sample = sample_for_reference(red["completed"], seed, ctx.cell["reference"])
            attach_prompts(ctx, sample)
            shapes = res["shapes"]
            ref_logits = run_reference(ctx, shapes, sample)
            low = run_reference(ctx, shapes, sample, products="int8")
            firsts = [{"tokens": lg.argmax(-1).tolist()} for lg in low]
            row["control_int8"], _ = serve_numbers(firsts, ref_logits)
            altered = [{"tokens": [(t + 1) % int(ctx.config["vocab_size"]) if i % 7 == 6 else t
                                   for i, t in enumerate(r["tokens"])]} for r in sample]
            row["fault_token_altered"], _ = serve_numbers(altered, ref_logits)
        ctx.log("readings " + repr(row))
        out.append(row)
    return out
