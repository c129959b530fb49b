"""What the harness gives every driver: the run's context, the compile
counter, the tracer, and small helpers. `cellbench/run.py` is the entry."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
EXIT_NO_DEVICE = 3


def deep_merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = deep_merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, rehearse: bool = False, listed_only: bool = False):
    """(bench, entry, cell, config) of a cell, found by its name. `entry` is
    the cell's line in BENCHMARK.json; a cell whose files are here but which
    is not listed there gets one made from its own file (calibration and
    tests of a cell not yet proved), unless `listed_only`. `rehearse` merges
    the cell's tiny `rehearse` overrides over cell and configuration."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None and listed_only:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = load_json(HERE / "workloads" / f"{workload}.json")
    if entry is None:
        entry = {"name": workload, "config": cell["config"], "chips": cell["chips"]}
    meta = next((c for c in bench["configs"] if c["name"] == entry["config"]), None)
    config = load_json(
        ROOT / meta["file"] if meta else HERE / "configs" / f"{entry['config']}.json"
    )
    if rehearse:
        over = cell.get("rehearse", {})
        config = deep_merge(config, over.get("config", {}))
        cell = deep_merge(cell, over.get("cell", {}))
    return bench, entry, cell, config


def ref_to_program_paths(config: dict, layers: int) -> dict:
    """reference leaf name -> path in the program's tree, from the
    configuration's `param_paths` templates."""
    out = {}
    for ref, path in config["param_paths"].items():
        if "{i}" in ref:
            for i in range(layers):
                out[ref.format(i=i)] = path.format(i=i)
        else:
            out[ref] = path
    return out


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


class Ctx:
    """What a driver gets: the cell, its configuration, the run's arguments,
    and the harness's services (logging, the compile counter, the tracer)."""

    def __init__(self, args, bench, entry, cell, config):
        self.args, self.bench, self.entry = args, bench, entry
        self.cell, self.config = cell, config
        self.seed, self.seconds = int(args.seed), float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), bool(args.rehearse)
        self.t_process = args.t_process
        self.root, self.here = ROOT, HERE
        self.scratch = ROOT / ".cellbench" / "run"
        self.tag = "[device not yet known]"
        self.device = None
        self.compiles = CompileCounter()

    def log(self, msg: str) -> None:
        print(f"{self.tag} {msg}", flush=True)

    def reference(self):
        name = self.config["reference"]
        return load_module(HERE / "references" / f"{name}.py", f"cellbench_ref_{name}")


class CompileCounter:
    """XLA programs compiled or fetched from the persistent cache, counted
    from JAX's own monitoring events (one `backend_compile_duration` per
    program, cache hit or not), with traces and lowerings beside them."""

    EVENTS = {
        "/jax/core/compile/backend_compile_duration": "programs",
        "/jax/core/compile/jaxpr_trace_duration": "traces",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
    }

    def __init__(self):
        self.counts = {v: 0 for v in self.EVENTS.values()}
        self.seconds = {v: 0.0 for v in self.EVENTS.values()}
        self.named: list = []  # (perf_counter, "Compiling jit(run) with ...")

    def install(self):
        import logging
        import time

        import jax.monitoring

        counter = self

        class Names(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling"):
                    counter.named.append((time.perf_counter(), msg[:400]))

        # JAX names each program it compiles at DEBUG level on this logger
        lg = logging.getLogger("jax._src.interpreters.pxla")
        lg.setLevel(logging.DEBUG)
        lg.propagate = False  # or every handler above prints each one
        lg.addHandler(Names(level=logging.DEBUG))

        def on(event, duration, **_):
            key = self.EVENTS.get(event)
            if key:
                self.counts[key] += 1
                self.seconds[key] += float(duration)

        jax.monitoring.register_event_duration_secs_listener(on)

    def snapshot(self) -> dict:
        return dict(self.counts)

    def names_between(self, t0: float, t1: float) -> list:
        return [m for t, m in self.named if t0 <= t <= t1]

    def window_report(self, before: dict, after: dict, t0: float, t1: float) -> tuple[dict, str]:
        """What compiled between two snapshots (taken at t0 and t1): the
        counts, and the line a run prints about them."""
        in_window = {k: after[k] - before[k] for k in after}
        text = (
            f"compilations in the window: programs={in_window['programs']} "
            f"traces={in_window['traces']} lowerings={in_window['lowerings']} (must be 0)"
            + "".join(f"\n    compiled in the window: {m}" for m in self.names_between(t0, t1))
        )
        return in_window, text


def setup_jax(ctx: Ctx, chips: int):
    """Compile cache at a fixed path inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), then the look for the chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    ctx.compiles.install()
    devices = jax.devices()
    platform = devices[0].platform
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if platform != "cpu":
        if not cache:
            cache = str(ROOT / ".jax_compile_cache")
            os.makedirs(cache, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", cache)
        cache_everything()
    kind = devices[0].device_kind
    ctx.tag = f"[platform={platform} device_kind={kind!r} devices={len(devices)}]"
    ctx.device = {"platform": platform, "kind": kind, "count": len(devices)}
    ctx.log(f"compile cache: {cache if platform != 'cpu' else 'off (cpu)'}")
    if ctx.rehearse:
        ctx.log("REHEARSAL at the cell's tiny `rehearse` size: no number below is a measurement")
        return devices
    if platform != "tpu" or len(devices) < chips:
        print(
            f"cellbench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} x {platform} ({kind}). No result.",
            file=sys.stderr,
        )
        sys.exit(EXIT_NO_DEVICE)
    from cellbench.peaks import peaks_for

    ctx.peaks = peaks_for(kind)  # unknown device: an error, here and not later
    return devices


def cache_everything():
    """The program sets a floor of 0.5 s on what is cached; the benchmark's
    own small programs (weights, reference) are below it and would compile
    in every run."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def free_device(devices) -> int:
    """Delete every array still alive on the devices: after the window all
    of them are the program's state (or the driver's own copies of it), and
    the reference must not run beside them. Returns bytes still in use."""
    import gc

    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devices)


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Tracer:
    """The profiler around a window, written under the checkout and removed
    once read."""

    def __init__(self, ctx: Ctx):
        self.dir = ctx.scratch / "trace"
        self.on = ctx.trace

    def __enter__(self):
        if self.on:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no Python frames: the server's threads would fill the trace
            opts.host_tracer_level = 2  # TraceAnnotation spans
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax

            jax.profiler.stop_trace()
        return False

    def read(self) -> dict | None:
        if not self.on:
            return None
        from cellbench import trace_reduce

        path = trace_reduce.find_xplane(str(self.dir))
        trace = trace_reduce.read_xplane(path)
        if os.environ.get("CELLBENCH_DESCRIBE_TRACE"):
            out = Path(os.environ["CELLBENCH_DESCRIBE_TRACE"])
            out.parent.mkdir(parents=True, exist_ok=True)
            out.write_text(json.dumps(trace_reduce.describe_xplane(path), indent=1))
            trace_reduce.save_sample(trace, str(out) + ".sample.json.gz")
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace
