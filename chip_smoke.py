#!/usr/bin/env python3
"""The quickest proof that train -> checkpoint -> serve still starts on the chip.

    python chip_smoke.py              one TPU v5e chip (what the driver runs)
    python chip_smoke.py --chips 4    the two multi-chip paths, and nothing else
    python chip_smoke.py --rehearse [--chips 4]
                                      every phase at a tiny size on the CPU

It drives the README's main path through the CLI, as a user would, on
`examples/llama1b_lora_v5e.yaml` (Llama-3.2-1B widths, all 16 layers, LoRA,
batch 4 x seq 2048, random weights from the seed):

  run (cold)   `python -m polyaxon_tpu run -f <file>`: 6 steps, 2 checkpoints
  run (warm)   the same command again: the persistent compile cache must hit
  serve        `python -m polyaxon_tpu serve -uid <run>`, default path
  serve        the same, step engine over the paged pool

Every phase is a child process, started one after another: a chip belongs
to one process at a time, so this parent never imports JAX. What it knows of
the device — the last line included — is what the children wrote to the run
store and to `/statsz`. Each earlier line is one JSON object. Any failed
check makes the last line `"ok": false` and the exit code non-zero; so does
every run that is not on a TPU, the rehearsal included.
"""

from __future__ import annotations

import argparse
import atexit
import json
import math
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import time
import traceback
import urllib.request
from importlib import metadata
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SPEC = ROOT / "examples" / "llama1b_lora_v5e.yaml"
WORK = ROOT / ".chip_smoke"  # git-ignored; run store, logs, lowered modules
SEED = 0

# The file's defaults are the real size. The rehearsal cuts everything (it
# only checks paths and control flow); on the chip nothing is cut.
REAL = dict(params={}, prompts=(32, 512, 1500), max_new=32, page_tokens=128,
            pool_pages=96, vocab=128256, seq=2048, batch=4)
TINY = dict(params=dict(preset="tiny", n_layers=2, vocab_size=4096, seq_len=256,
                        batch_size=4),
            prompts=(8, 64, 200), max_new=8, page_tokens=16, pool_pages=64,
            vocab=4096, seq=256, batch=4)
STEPS = 6
# first loss of a freshly initialised head: logits are unit-variance (an
# RMS-normed feature times a lecun-normal kernel), so E[loss] = ln V + 1/2
FIRST_LOSS_TOL = 0.5
# sharded against one chip, same seed: bf16 products summed in another order
SHARDED_LOSS_TOL = 0.05
WARM_COMPILE_FRACTION = 0.25

_children: list[subprocess.Popen] = []
_failures: list[str] = []


class Failed(Exception):
    pass


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


def _kill_children(*_a) -> None:
    for p in _children:
        if p.poll() is None:
            p.kill()


def phase(name: str, fn, *args, needs=()):
    """Run one phase; a failure is recorded (and fails the script), never
    passed over. Phases whose inputs failed are reported as not run."""
    if any(n is None for n in needs):
        _failures.append(f"{name}: not run, an earlier phase failed")
        emit(phase=name, ok=False, error="not run: an earlier phase failed")
        return None
    t0 = time.monotonic()
    try:
        out = fn(*args)
    except Exception as e:  # noqa: BLE001 — recorded, and fails the script
        if not isinstance(e, Failed):
            traceback.print_exc()
        _failures.append(f"{name}: {e!r}")
        emit(phase=name, ok=False, wall_s=round(time.monotonic() - t0, 1),
             error=f"{type(e).__name__}: {e}")
        return None
    emit(phase=name, ok=True, wall_s=round(time.monotonic() - t0, 1), **public(out))
    return out


# ------------------------------------------------------------------ children
def child_env(cfg, extra=None) -> dict:
    env = dict(os.environ)
    env.update(
        POLYAXON_HOME=str(WORK / "home"),
        PYTHONPATH=str(ROOT) + os.pathsep + env.get("PYTHONPATH", ""),
        JAX_LOG_COMPILES="1",  # compile seconds and cache hits, on stderr
        TPU_LOG_DIR="disabled",
    )
    if cfg["rehearse"]:
        env["JAX_PLATFORMS"] = "cpu"  # asked for by name: the rehearsal
    env.update(extra or {})
    return env


def spawn(name: str, argv, env) -> tuple[subprocess.Popen, Path, Path]:
    logs = WORK / "logs"
    logs.mkdir(parents=True, exist_ok=True)
    out, err = logs / f"{name}.out", logs / f"{name}.err"
    with open(out, "wb") as fo, open(err, "wb") as fe:
        p = subprocess.Popen(argv, env=env, cwd=ROOT, stdout=fo, stderr=fe)
    _children.append(p)
    return p, out, err


def tail(path: Path, n: int = 1500) -> str:
    data = path.read_bytes()
    return data[-n:].decode(errors="replace")


def run_child(name: str, argv, env, timeout: float) -> tuple[Path, Path]:
    p, out, err = spawn(name, argv, env)
    try:
        rc = p.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        p.kill()
        p.wait()
        raise Failed(f"{name}: no end within {timeout:.0f}s; stderr ends:\n{tail(err)}")
    check(rc == 0, f"{name}: exit code {rc}; stderr ends:\n{tail(err)}")
    return out, err


def compile_log(err: Path) -> dict:
    """What JAX_LOG_COMPILES wrote: every XLA compilation (or cache load)
    with its seconds, and the persistent cache's hits."""
    text = err.read_text(errors="replace")
    # JAX's own handler stamps the time; a process that also configures the
    # root logger prints every line a second time without it
    stamp = r"^WARNING:\d{4}-\d\d-\d\d [\d:,]+:[\w.]+:\d+: "
    secs = [
        (m.group(1), float(m.group(2)))
        for m in re.finditer(
            stamp + r"Finished XLA compilation of (.+?) in ([0-9.]+) sec", text, re.M
        )
    ]
    hits = re.findall(
        stamp + r"Persistent compilation cache hit for '([^']+)'", text, re.M
    )
    biggest = max(secs, key=lambda s: s[1], default=(None, 0.0))
    return {
        "compile_count": len(secs),
        "compile_s": round(sum(s for _, s in secs), 2),
        "largest_compile": {"name": biggest[0], "s": round(biggest[1], 2)},
        "cache_hits": len(hits),
        "_secs": secs,
        "_hits": hits,
    }


def public(d: dict) -> dict:
    return {k: v for k, v in d.items() if not k.startswith("_")}


def cache_entries(path) -> int | None:
    return len(os.listdir(path)) if path and os.path.isdir(path) else None


def run_store():
    from polyaxon_tpu.store.local import RunStore  # JAX-free

    return RunStore(home=WORK / "home")


# -------------------------------------------------------------------- train
def cli_run(cfg, name: str, extra_env=None) -> dict:
    """`polyaxon run -f` in a child; everything it returns was read from the
    run store or from the child's stderr."""
    dump = WORK / "ir" / name
    env = child_env(cfg, extra_env)
    # JAX writes each module as it lowers it, so also when the executable
    # then comes out of the persistent cache (XLA's own dump would not)
    env["JAX_DUMP_IR_TO"] = str(dump)
    argv = [sys.executable, "-m", "polyaxon_tpu", "run", "-f", str(SPEC)]
    for k, v in cfg["params"].items():
        argv += ["-P", f"{k}={v}"]
    out, err = run_child(name, argv, env, timeout=700)

    stdout = out.read_text()
    m = re.search(r"run ([0-9a-f]{8}) .*created", stdout)
    check(m is not None, f"{name}: no run id on stdout: {stdout[-300:]}")
    store = run_store()
    uuid = store.resolve(m.group(1))
    events = {e["kind"]: e for e in store.read_events(uuid)}
    check("device" in events, f"{name}: the run store has no `device` event")
    check("run_summary" in events, f"{name}: the run store has no `run_summary`")
    device = events["device"]
    losses = [
        round(r["loss"], 4)
        for r in sorted(store.read_metrics(uuid), key=lambda r: r["step"])
        if "loss" in r
    ]
    comp = compile_log(err)
    step_s = sum(s for n, s in comp["_secs"] if "step_fn" in n)
    ir = list(dump.glob("*jit_step_fn*.mlir")) if dump.is_dir() else []
    custom_call = any("tpu_custom_call" in f.read_text(errors="replace") for f in ir)
    ckpt = store.outputs_dir(uuid) / "checkpoints"
    res = {
        "run": uuid[:8],
        "device": {k: device.get(k) for k in (
            "platform", "device_kind", "device_ids", "visible_chips",
            "attention_backend", "pallas_interpret", "mesh")},
        "losses": losses,
        "step_compile_s": round(step_s, 2),
        "step_cache_hit": any("step_fn" in h for h in comp["_hits"]),
        "tpu_custom_call_in_train_step": custom_call if ir else None,
        "checkpoints": sorted(os.listdir(ckpt)) if ckpt.is_dir() else [],
        "compile_cache_dir": device.get("compile_cache_dir"),
        "compile_cache_entries": cache_entries(device.get("compile_cache_dir")),
        "device_memory": events["run_summary"].get("device_memory"),
        **public(comp),
        "_uuid": uuid,
    }
    return res


def check_run(cfg, res: dict) -> None:
    losses = res["losses"]
    check(len(losses) >= STEPS, f"{len(losses)} losses logged, want {STEPS}: {losses}")
    check(all(math.isfinite(x) for x in losses), f"a loss is not finite: {losses}")
    want = math.log(cfg["vocab"]) + 0.5
    check(
        abs(losses[0] - want) <= FIRST_LOSS_TOL,
        f"first loss {losses[0]} is not within {FIRST_LOSS_TOL} of "
        f"ln({cfg['vocab']}) + 1/2 = {want:.2f}",
    )
    check(str(STEPS) in res["checkpoints"],
          f"no checkpoint of step {STEPS}: {res['checkpoints']}")
    dev = res["device"]
    check(dev["attention_backend"] == "flash",
          f"attention backend is {dev['attention_backend']}, the file says flash")
    if dev["platform"] == "tpu":
        check(dev["pallas_interpret"] is False, "Pallas is in interpret mode on a TPU")
        check(res["tpu_custom_call_in_train_step"] is True,
              "no tpu_custom_call in the lowered train step "
              f"({res['tpu_custom_call_in_train_step']})")
        check(bool(res["device_memory"]), "the run reported no device memory")
        from polyaxon_tpu.utils.tpu_info import peak_bf16_flops  # JAX-free

        check(peak_bf16_flops(dev["device_kind"]) is not None,
              f"utils/tpu_info.py has no row for {dev['device_kind']!r}")


def train_cold(cfg):
    res = cli_run(cfg, "run-cold", cfg["one_chip_env"])
    check_run(cfg, res)
    return res


def train_warm(cfg, cold):
    """The same command again: its train step must come out of the
    persistent cache, in a small fraction of the cold compile's time."""
    res = cli_run(cfg, "run-warm", cfg["one_chip_env"])
    shutil.rmtree(run_store().outputs_dir(res["_uuid"]), ignore_errors=True)
    check(res["losses"] == cold["losses"],
          f"the same seed gave other losses: {res['losses']} vs {cold['losses']}")
    if res["compile_cache_dir"] is None:
        # the CPU backend is not cached by default; nothing to show
        check(cfg["rehearse"], "no compile cache directory on an accelerator")
        return res
    check(res["step_cache_hit"], "the warm run's train step missed the compile cache")
    if not cold["step_cache_hit"]:
        check(
            res["step_compile_s"] <= WARM_COMPILE_FRACTION * cold["step_compile_s"],
            f"warm train-step compile {res['step_compile_s']}s is not under "
            f"{WARM_COMPILE_FRACTION} of the cold {cold['step_compile_s']}s",
        )
    return res


# -------------------------------------------------------------------- serve
def http_json(url: str, body=None, timeout: float = 600.0):
    data = json.dumps(body).encode() if body is not None else None
    with urllib.request.urlopen(
        urllib.request.Request(url, data=data), timeout=timeout
    ) as r:
        check(r.status == 200, f"{url}: HTTP {r.status}")
        return r.read()


def requests_for(cfg) -> list[dict]:
    """Seeded greedy requests: short, medium (streamed), long, and the short
    one again."""
    rng = random.Random(SEED)
    short, medium, long_ = (
        [rng.randrange(cfg["vocab"]) for _ in range(n)] for n in cfg["prompts"]
    )
    base = {"maxNewTokens": cfg["max_new"], "temperature": 0.0}
    return [
        {"name": "short", "stream": False, "body": {"tokens": [short], **base}},
        {"name": "medium-streamed", "stream": True, "body": {"tokens": [medium], **base}},
        {"name": "long", "stream": False, "body": {"tokens": [long_], **base}},
        {"name": "short-again", "stream": False, "body": {"tokens": [short], **base}},
    ]


def ask(base: str, req: dict, cfg) -> list[int]:
    """POST one request; returns the generated tokens."""
    prompt = req["body"]["tokens"][0]
    if req["stream"]:
        raw = http_json(base + "/generate?stream=1", req["body"]).decode()
        frames = [json.loads(l[6:]) for l in raw.splitlines() if l.startswith("data: ")]
        check(frames and frames[-1].get("done") is True and "row" not in frames[-1],
              f"{req['name']}: the stream did not end in a done frame")
        new = [t for f in frames if "tokens" in f for t in f["tokens"]]
    else:
        row = json.loads(http_json(base + "/generate", req["body"]))["tokens"][0]
        check(row[: len(prompt)] == prompt, f"{req['name']}: the prompt came back changed")
        new = row[len(prompt):]
    check(len(new) == cfg["max_new"],
          f"{req['name']}: {len(new)} new tokens, want {cfg['max_new']}")
    check(all(isinstance(t, int) and 0 <= t < cfg["vocab"] for t in new),
          f"{req['name']}: a token id is outside [0, {cfg['vocab']})")
    return new


def wait_ready(p: subprocess.Popen, base: str, err: Path, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        check(p.poll() is None,
              f"server exited with code {p.returncode} before it was ready; "
              f"stderr ends:\n{tail(err)}")
        try:
            if json.loads(http_json(base + "/readyz", timeout=2.0)).get("ready"):
                return
        except (OSError, Failed):
            pass
        time.sleep(0.5)
    raise Failed(f"server not ready within {timeout:.0f}s; stderr ends:\n{tail(err)}")


def stop(p: subprocess.Popen) -> None:
    if p.poll() is None:
        p.send_signal(signal.SIGTERM)
        try:
            p.wait(timeout=60)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


def serve(cfg, name: str, uuid: str, flags, extra_env=None) -> dict:
    """`polyaxon serve -uid` in a child; answers the seeded requests."""
    from polyaxon_tpu.native import free_port  # JAX-free

    port = free_port()
    base = f"http://127.0.0.1:{port}"
    argv = [sys.executable, "-m", "polyaxon_tpu", "serve", "-uid", uuid,
            "--port", str(port), *flags]
    p, out, err = spawn(name, argv, child_env(cfg, extra_env))
    try:
        t0 = time.monotonic()
        wait_ready(p, base, err, timeout=400)
        ready_s = time.monotonic() - t0
        before = json.loads(http_json(base + "/statsz"))
        answers = {r["name"]: ask(base, r, cfg) for r in requests_for(cfg)}
        after = json.loads(http_json(base + "/statsz"))
    finally:
        stop(p)
    check("device" in before, "/statsz names no device")
    startup = (out.read_text().splitlines() or [""])[0]
    dev = after["device"]
    check(f"[{dev['platform']}:{dev['device_kind']}" in startup,
          f"the startup line does not name the device: {startup!r}")
    check(answers["short"] == answers["short-again"],
          "the same greedy request gave other tokens the second time")
    if dev["platform"] == "tpu":
        check(dev["pallas_interpret"] is False, "Pallas is in interpret mode on a TPU")
        check(bool(dev["memory"]), "/statsz reported no device memory")
    comp = compile_log(err)
    return {
        "device": {k: dev.get(k) for k in (
            "platform", "device_kind", "device_ids", "visible_chips",
            "attention_backend", "pallas_interpret")},
        "ready_s": round(ready_s, 1),
        "step_engine": bool(after["chunked"]["enabled"]),
        "kv_pool": after["kv"].get("enabled", False),
        "programs_compiled": after["compile_count"],
        "tokens": answers,
        "device_memory": dev["memory"],
        **public(comp),
    }


def serve_default(cfg, run):
    res = serve(cfg, "serve-default", run["_uuid"], [], cfg["one_chip_env"])
    check(not res["step_engine"], "the default path ran the step engine")
    return res


def serve_paged(cfg, run, default):
    flags = ["--chunked-prefill", "--kv-pool-pages", str(cfg["pool_pages"]),
             "--kv-page-tokens", str(cfg["page_tokens"])]
    res = serve(cfg, "serve-step-engine", run["_uuid"], flags, cfg["one_chip_env"])
    check(res["step_engine"], "--chunked-prefill did not start the step engine")
    for name, toks in res["tokens"].items():
        check(toks == default["tokens"][name],
              f"{name}: the step engine's greedy tokens differ from the default "
              f"path's: {toks} vs {default['tokens'][name]}")
    return res


# ---------------------------------------------------------------- four chips
def sharded_train(cfg, one_chip):
    """The same program, same seed, on a {fsdp: 2, model: 2} mesh over all
    four chips, in one child that holds them all."""
    argv = [sys.executable, str(Path(__file__).resolve()), "--child", "train4"]
    if cfg["rehearse"]:
        argv.append("--rehearse")
    extra = {"JAX_NUM_CPU_DEVICES": "4"} if cfg["rehearse"] else None
    out, err = run_child("train-sharded", argv, child_env(cfg, extra), timeout=900)
    res = json.loads(out.read_text().strip().splitlines()[-1])
    res.update(public(compile_log(err)))
    check(len(res["device"]["device_ids"]) == 4,
          f"the mesh holds {res['device']['device_ids']}, want four devices")
    diffs = [abs(a - b) for a, b in zip(res["losses"], one_chip["losses"])]
    res["one_chip_losses"] = one_chip["losses"]
    res["max_loss_diff"] = round(max(diffs), 5)
    res["loss_tolerance"] = SHARDED_LOSS_TOL
    check(len(res["losses"]) == len(one_chip["losses"]) >= STEPS,
          f"{len(res['losses'])} sharded losses against {len(one_chip['losses'])}")
    check(max(diffs) <= SHARDED_LOSS_TOL,
          f"sharded and one-chip losses differ by {max(diffs):.4f} > "
          f"{SHARDED_LOSS_TOL}: {res['losses']} vs {one_chip['losses']}")
    peaks = {k: v["peak_bytes_in_use"] for k, v in res["device_memory"].items()}
    res["peak_bytes_per_device"] = peaks
    if res["device"]["platform"] == "tpu":
        one = max(v["peak_bytes_in_use"] for v in one_chip["device_memory"].values())
        res["one_chip_peak_bytes"] = one
        check(len(peaks) == 4, f"memory reported for {sorted(peaks)}, want four devices")
        check(max(peaks.values()) <= 0.6 * one,
              f"a device of the mesh peaks at {max(peaks.values())} bytes, over 0.6 "
              f"of the one-chip run's {one}: the parameters are not spread")
        check(min(peaks.values()) >= 0.5 * max(peaks.values()),
              f"per-device peaks are lopsided: {peaks}")
    return res


def child_train4(rehearse: bool) -> None:
    import jax

    from polyaxon_tpu.compiler.resolver import compile_operation
    from polyaxon_tpu.polyaxonfile.reader import read_polyaxonfile
    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.utils.jax_platform import device_memory

    params = TINY["params"] if rehearse else REAL["params"]
    op = read_polyaxonfile(str(SPEC), params={k: str(v) for k, v in params.items()})
    program = compile_operation(op, base_dir=None).run.program
    # train only: the one-chip `run` child wrote the checkpoint that is served
    program = program.model_copy(
        update={"train": program.train.model_copy(update={"checkpoint_every": None})}
    )
    losses, events = [], {}
    trainer = Trainer(
        program,
        mesh_axes={"fsdp": 2, "model": 2},
        devices=jax.devices(),
        log_fn=lambda step, m: losses.append(round(m["loss"], 4)),
        event_fn=lambda kind, body: events.setdefault(kind, body),
    )
    try:
        trainer.run()
    finally:
        trainer.close()
    print(json.dumps({
        "device": events["device"],
        "losses": losses,
        "device_memory": device_memory(trainer.mesh.local_devices),
    }))


def serve_fleet(cfg, run, reference):
    """`serve --replicas 4 --route`: four children, a chip each, behind the
    router; the same greedy requests give the one-replica server's tokens."""
    from polyaxon_tpu.native import free_port

    port = free_port()
    base = f"http://127.0.0.1:{port}"
    argv = [sys.executable, "-m", "polyaxon_tpu", "serve", "-uid", run["_uuid"],
            "--port", str(port), "--replicas", "4", "--route"]
    p, out, err = spawn("serve-fleet", argv, child_env(cfg))
    try:
        wait_ready(p, base, err, timeout=1500)
        answers = {r["name"]: ask(base, r, cfg) for r in requests_for(cfg)}
        router = json.loads(http_json(base + "/statsz"))
        replicas = [
            json.loads(http_json(r["url"] + "/statsz")) for r in router["replicas"]
        ]
    finally:
        stop(p)
    devices = [
        {k: r["device"].get(k) for k in (
            "platform", "device_kind", "device_ids", "visible_chips")}
        | {"requests": r["requests"]}
        for r in replicas
    ]
    check(router["routable"] == 4, f"{router['routable']} routable replicas, want 4")
    for name, toks in answers.items():
        check(toks == reference["tokens"][name],
              f"{name}: the fleet's tokens differ from the one-replica server's")
    if devices[0]["platform"] == "tpu":
        chips = [d["visible_chips"] for d in devices]
        check(len(set(chips)) == 4 and None not in chips,
              f"the four replicas were given chips {chips}, want four different ones")
    return {
        "routable": router["routable"],
        "router_requests": router["requests"],
        "replicas": devices,
        "distinct_device_ids": len({tuple(d["device_ids"]) for d in devices}),
        "tokens": answers,
    }


# --------------------------------------------------------------------- main
def device_line(reports: list[dict], count: int):
    """The contract's device, from what the children reported and only if
    they agree."""
    seen = {(r["platform"], r["device_kind"]) for r in reports}
    if len(seen) != 1:
        if seen:
            _failures.append(f"the children disagree about the device: {sorted(seen)}")
        return None
    platform, kind = seen.pop()
    return {"platform": platform, "kind": kind, "count": count}


def versions() -> dict:
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax", "orbax-checkpoint"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-training and routed-replica paths")
    ap.add_argument("--rehearse", action="store_true",
                    help="every phase at a tiny size on the CPU; ends ok=false")
    ap.add_argument("--child", choices=("train4",), help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        child_train4(args.rehearse)
        return 0

    if not (SPEC.is_file() and (ROOT / "polyaxon_tpu").is_dir()):
        print(json.dumps({"ok": False, "device": None}))
        print(f"chip_smoke: {SPEC} is not here; run from a checkout", file=sys.stderr)
        return 2
    from polyaxon_tpu.utils.jax_platform import chip_env, env_names_cpu  # JAX-free

    if env_names_cpu() and not args.rehearse:
        # no TPU to be had: fail now, before any work
        print(json.dumps({"ok": False, "device": None}))
        print("chip_smoke: the environment names the CPU; --rehearse runs the "
              "phases there", file=sys.stderr)
        return 2

    atexit.register(_kill_children)
    signal.signal(signal.SIGTERM, lambda *a: sys.exit(143))
    shutil.rmtree(WORK, ignore_errors=True)
    cfg = dict(TINY if args.rehearse else REAL, rehearse=args.rehearse)
    emit(chip_smoke=str(SPEC.relative_to(ROOT)), chips=args.chips,
         rehearse=args.rehearse, versions=versions(),
         cut={"params": cfg["params"]} if cfg["params"] else None,
         workload={k: cfg[k] for k in ("prompts", "max_new", "seq", "batch")})

    # one-chip children get the replica children's own chip assignment, so
    # that what four chips depend on is already exercised on one
    cfg["one_chip_env"] = chip_env(0, 1)
    if args.chips == 1:
        cold = phase("run-cold", train_cold, cfg)
        warm = phase("run-warm", train_warm, cfg, cold, needs=(cold,))
        default = phase("serve-default", serve_default, cfg, cold, needs=(cold,))
        paged = phase("serve-step-engine", serve_paged, cfg, cold, default,
                      needs=(cold, default))
        reports = [r["device"] for r in (cold, warm, default, paged) if r]
        ids = {i for r in (cold, warm, default, paged) if r
               for i in r["device"]["device_ids"]}
        count = len(ids)
    else:
        one = phase("run-one-chip", train_cold, cfg)
        sharded = phase("train-sharded", sharded_train, cfg, one, needs=(one,))
        ref = phase("serve-one-replica", serve_default, cfg, one, needs=(one,))
        fleet = phase("serve-fleet", serve_fleet, cfg, one, ref, needs=(one, ref))
        reports = [r["device"] for r in (one, sharded, ref) if r]
        reports += fleet["replicas"] if fleet else []
        count = len(sharded["device"]["device_ids"]) if sharded else 0

    device = device_line(reports, count)
    shutil.rmtree(WORK / "home", ignore_errors=True)  # checkpoints are large
    # the children's logs, where the chip tool brings them back from
    keep = ROOT / "chiprun_out" / ("chip_smoke_4" if args.chips == 4 else "chip_smoke")
    shutil.rmtree(keep, ignore_errors=True)
    shutil.copytree(WORK / "logs", keep, dirs_exist_ok=True)
    ok = not _failures and device is not None and device["platform"] == "tpu"
    for f in _failures:
        print(f"chip_smoke: FAILED {f}", file=sys.stderr)
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
