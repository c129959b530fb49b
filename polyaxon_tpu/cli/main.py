"""`polyaxon` CLI — the user surface (SURVEY.md §2 "CLI", §3 stacks (a)/(e)).

Commands (parity with the reference's core verbs, local-first execution):
  polyaxon run -f file.yaml [-P name=value] [--eager/--local]
  polyaxon check -f file.yaml
  polyaxon ops ls / get / logs / statuses / stop [-uid UID]
  polyaxon tuner ... (sweep driving; Polytune)
  polyaxon version
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import click

from .. import __version__
from ..compiler.resolver import CompilationError, compile_operation
from ..polyaxonfile.reader import PolyaxonfileError, read_polyaxonfile
from ..schemas.lifecycle import V1Statuses
from ..store.local import RunStore


@click.group()
def cli():
    """Polyaxon-TPU: experiment orchestration, natively on TPU."""


@cli.command()
def version():
    click.echo(f"polyaxon-tpu {__version__}")


def _params_to_dict(params):
    out = {}
    for p in params:
        if "=" not in p:
            raise click.BadParameter(f"-P expects name=value, got {p!r}")
        k, v = p.split("=", 1)
        try:
            v = json.loads(v)
        except (ValueError, json.JSONDecodeError):
            pass  # keep as string
        out[k] = v
    return out


@cli.command()
@click.option("-f", "--file", "fpath", required=True, type=click.Path(exists=True))
@click.option("-P", "--param", "params", multiple=True, help="override: name=value")
@click.option("--name", default=None, help="override run name")
@click.option("--project", default="default")
@click.option("--watch/--no-watch", default=False, help="stream logs after submit")
def run(fpath, params, name, project, watch):
    """Submit a polyaxonfile for execution. With a remote control plane
    configured (`polyaxon config set streams_url http://host:8585` or
    POLYAXON_STREAMS_URL), the operation is POSTed to the server and an
    agent there executes it — the reference's CLI↔API-server model;
    otherwise the local executor runs it in-process."""
    try:
        op = read_polyaxonfile(fpath, params=_params_to_dict(params))
    except PolyaxonfileError as e:
        raise click.ClickException(str(e))
    if name:
        op = op.model_copy(update={"name": name})

    from .. import settings

    remote_url = settings.get("streams_url")
    if remote_url:
        if op.schedule is not None or op.matrix is not None:
            # registering these locally would silently target the WRONG
            # store (the remote agent drains the server's store)
            raise click.ClickException(
                "schedules and sweeps can't be submitted to a remote control "
                "plane from the CLI yet; run them on the server host, or "
                "unset streams_url to execute locally"
            )
        from ..client import ClientError, RunClient

        client = RunClient(base_url=str(remote_url), project=project)
        try:
            uuid = client.create(op)
            click.echo(f"run {uuid[:8]} created on {remote_url}")
            if watch:
                status = client.wait(uuid, timeout=86400)
                click.echo(f"run {uuid[:8]} finished: {status}")
                click.echo(client.logs(uuid))
                if status == V1Statuses.FAILED:
                    sys.exit(1)
        except ClientError as e:
            raise click.ClickException(str(e))
        except TimeoutError as e:
            raise click.ClickException(str(e))
        return
    store = RunStore()
    if op.schedule is not None:
        from ..scheduler import ScheduleRegistry

        sid = ScheduleRegistry(store).add(op, project=project)
        click.echo(
            f"schedule {sid} registered ({op.schedule.kind}); "
            "a running agent (`polyaxon agent start`) fires it"
        )
        return
    if op.joins:
        from ..scheduler import resolve_joins

        op = resolve_joins(op, store)
    if op.matrix is not None:
        from ..tuner.driver import run_sweep

        results = run_sweep(op, store=store, project=project, base_dir=None)
        click.echo(json.dumps(results, indent=1, default=str))
        return
    try:
        compiled = compile_operation(
            op,
            project=project,
            artifacts_root=str(store.runs_dir),
            base_dir=None,
        )
    except CompilationError as e:
        raise click.ClickException(str(e))
    click.echo(f"run {compiled.run_uuid[:8]} ({compiled.name}) created")
    from ..runtime.executor import Executor

    status = Executor(store).execute(compiled)
    click.echo(f"run {compiled.run_uuid[:8]} finished: {status}")
    if status == V1Statuses.FAILED:
        click.echo(store.read_logs(compiled.run_uuid), err=True)
        sys.exit(1)
    if watch:
        click.echo(store.read_logs(compiled.run_uuid))


@cli.command()
@click.option("-f", "--file", "fpath", required=True, type=click.Path(exists=True))
def check(fpath):
    """Validate + dry-compile a polyaxonfile, print the resolved spec."""
    try:
        op = read_polyaxonfile(fpath)
        compiled = compile_operation(op, base_dir=None)
    except (PolyaxonfileError, CompilationError) as e:
        raise click.ClickException(str(e))
    click.echo(json.dumps(compiled.to_dict(), indent=1, default=str))


def _http_json(url, timeout=10.0):
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        try:
            payload = json.loads(e.read())
        except ValueError:
            payload = {}
        raise click.ClickException(
            f"{url} -> HTTP {e.code}: {payload.get('error', e.reason)}"
        )
    except (urllib.error.URLError, OSError) as e:
        raise click.ClickException(f"cannot reach {url}: {e}")


def _echo_slo(slo: dict):
    if not slo.get("enabled"):
        click.echo("slo: no objectives configured")
        return
    click.echo(
        "slo: " + ("BREACHED" if slo.get("breached") else "ok")
    )
    for s in slo.get("slos", []):
        windows = " ".join(
            f"{w}={b:.2f}x"
            for w, b in (s.get("burn_rates") or {}).items()
        )
        click.echo(
            f"  {s['name']:<20} {s.get('kind', '?'):<13} "
            f"objective={s.get('objective')}  "
            f"burn={s.get('burn_rate', 0):.2f}x "
            f"[{windows}]  bad/total={s.get('bad', 0):g}/"
            f"{s.get('total', 0):g}"
            + ("  BREACHED" if s.get("breached") else "")
        )


def _echo_trace_list(url: str, n: int, sort: str):
    data = _http_json(f"{url}/tracez?n={n}&sort={sort}")
    click.echo(
        f"traces: {data.get('retained', 0)} retained "
        f"({data.get('errors', 0)} errors kept, sort={sort})"
    )
    for t in data.get("traces", []):
        click.echo(
            f"  {t['id']:<34} {t.get('status', '?'):<18} "
            f"{t.get('dur_ms', 0):9.2f} ms  {t.get('spans', 0)} spans"
        )


@cli.command()
@click.argument("run_ref", required=False)
@click.option("--spans", "n_spans", default=12, show_default=True,
              help="recent telemetry spans to show")
@click.option("--events", "n_events", default=6, show_default=True,
              help="recent lifecycle events to show")
@click.option("--url", default=None,
              help="live server base URL (http://host:port): read /statsz "
                   "from the serving surface instead of the run store")
@click.option("--slo", "show_slo", is_flag=True,
              help="with --url: show SLO burn rates (/sloz)")
@click.option("--traces", "n_traces", default=None, type=int,
              help="with --url: list the N most recent request traces "
                   "(/tracez)")
def stats(run_ref, n_spans, n_events, url, show_slo, n_traces):
    """Live metrics and recent spans of a run, from the run store.

    Metrics fold to their latest value (training and sys.* monitor
    samples interleave in one stream); spans come from the trainer's
    telemetry export (<outputs>/telemetry/spans.jsonl). With --url the
    serving surfaces are read instead: /statsz, plus /sloz (--slo) and
    /tracez (--traces N)."""
    from ..store.local import UnknownRunError

    if url:
        url = url.rstrip("/")
        stats = _http_json(f"{url}/statsz")
        click.echo(json.dumps(
            {k: v for k, v in stats.items() if k not in ("slo", "tracing")},
            indent=1, default=str,
        ))
        tracing = stats.get("tracing") or {}
        click.echo(
            f"tracing: {'on' if tracing.get('enabled') else 'off'} "
            f"({tracing.get('retained', 0)} traces retained)"
        )
        if show_slo:
            _echo_slo(stats.get("slo") or _http_json(f"{url}/sloz"))
        if n_traces:
            _echo_trace_list(url, n_traces, "recent")
        return
    if show_slo or n_traces:
        raise click.ClickException("--slo/--traces need --url (live server)")
    if not run_ref:
        raise click.ClickException("pass a RUN_REF or --url")
    store = RunStore()
    try:
        uuid = store.resolve(run_ref)
    except UnknownRunError as e:
        raise click.ClickException(str(e.args[0]) if e.args else str(e))
    status = store.get_status(uuid)
    click.echo(f"run {uuid[:8]}  status={status.get('status', '?')}")
    # scheduler view: where a pending run sits in its queue, and what the
    # fleet has reserved (or not) for it
    meta = status.get("meta") or {}
    if status.get("status") in (V1Statuses.QUEUED, V1Statuses.SCHEDULED):
        import time as _time

        from ..scheduler.queue import RunQueue

        qname = meta.get("queue") or "default"
        entry = next(
            (
                e
                for e in RunQueue(store, name=qname).peek_all()
                if e["uuid"] == uuid
            ),
            None,
        )
        if entry is not None and entry.get("enqueued_at"):
            wait = max(0.0, _time.time() - float(entry["enqueued_at"]))
            click.echo(
                f"queued on {qname!r} for {wait:.1f}s "
                f"(priority {entry.get('priority', 0)}, "
                f"seq {entry.get('seq', '?')}, "
                f"chips {entry.get('chips', '?')})"
            )
    from ..scheduler.fleet import Fleet

    _fleet = Fleet(store)
    if _fleet.configured:
        rec = _fleet.ledger.get(uuid)
        if rec is not None:
            click.echo(
                f"reservation: {rec['chips']} chips"
                + (
                    " (block "
                    + "x".join(str(b) for b in rec["block"])
                    + ")"
                    if rec.get("block")
                    else ""
                )
                + (
                    # an elastic grant below the full ask: the expansion
                    # pass grows it back when the full block frees up
                    f" [elastic: {rec['requested_chips']} requested]"
                    if rec.get("requested_chips")
                    else ""
                )
            )
        elif status.get("status") in (V1Statuses.QUEUED, V1Statuses.SCHEDULED):
            click.echo("reservation: none yet (waiting for admission)")
    if meta.get("preempt_restarts"):
        click.echo(
            f"scheduler preemptions: {meta['preempt_restarts']} "
            "(resumed from checkpoint)"
        )
    folded: dict = {}
    step = None
    for rec in store.read_metrics(uuid):
        is_training = any(
            k not in ("step", "ts") and not k.startswith("sys.") for k in rec
        )
        for k, v in rec.items():
            if k == "step":
                if is_training and v is not None:
                    step = max(step or 0, int(v))
            elif k != "ts":
                folded[k] = v
    if folded:
        at = "" if step is None else f" (train step {step})"
        click.echo(f"\nmetrics, latest value per series{at}:")
        for k in sorted(folded):
            v = folded[k]
            val = f"{v:.6g}" if isinstance(v, (int, float)) else str(v)
            click.echo(f"  {k:<32} {val}")
    spans_path = store.outputs_dir(uuid) / "telemetry" / "spans.jsonl"
    if spans_path.exists():
        lines = spans_path.read_text().splitlines()[-max(1, n_spans):]
        click.echo(f"\nspans, last {len(lines)}:")
        for ln in lines:
            try:
                rec = json.loads(ln)
            except ValueError:
                continue
            attrs = " ".join(
                f"{k}={v}" for k, v in (rec.get("attrs") or {}).items()
            )
            indent = "  " if rec.get("parent_id") else ""
            click.echo(
                f"  {indent}{rec.get('name', '?'):<14} "
                f"{(rec.get('dur_s') or 0) * 1e3:10.3f} ms  {attrs}"
            )
    events = store.read_events(uuid)
    if events:
        click.echo(f"\nevents, last {min(max(1, n_events), len(events))}:")
        for ev in events[-max(1, n_events):]:
            body = {k: v for k, v in ev.items() if k not in ("kind", "ts")}
            click.echo(
                f"  {ev.get('kind', '?'):<20} "
                f"{json.dumps(body, default=str)[:120]}"
            )


@cli.command()
@click.argument("trace_id", required=False)
@click.option("--url", default="http://127.0.0.1:8601", show_default=True,
              help="live server base URL")
@click.option("-n", "n_traces", default=20, show_default=True,
              help="traces to list (no TRACE_ID)")
@click.option("--sort", default="recent", show_default=True,
              type=click.Choice(["recent", "slowest", "errors"]),
              help="list order (no TRACE_ID)")
@click.option("--export", "export_path", default=None,
              type=click.Path(dir_okay=False, writable=True),
              help="dump the ring's retained traces (full span "
                   "timelines) as JSONL to this file for offline "
                   "analysis, newest first")
def trace(trace_id, url, n_traces, sort, export_path):
    """Inspect a serving request trace (GET /tracez).

    Without TRACE_ID, lists retained traces (tail-sampled: errors and
    the slowest requests are always kept). With a TRACE_ID — the value
    of a response's X-Request-Id header — prints its span timeline.
    With --export FILE, every listed trace is fetched in full and
    written as one JSON object per line."""
    url = url.rstrip("/")
    if export_path:
        listing = _http_json(f"{url}/tracez?n={n_traces}&sort={sort}")
        count = 0
        with open(export_path, "w") as f:
            for t in listing.get("traces", []):
                full = _http_json(f"{url}/tracez?id={t['id']}")
                f.write(json.dumps(full, default=str) + "\n")
                count += 1
        click.echo(f"exported {count} traces to {export_path}")
        return
    if not trace_id:
        _echo_trace_list(url, n_traces, sort)
        return
    t = _http_json(f"{url}/tracez?id={trace_id}")
    click.echo(
        f"trace {t['id']}  status={t.get('status', '?')}  "
        f"{t.get('dur_ms', 0):.2f} ms"
        + (f"  error={t['error']}" if t.get("error") else "")
    )
    for k, v in (t.get("attrs") or {}).items():
        click.echo(f"  {k}={v}")
    for s in t.get("spans", []):
        attrs = " ".join(
            f"{k}={v}" for k, v in (s.get("attrs") or {}).items()
        )
        click.echo(
            f"  {s.get('start_s', 0) * 1e3:9.3f} ms  "
            f"{s.get('name', '?'):<14} "
            f"{s.get('dur_s', 0) * 1e3:9.3f} ms  {attrs}"
        )


@cli.command()
@click.argument("series", required=False)
@click.option("--url", default="http://127.0.0.1:8601", show_default=True,
              help="base URL of any /queryz surface (serving server, "
                   "router, streams server)")
@click.option("--since", default=None, type=float,
              help="window start (server-clock seconds)")
@click.option("--until", default=None, type=float,
              help="window end (server-clock seconds)")
@click.option("--last", default=None, type=float,
              help="query the trailing N seconds (instead of --since)")
@click.option("--step", default=None, type=float,
              help="aggregation step, seconds (default: one window)")
@click.option("--agg", default="avg", show_default=True,
              type=click.Choice(
                  ["avg", "min", "max", "rate", "p50", "p95", "p99"]
              ))
@click.option("--json", "as_json", is_flag=True,
              help="print the raw /queryz payload")
def query(series, url, since, until, last, step, agg, as_json):
    """Query the metrics history of a live server (GET /queryz).

    Without SERIES, lists what the server's history store holds. With
    one, prints aggregated points over the window — `rate` is counter-
    reset aware (a replica restart is annotated, never a negative
    rate)."""
    url = url.rstrip("/")
    if not series:
        data = _http_json(f"{url}/queryz")
        click.echo(
            f"history: {data.get('bytes', 0)} bytes, "
            f"{len(data.get('series', []))} series"
        )
        for name in data.get("series", []):
            click.echo(f"  {name}")
        return
    params = {"series": series, "agg": agg}
    for k, v in (("since", since), ("until", until),
                 ("last", last), ("step", step)):
        if v is not None:
            params[k] = v
    from urllib.parse import urlencode

    data = _http_json(f"{url}/queryz?{urlencode(params)}")
    if as_json:
        click.echo(json.dumps(data, indent=1, default=str))
        return
    click.echo(
        f"{data['series']}  agg={data['agg']}  "
        f"samples={data.get('samples', 0)}"
        + (f"  resets={data['resets']}" if data.get("resets") else "")
    )
    for t, v in data.get("points", []):
        click.echo(
            f"  {t:14.3f}  " + ("-" if v is None else f"{v:.6g}")
        )


@cli.group()
def perf():
    """Performance history tools (metrics history + bench records)."""


#: bench-record field → (history series, aggregation) used by
#: `perf diff` when no explicit --map is given
_PERF_DIFF_DEFAULT_MAP = {
    # serving.ttft_ms is a histogram series: percentile aggs only
    "ttft_ms": ("serving.ttft_ms", "p95"),
}


@perf.command("diff")
@click.argument("bench_file", type=click.Path(exists=True, dir_okay=False))
@click.option("--url", default="http://127.0.0.1:8601", show_default=True,
              help="live /queryz surface to read the current window from")
@click.option("--last", default=300.0, show_default=True, type=float,
              help="live window length, seconds")
@click.option("--map", "mappings", multiple=True,
              help="bench_field=series[:agg] (repeatable; replaces the "
                   "default ttft_ms=serving.ttft_ms:p95)")
@click.option("--tolerance", default=None, type=float,
              help="fail (exit 1) when live > bench*(1+TOLERANCE) on "
                   "any compared field; omit for report-only")
def perf_diff(bench_file, url, last, mappings, tolerance):
    """Diff a live history window against a record file: one JSON
    object whose `tail` string holds JSON lines.

    The record's tail is scanned for each mapped field
    (last record carrying it wins), the live side is the /queryz
    aggregate over the trailing --last seconds, and the drift is
    printed per field. With --tolerance the command gates: any field
    where live exceeds the bench value by more than the tolerance
    fraction fails the diff (lower-is-better fields like latencies)."""
    url = url.rstrip("/")
    with open(bench_file) as f:
        record = json.load(f)
    bench: dict = {}
    for line in (record.get("tail") or "").splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict):
            for k, v in rec.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    bench[k] = float(v)
    fmap = dict(_PERF_DIFF_DEFAULT_MAP)
    if mappings:
        fmap = {}
        for m in mappings:
            field, _, target = m.partition("=")
            if not target:
                raise click.ClickException(
                    f"--map wants bench_field=series[:agg], got {m!r}"
                )
            series, _, agg = target.partition(":")
            fmap[field] = (series, agg or "avg")
    from urllib.parse import urlencode

    compared, failed = 0, []
    for field, (series, agg) in sorted(fmap.items()):
        if field not in bench:
            click.echo(f"  {field:<16} not in bench record, skipped")
            continue
        q = urlencode(
            {"series": series, "agg": agg, "last": last, "step": last}
        )
        data = _http_json(f"{url}/queryz?{q}")
        live = next(
            (v for _, v in reversed(data.get("points", []))
             if v is not None),
            None,
        )
        if live is None:
            click.echo(
                f"  {field:<16} bench={bench[field]:.4g}  live=EMPTY "
                f"({series}:{agg} has no samples in the window)"
            )
            continue
        compared += 1
        drift = (live - bench[field]) / bench[field] if bench[field] else 0.0
        worse = (
            tolerance is not None
            and live > bench[field] * (1.0 + tolerance)
        )
        click.echo(
            f"  {field:<16} bench={bench[field]:.4g}  live={live:.4g}  "
            f"drift={drift:+.1%}" + ("  REGRESSED" if worse else "")
        )
        if worse:
            failed.append(field)
    if not compared:
        raise click.ClickException(
            "nothing compared: no mapped field present in both the "
            "bench record and the live history"
        )
    if failed:
        raise click.ClickException(
            f"perf diff failed tolerance {tolerance:+.0%}: "
            + ", ".join(failed)
        )
    click.echo(f"compared {compared} field(s): ok")


class _RunRefGroup(click.Group):
    """Unknown run refs surface as clean CLI errors, not the store's raw
    traceback — every ops subcommand resolves a uid. Only the dedicated
    UnknownRunError is caught: an unrelated KeyError is a real bug and
    must keep its traceback."""

    def invoke(self, ctx):
        from ..client import ClientError
        from ..store.local import UnknownRunError

        try:
            return super().invoke(ctx)
        except UnknownRunError as e:
            # str(KeyError) is repr(msg) — args[0] is the clean message
            raise click.ClickException(str(e.args[0]) if e.args else str(e))
        except ClientError as e:  # remote control plane: 404s etc.
            raise click.ClickException(str(e))


@cli.group(cls=_RunRefGroup)
def ops():
    """Inspect and manage runs (remote when streams_url is configured)."""


def _run_client():
    """Local RunClient, or HTTP when a remote control plane is configured
    (POLYAXON_STREAMS_URL / `polyaxon config set streams_url ...`)."""
    from .. import settings
    from ..client import RunClient

    url = settings.get("streams_url")
    return RunClient(base_url=str(url)) if url else RunClient()


@ops.command("ls")
@click.option("--project", default=None)
@click.option("--sweep", "sweep_ref", default=None,
              help="only this sweep's trial runs (lineage from run meta)")
def ops_ls(project, sweep_ref):
    client = _run_client()
    rows = client.list(project)
    if sweep_ref:
        # resolve via a status fetch: works identically for the local
        # store and the HTTP transport (the server resolves short refs)
        sweep_uuid = client.get(sweep_ref).get("uuid") or sweep_ref
        kept = []
        for r in rows:
            meta = r.get("meta") or {}  # listings carry meta — no N+1
            if meta.get("sweep") == sweep_uuid:
                kept.append({**r, "iteration": meta.get("iteration")})
        rows = kept
    if not rows:
        click.echo("no runs")
        return
    for r in rows:
        line = (
            f"{r['uuid'][:8]}  {r.get('status', '?'):<12} "
            f"{r.get('project', ''):<12} {r.get('name', '')}"
        )
        if sweep_ref:
            line += f"  [iter {r.get('iteration')}]"
        click.echo(line)


@ops.command("get")
@click.option("-uid", "--uid", required=True)
def ops_get(uid):
    client = _run_client()
    out = {
        "status": client.get(uid),
        "metrics_tail": client.metrics(uid)[-5:],
    }
    if client._http is None:  # spec only stored locally
        out["spec"] = client.store.read_spec(client.store.resolve(uid))
    click.echo(json.dumps(out, indent=1, default=str))


@ops.command("logs")
@click.option("-uid", "--uid", required=True)
@click.option("--follow/--no-follow", default=False)
def ops_logs(uid, follow):
    from .. import settings

    if settings.get("streams_url"):
        client = _run_client()
        if not follow:
            click.echo(client.logs(uid), nl=False)
            return
        import time as _time

        from ..schemas.lifecycle import DONE_STATUSES

        offset = 0
        while True:  # poll the offset endpoint — the remote tail loop
            chunk = client.logs(uid, offset=offset)
            if chunk:
                click.echo(chunk, nl=False)
                offset += len(chunk)
            if client.get(uid).get("status") in DONE_STATUSES:
                return
            _time.sleep(1.0)
    store = RunStore()
    uid = store.resolve(uid)
    if follow:
        for chunk in store.watch_logs(uid):
            click.echo(chunk, nl=False)
    else:
        click.echo(store.read_logs(uid), nl=False)


@ops.command("statuses")
@click.option("-uid", "--uid", required=True)
def ops_statuses(uid):
    for c in _run_client().statuses(uid):
        click.echo(f"{c.get('ts', 0):.3f}  {c['type']:<12} {c.get('reason', '')}")


@ops.command("metrics")
@click.option("-uid", "--uid", required=True)
def ops_metrics(uid):
    for m in _run_client().metrics(uid):
        click.echo(json.dumps(m))


@ops.command("compare")
@click.option("-uid", "--uid", "uids", multiple=True, required=True,
              help="repeat for each run (2+)")
def ops_compare(uids):
    """Side-by-side final metrics and params of two or more runs."""
    if len(uids) < 2:
        raise click.ClickException("compare needs at least two --uid")
    client = _run_client()
    cols = []
    for uid in uids:
        status = client.get(uid)
        # fold last-value-per-key across ALL metric lines: system monitors
        # interleave sys.* samples into the same stream, so the final line
        # alone often carries no training metrics at all. The step column
        # folds only from TRAINING records (ones carrying a non-sys metric)
        # — monitor records use their own sample counter as `step`.
        folded: dict = {}
        step = None
        for rec in client.metrics(uid):
            is_training = any(
                k not in ("step", "ts") and not k.startswith("sys.")
                for k in rec
            )
            for k, v in rec.items():
                if k == "step":
                    if is_training and v is not None:
                        step = max(step or 0, int(v))
                elif k != "ts":
                    folded[k] = v
        spec = client.spec(uid)
        cols.append({
            "uid": status.get("uuid", uid)[:8],
            "status": str(status.get("status", "?")),
            "params": spec.get("params") or {},
            "metrics": folded,
            "step": step,
        })
    rows = sorted({k for c in cols for k in c["metrics"]})
    pkeys = sorted({k for c in cols for k in c["params"]})
    header = ["", *[c["uid"] for c in cols]]
    table = [header, ["status", *[c["status"] for c in cols]],
             ["step", *["—" if c["step"] is None else str(c["step"])
                        for c in cols]]]
    for k in pkeys:
        table.append(
            [f"param.{k}", *[str(c["params"].get(k, "—")) for c in cols]]
        )
    for k in rows:
        table.append([
            k,
            *[
                f"{c['metrics'][k]:.6g}" if k in c["metrics"] else "—"
                for c in cols
            ],
        ])
    widths = [max(len(r[i]) for r in table) for i in range(len(header))]
    for r in table:
        click.echo("  ".join(x.ljust(w) for x, w in zip(r, widths)))


@ops.command("artifacts")
@click.option("-uid", "--uid", required=True)
@click.option("--path", default=None, help="artifact path to download (omit to list)")
@click.option("-o", "--output", default=".", help="download destination dir")
def ops_artifacts(uid, path, output):
    """List a run's output artifacts, or download one with --path
    (remote when streams_url is configured)."""
    from pathlib import Path as _Path

    client = _run_client()
    if path is None:
        files = client.artifacts(uid)
        if not files:
            click.echo("no artifacts")
        for f in files:
            click.echo(f)
        return
    dst = client.download_artifact(uid, path, _Path(output) / _Path(path).name)
    click.echo(str(dst))


@ops.command("stop")
@click.option("-uid", "--uid", required=True)
def ops_stop(uid):
    client = _run_client()
    client.stop(uid)
    status = client.get(uid).get("status", "stopping")
    click.echo(f"{uid[:8]} {status}")


@ops.command("delete")
@click.option("-uid", "--uid", required=True)
@click.option("--yes", is_flag=True, help="skip confirmation")
@click.option("--cascade", is_flag=True,
              help="sweeps: also delete their trial runs")
def ops_delete(uid, yes, cascade):
    """Delete a finished run's data (metrics, logs, outputs) permanently."""
    if not yes:
        click.confirm(f"permanently delete run {uid[:8]}?", abort=True)
    try:
        _run_client().delete(uid, cascade=cascade)
    except ValueError as e:  # clone-target guard; group catches ClientError
        raise click.ClickException(str(e))
    click.echo(f"{uid[:8]} deleted")


def _clone_cmd(uid, kind, eager):
    from ..client import RunClient
    from ..compiler.resolver import CompilationError

    client = RunClient()
    try:
        new_uuid = getattr(client, kind)(uid, queue=not eager)
    except CompilationError as e:  # group catches ClientError
        raise click.ClickException(str(e))
    status = client.get(new_uuid).get("status", "queued")
    click.echo(f"{kind} of {uid[:8]} -> run {new_uuid[:8]} ({status})")


@ops.command("restart")
@click.option("-uid", "--uid", required=True)
@click.option("--eager/--queue", default=True, help="run now vs enqueue for an agent")
def ops_restart(uid, eager):
    """Fresh run from the source run's resolved spec."""
    _clone_cmd(uid, "restart", eager)


@ops.command("resume")
@click.option("-uid", "--uid", required=True)
@click.option("--eager/--queue", default=True)
def ops_resume(uid, eager):
    """Continue training from the source run's latest checkpoint."""
    _clone_cmd(uid, "resume", eager)


@ops.command("copy")
@click.option("-uid", "--uid", required=True)
@click.option("--eager/--queue", default=True)
def ops_copy(uid, eager):
    """New run seeded with a copy of the source outputs."""
    _clone_cmd(uid, "copy", eager)


@cli.group()
def streams():
    """Log/metric/event/artifact streaming service."""


@streams.command("start")
@click.option("--host", default="127.0.0.1")
@click.option("--port", default=8585, type=int)
@click.option("--federate", "federate_specs", multiple=True,
              metavar="SLUG=URL",
              help="sibling registry to federate on /metricsz "
                   "(repeatable), e.g. agent=http://127.0.0.1:9090")
def streams_start(host, port, federate_specs):
    """Serve the run store over HTTP (logs/metrics/events/artifacts)."""
    from ..streams import serve

    sources: dict[str, str] = {}
    for spec in federate_specs:
        slug, sep, src_url = spec.partition("=")
        if not sep or not slug or not src_url:
            raise click.ClickException(
                f"--federate takes SLUG=URL, got {spec!r}"
            )
        sources[slug] = src_url
    serve(RunStore(), host=host, port=port, federate=sources or None)


@cli.group()
def agent():
    """Cluster-side executor: drains the run queue."""


@agent.command("start")
@click.option("--poll-interval", default=1.0, type=float)
@click.option("--queue", "queues", multiple=True,
              help="only drain these queues (repeatable); default: all")
@click.option("--cluster/--local", "use_cluster", default=False,
              help="submit runs to k8s via kubectl instead of executing "
                   "in-process; the serve loop then reconciles pod phases")
@click.option("--namespace", default="polyaxon", show_default=True)
@click.option("--context", "kube_context", default=None,
              help="kubeconfig context for --cluster")
@click.option("--kube-dry-run", is_flag=True, default=False,
              help="validate manifests with kubectl --dry-run=client "
                   "instead of really submitting")
def agent_start(poll_interval, queues, use_cluster, namespace, kube_context,
                kube_dry_run):
    from ..scheduler import Agent

    store = RunStore()
    which = ", ".join(queues) if queues else "all queues"
    kwargs = {}
    if use_cluster:
        from ..k8s.cluster import KubectlCluster
        from ..scheduler.reconciler import ClusterSubmitter

        cluster = KubectlCluster(
            namespace=namespace, context=kube_context, dry_run=kube_dry_run
        )
        kwargs["submit_fn"] = ClusterSubmitter(
            store, cluster, namespace=namespace
        )
        click.echo(f"cluster mode: kubectl -n {namespace}"
                   + (" (dry-run)" if kube_dry_run else ""))
    click.echo(f"agent started; polling {which} (ctrl-c to stop)")
    Agent(store=store, queues=list(queues) or None, **kwargs).serve(
        poll_interval=poll_interval
    )


@agent.command("drain")
@click.option("--queue", "queues", multiple=True)
def agent_drain(queues):
    """Process everything queued, then exit."""
    from ..scheduler import Agent

    n = Agent(store=RunStore(), queues=list(queues) or None).drain()
    click.echo(f"processed {n} run(s)")


@cli.command()
@click.option("-uid", "--uid", required=True, help="run to serve (uuid/prefix/name)")
@click.option("--host", default="127.0.0.1")
@click.option("--port", default=8601, type=int)
@click.option("--mesh", default=None,
              help="shard params over a device mesh, e.g. model=4 or "
                   "model=2,fsdp=2 — for models too big for one chip")
@click.option("--max-batch", default=None, type=int,
              help="coalesce up to N compatible requests into one decode "
                   "(continuous batching; default 8)")
@click.option("--max-wait-ms", default=None, type=float,
              help="how long a partial batch waits for stragglers "
                   "(default 5.0)")
@click.option("--buckets", default=None,
              help="prompt-length bucket ladder, e.g. 32,64,128,256 "
                   "(default: geometric ladder up to the model's seq_len)")
@click.option("--no-batching", is_flag=True,
              help="disable bucketing+coalescing: one exact-shape compile "
                   "per request signature (debug/baseline mode)")
@click.option("--max-queue", default=None, type=int,
              help="admission bound: shed (503) when this many requests "
                   "are queued or in flight (default 64)")
@click.option("--default-deadline-ms", default=None, type=float,
              help="deadline budget applied to requests that carry no "
                   "deadlineMs of their own (default: none)")
@click.option("--drain-grace-s", default=None, type=float,
              help="on SIGTERM/stop, finish in-flight work for up to this "
                   "many seconds before failing the rest (default 5.0)")
@click.option("--breaker-threshold", default=None, type=int,
              help="consecutive decode failures that trip the circuit "
                   "breaker (default 5)")
@click.option("--expected-devices", default=None, type=int,
              help="wire slice health into /readyz: report not-ready when "
                   "fewer than N devices respond")
@click.option("--kv-pool-pages", default=None, type=int,
              help="size of the block-paged KV pool in pages: admission "
                   "reserves pages instead of worst-case rows, prompt "
                   "prefixes are cached across requests, and decode "
                   "streams (default: off — dense per-group caches)")
@click.option("--kv-page-tokens", default=None, type=int,
              help="KV page granularity in tokens (default 128)")
@click.option("--no-prefix-cache", is_flag=True,
              help="disable cross-request prefix KV reuse (paged pool only)")
@click.option("--no-stream", is_flag=True,
              help="disable POST /generate?stream=1 incremental delivery")
@click.option("--speculate", is_flag=True,
              help="self-speculative decoding: draft tokens from a per-row "
                   "n-gram index and verify them in one batched window — "
                   "outputs stay byte-identical to plain decode")
@click.option("--draft-tokens", default=None, type=int,
              help="drafts per speculative verify window (default 4; "
                   "higher pays off only at high accept rates)")
@click.option("--quantize", is_flag=True,
              help="int8 weight-only quantize the projection kernels at "
                   "load (per-output-channel scales; prefill/embed/lm_head "
                   "stay full precision)")
@click.option("--draft-model", default=None, type=str,
              help="swap the n-gram proposer for a real small draft model: "
                   "k=v,k=v config overrides (e.g. n_layers=2), or 'auto' "
                   "for the default half-depth truncation (requires "
                   "--speculate)")
@click.option("--adaptive-draft", is_flag=True,
              help="steer the speculative draft width K from the live "
                   "accept rate: ramp on copy-friendly traffic, shrink on "
                   "high-entropy, auto-disable + reprobe when speculation "
                   "loses (requires --speculate)")
@click.option("--kv-quant", default=None,
              type=click.Choice(["none", "int8"]),
              help="store the paged KV pool int8-per-slot with f32 scales "
                   "(~2x resident rows per HBM byte; requires "
                   "--kv-pool-pages)")
@click.option("--chunked-prefill", is_flag=True,
              help="slice prompt prefill into bounded chunks interleaved "
                   "with decode steps so short requests are not stuck "
                   "behind long prompts (requires --kv-pool-pages)")
@click.option("--no-chunked-prefill", is_flag=True,
              help="force chunked prefill off even when the run spec "
                   "pins chunkedPrefill: true")
@click.option("--prefill-chunk-tokens", default=None, type=int,
              help="prompt tokens prefilled per device step when chunked "
                   "prefill is on (default 64)")
@click.option("--max-step-tokens", default=None, type=int,
              help="token budget one device step may touch: all decode "
                   "rows plus at most one prefill slice (default 256)")
@click.option("--spill-ram-bytes", default=None, type=int,
              help="host-RAM budget for evicted prefix-cache entries: a "
                   "later hit restores the pages instead of re-prefilling "
                   "(requires --kv-pool-pages with the prefix cache)")
@click.option("--spill-dir", default=None, type=str,
              help="directory for the on-disk spill tier below the RAM "
                   "tier (CRC-framed segments; torn tails truncated, "
                   "corrupt segments quarantined at startup)")
@click.option("--spill-dir-bytes", default=None, type=int,
              help="byte budget for the on-disk spill tier (oldest "
                   "segments dropped first; requires --spill-dir)")
@click.option("--adapter", "adapter_specs", multiple=True,
              metavar="NAME=SOURCE",
              help="register a named LoRA adapter to multiplex against "
                   "the base model (repeatable): SOURCE is a .npz saved "
                   "by serving.adapters.save_adapter, or seed:<int> for "
                   "a synthetic adapter; requires a loraRank checkpoint")
@click.option("--tenant-quota", "tenant_specs", multiple=True,
              metavar="NAME=OUT:TOK:WEIGHT:ADAPTER",
              help="per-tenant admission contract (repeatable): cap on "
                   "outstanding requests, cap on outstanding tokens, "
                   "fair-share weight, bound adapter name — any field "
                   "may be left empty, e.g. acme=8::2.0:acme")
@click.option("--adapter-slots", default=None, type=int,
              help="device-resident adapter slots beyond the "
                   "checkpoint's own slot 0 (default: one per adapter; "
                   "fewer slots LRU-evict idle adapters through the "
                   "spill tiers and restore them on request)")
@click.option("--no-affinity", is_flag=True,
              help="router mode: disable prefix-affinity routing (warm "
                   "prompts no longer stick to the replica holding their "
                   "prefix KV)")
@click.option("--no-trace", is_flag=True,
              help="disable per-request tracing (/tracez and X-Request-Id "
                   "correlation stay, but no span timelines are recorded)")
@click.option("--replicas", default=None, type=int,
              help="run N replica processes as a fleet-placed gang behind "
                   "the router (default: the run spec's serving.replicas, "
                   "else 1)")
@click.option("--role", default=None,
              type=click.Choice(["both", "prefill", "decode"]),
              help="serving role for this replica: 'prefill' runs only "
                   "chunked-prefill steps and live-hands the KV page set "
                   "to a decode replica over POST /kv_import (requires "
                   "--chunked-prefill + --kv-pool-pages + prefix cache); "
                   "'decode' advertises itself as an adoption target; "
                   "'both' (default) is the monolithic server")
@click.option("--pools", default=None, metavar="PREFILL:DECODE",
              help="fleet mode: disaggregate into PREFILL prefill-only "
                   "replicas plus DECODE decode replicas behind the "
                   "router (implies --route; default: the run spec's "
                   "serving.pools)")
@click.option("--mesh-model", default=None, type=int,
              help="shorthand for --mesh model=N: tensor-parallel the "
                   "projection kernels over N chips per replica")
@click.option("--route", is_flag=True,
              help="front the replica(s) with the JSQ/P2C router "
                   "(serving/router.py): health checks, shed retry on a "
                   "sibling, rolling redeploy without an outage")
@click.option("--autoscale-max", default=None, type=int,
              help="router mode: scale replicas up to N on shed burn, "
                   "back down when calm (default: fixed replica count)")
def serve(uid, host, port, mesh, max_batch, max_wait_ms, buckets, no_batching,
          max_queue, default_deadline_ms, drain_grace_s, breaker_threshold,
          expected_devices, kv_pool_pages, kv_page_tokens, no_prefix_cache,
          no_stream, speculate, draft_tokens, quantize, draft_model,
          adaptive_draft, kv_quant, chunked_prefill,
          no_chunked_prefill, prefill_chunk_tokens, max_step_tokens,
          spill_ram_bytes, spill_dir, spill_dir_bytes, adapter_specs,
          tenant_specs, adapter_slots, no_affinity,
          no_trace, replicas, role, pools, mesh_model, route, autoscale_max):
    """Serve a checkpointed LM run's generation over HTTP
    (GET /healthz, GET /readyz, GET /statsz, POST /generate)."""
    from ..serving import ModelServer
    from ..serving.server import ServingError
    from ..utils.jax_platform import PlatformMismatchError

    mesh_axes = None
    if mesh:
        try:
            mesh_axes = {
                k.strip(): int(v)
                for k, v in (part.split("=", 1) for part in mesh.split(","))
            }
        except ValueError:
            raise click.ClickException(
                f"--mesh expects axis=N[,axis=N...], got {mesh!r}"
            )
    if mesh_model is not None:
        mesh_axes = {**(mesh_axes or {}), "model": mesh_model}
    # pass only the flags actually given: they layer over the run spec's
    # own `serving:` section (if any), which supplies every other knob
    overrides = {}
    if buckets:
        try:
            overrides["prompt_buckets"] = tuple(
                int(b) for b in buckets.split(",")
            )
        except ValueError:
            raise click.ClickException(
                f"--buckets expects N,N,... ints, got {buckets!r}"
            )
    if no_batching:
        overrides["batching"] = False
    if no_prefix_cache:
        overrides["prefix_cache"] = False
    if no_stream:
        overrides["stream"] = False
    if speculate:
        overrides["speculate"] = True
    if quantize:
        overrides["quantize"] = True
    if draft_model is not None:
        from ..serving.batching import normalize_draft_model

        if draft_model.strip().lower() == "auto":
            spec = {}
        else:
            try:
                spec = {}
                for part in draft_model.split(","):
                    k, v = part.split("=", 1)
                    try:
                        spec[k.strip()] = int(v)
                    except ValueError:
                        spec[k.strip()] = float(v)
            except ValueError:
                raise click.ClickException(
                    f"--draft-model expects 'auto' or k=v[,k=v...] numeric "
                    f"overrides, got {draft_model!r}"
                )
        overrides["draft_model"] = normalize_draft_model(spec)
    if adaptive_draft:
        overrides["adaptive_draft"] = True
    if kv_quant is not None:
        overrides["kv_quant"] = kv_quant
    if chunked_prefill and no_chunked_prefill:
        raise click.ClickException(
            "--chunked-prefill and --no-chunked-prefill are exclusive"
        )
    if chunked_prefill:
        overrides["chunked_prefill"] = True
    if no_chunked_prefill:
        overrides["chunked_prefill"] = False
    if no_trace:
        overrides["trace"] = False
    if adapter_specs:
        from ..serving.tenancy import normalize_adapters

        amap = {}
        for spec in adapter_specs:
            name, sep, src = spec.partition("=")
            if not sep or not name.strip() or not src.strip():
                raise click.ClickException(
                    f"--adapter expects NAME=SOURCE, got {spec!r}"
                )
            amap[name.strip()] = src.strip()
        try:
            overrides["adapters"] = normalize_adapters(amap)
        except ValueError as e:
            raise click.ClickException(str(e))
    if tenant_specs:
        from ..serving.tenancy import normalize_tenants

        rows = []
        for spec in tenant_specs:
            name, _, rest = spec.partition("=")
            if not name.strip():
                raise click.ClickException(
                    f"--tenant-quota expects NAME=OUT:TOK:WEIGHT:ADAPTER "
                    f"(fields optional), got {spec!r}"
                )
            fields = (rest.split(":") + [""] * 4)[:4]
            row = {"name": name.strip()}
            try:
                if fields[0].strip():
                    row["max_outstanding"] = int(fields[0])
                if fields[1].strip():
                    row["max_tokens"] = int(fields[1])
                if fields[2].strip():
                    row["weight"] = float(fields[2])
            except ValueError:
                raise click.ClickException(
                    f"--tenant-quota {spec!r}: OUT/TOK are ints, WEIGHT "
                    f"is a float"
                )
            if fields[3].strip():
                row["adapter"] = fields[3].strip()
            rows.append(row)
        try:
            overrides["tenants"] = normalize_tenants(rows)
        except ValueError as e:
            raise click.ClickException(str(e))
    if adapter_slots is not None:
        overrides["adapter_slots"] = adapter_slots
    for field, value in (
        ("max_batch", max_batch),
        ("max_wait_ms", max_wait_ms),
        ("max_queue", max_queue),
        ("default_deadline_ms", default_deadline_ms),
        ("drain_grace_s", drain_grace_s),
        ("breaker_threshold", breaker_threshold),
        ("kv_pool_pages", kv_pool_pages),
        ("kv_page_tokens", kv_page_tokens),
        ("draft_tokens", draft_tokens),
        ("prefill_chunk_tokens", prefill_chunk_tokens),
        ("max_step_tokens", max_step_tokens),
        ("spill_ram_bytes", spill_ram_bytes),
        ("spill_dir", spill_dir),
        ("spill_dir_bytes", spill_dir_bytes),
        ("role", role),
    ):
        if value is not None:
            overrides[field] = value
    pool_counts = None
    if pools:
        try:
            p, _, d = pools.partition(":")
            pool_counts = (int(p), int(d))
            if min(pool_counts) < 0 or sum(pool_counts) < 1:
                raise ValueError
        except ValueError:
            raise click.ClickException(
                f"--pools expects PREFILL:DECODE counts, got {pools!r}"
            )
    # a run whose spec declares serving.pools must come up disaggregated
    # without any CLI opt-in — `serve --uid` promises the shape the spec
    # pinned, and a silently-monolithic pooled run honors neither role
    spec_wants_pools = (
        pool_counts is None and not route and (replicas or 0) <= 1
        and role is None and _run_spec_pools(uid) is not None
    )
    if route or (replicas or 0) > 1 or pool_counts is not None \
            or spec_wants_pools:
        _serve_fleet(
            uid, host, port,
            replicas=replicas,
            mesh_axes=mesh_axes,
            overrides=overrides,
            expected_devices=expected_devices,
            autoscale_max=autoscale_max,
            no_affinity=no_affinity,
            pools=pool_counts,
        )
        return
    try:
        server = ModelServer.from_run(uid, mesh_axes=mesh_axes,
                                      config_overrides=overrides or None,
                                      expected_devices=expected_devices)
    except (ServingError, KeyError, ValueError, PlatformMismatchError) as e:
        # ValueError: mesh-vs-device/model mismatch from the mesh builder
        raise click.ClickException(str(e.args[0]) if e.args else str(e))
    bound = server.start(host=host, port=port)
    mode = (
        f"batching max_batch={server.config.max_batch} "
        f"max_wait_ms={server.config.max_wait_ms}"
        if server.config.batching
        else "per-request (no batching)"
    )
    if server.config.batching and server.config.kv_pool_pages:
        mode += (
            f" kv_pool={server.config.kv_pool_pages}x"
            f"{server.config.kv_page_tokens}tok"
        )
    dev = server.device_info()
    click.echo(
        f"serving {server.model_name} (step {server.step}) "
        f"on http://{host}:{bound} [{mode}] "
        f"[{dev['platform']}:{dev['device_kind']} ids={dev['device_ids']} "
        f"attention={dev.get('attention_backend')}] — "
        "POST /generate, GET /healthz, GET /readyz, GET /statsz, "
        "GET /tracez, GET /sloz"
    )
    import signal
    import threading

    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    try:
        stop.wait()
    finally:
        # graceful drain: /readyz flips to 503 and admission closes
        # immediately; in-flight work gets drain_grace_s to finish
        click.echo("draining...")
        server.stop()


# override-field → CLI flag spelling, for replica child processes
_SERVE_FLAG_SPELLING = {
    "max_batch": "--max-batch",
    "max_wait_ms": "--max-wait-ms",
    "max_queue": "--max-queue",
    "default_deadline_ms": "--default-deadline-ms",
    "drain_grace_s": "--drain-grace-s",
    "breaker_threshold": "--breaker-threshold",
    "kv_pool_pages": "--kv-pool-pages",
    "kv_page_tokens": "--kv-page-tokens",
    "draft_tokens": "--draft-tokens",
    "kv_quant": "--kv-quant",
    "prefill_chunk_tokens": "--prefill-chunk-tokens",
    "max_step_tokens": "--max-step-tokens",
    "spill_ram_bytes": "--spill-ram-bytes",
    "spill_dir_bytes": "--spill-dir-bytes",
    "adapter_slots": "--adapter-slots",
    "role": "--role",
}


def _serve_child_argv(uid, port, mesh_axes, overrides, expected_devices):
    """The single-replica `polyaxon serve` command line a replica child
    runs — the SAME code path as one-replica serving, so fleet mode adds
    no second serving implementation."""
    argv = [sys.executable, "-m", "polyaxon_tpu.cli.main", "serve",
            "-uid", uid, "--host", "127.0.0.1", "--port", str(port)]
    if mesh_axes:
        argv += ["--mesh", ",".join(f"{k}={v}" for k, v in mesh_axes.items())]
    if expected_devices is not None:
        argv += ["--expected-devices", str(expected_devices)]
    for field, value in (overrides or {}).items():
        if field == "prompt_buckets":
            argv += ["--buckets", ",".join(str(b) for b in value)]
        elif field == "batching" and value is False:
            argv += ["--no-batching"]
        elif field == "prefix_cache" and value is False:
            argv += ["--no-prefix-cache"]
        elif field == "stream" and value is False:
            argv += ["--no-stream"]
        elif field == "trace" and value is False:
            argv += ["--no-trace"]
        elif field in ("speculate", "quantize") and value:
            argv += [f"--{field}"]
        elif field == "adaptive_draft" and value:
            argv += ["--adaptive-draft"]
        elif field == "draft_model" and value is not None:
            argv += ["--draft-model",
                     ",".join(f"{k}={v}" for k, v in value) or "auto"]
        elif field == "chunked_prefill":
            argv += ["--chunked-prefill" if value else "--no-chunked-prefill"]
        elif field == "spill_dir" and value:
            # each replica child gets its own segment namespace: two
            # processes writing one spill dir would collide on seq names
            argv += ["--spill-dir", str(Path(value) / f"r{port}")]
        elif field == "adapters":
            for name, src in value:
                argv += ["--adapter", f"{name}={src}"]
        elif field == "tenants":
            for pairs in value:
                d = dict(pairs)
                out = d.get("max_outstanding")
                tok = d.get("max_tokens")
                argv += ["--tenant-quota",
                         f"{d['name']}"
                         f"={'' if out is None else out}"
                         f":{'' if tok is None else tok}"
                         f":{d.get('weight', 1.0)}"
                         f":{d.get('adapter', '')}"]
        elif field in _SERVE_FLAG_SPELLING:
            argv += [_SERVE_FLAG_SPELLING[field], str(value)]
    return argv


def _run_spec_pools(uid):
    """(prefill, decode) from the run spec's serving.pools, or None —
    unresolved uids and template-valued counts fall through to the
    monolithic path, whose own error reporting is better placed."""
    try:
        from ..schemas.run_kinds import V1JAXJob

        store = RunStore()
        run = (
            store.read_spec(store.resolve(uid)).get("component") or {}
        ).get("run") or {}
        if run.get("kind") != "jaxjob" or not run.get("program"):
            return None
        spec = V1JAXJob.model_validate(run).program.serving
        ps = spec.pools if spec is not None else None
        if ps is None or not (
            isinstance(ps.prefill, int) and isinstance(ps.decode, int)
        ):
            return None
        return (int(ps.prefill), int(ps.decode))
    except Exception:
        return None


def _serve_fleet(uid, host, port, *, replicas, mesh_axes, overrides,
                 expected_devices, autoscale_max, no_affinity=False,
                 pools=None):
    """`polyaxon serve --replicas N --route`: N single-replica children
    as a fleet-placed gang, fronted by the JSQ/P2C router."""
    from ..scheduler.fleet import Fleet
    from ..serving.replicas import (
        ReplicaSetManager,
        SubprocessReplica,
        host_tpu_chips,
        replica_chip_env,
    )
    from ..serving.router import AutoscalePolicy, Router
    from ..telemetry import MetricsRegistry

    store = RunStore()
    try:
        uuid = store.resolve(uid)
    except KeyError as e:
        raise click.ClickException(str(e.args[0]) if e.args else str(e))
    # spec defaults: CLI flags layer over the run's own serving section
    serving_spec = None
    try:
        from ..schemas.run_kinds import V1JAXJob

        run = (store.read_spec(uuid).get("component") or {}).get("run") or {}
        if run.get("kind") == "jaxjob" and run.get("program"):
            serving_spec = V1JAXJob.model_validate(run).program.serving
    except Exception:
        pass
    # disaggregated pools (ISSUE 20): slots [0, n_prefill) run prefill-
    # only replicas, the rest decode; the CLI --pools wins over the run
    # spec's serving.pools
    if pools is None and serving_spec is not None and serving_spec.pools:
        ps = serving_spec.pools
        if isinstance(ps.prefill, int) and isinstance(ps.decode, int):
            pools = (int(ps.prefill), int(ps.decode))
    if pools is not None:
        n = pools[0] + pools[1]
    else:
        n = replicas or (
            int(serving_spec.replicas)
            if serving_spec is not None
            and isinstance(serving_spec.replicas, int)
            else 1
        )
    if mesh_axes is None and serving_spec is not None:
        mesh_axes = serving_spec.mesh_axes
    chips = 1
    if mesh_axes:
        sizes = [int(v) for v in mesh_axes.values() if int(v) != -1]
        import math as _math

        chips = _math.prod(sizes) if sizes else 1

    # one process for each chip: every slot's child sees only its own
    # chips. This parent never opens a chip itself — the count comes from
    # the fleet's inventory where one is configured, else a short child.
    fleet = Fleet(store)
    try:
        host_chips = (
            fleet.inventory().total if fleet.configured else host_tpu_chips()
        )
        replica_chip_env(n - 1, chips, host_chips)  # refuse before any child
    except (RuntimeError, ValueError) as e:
        raise click.ClickException(str(e))
    log_dir = store.outputs_dir(uuid) / "serving"

    def factory(i):
        slot_overrides = overrides
        if pools is not None:
            # slots past the declared pools (autoscale growth) decode:
            # decode capacity is the safe direction to grow
            slot_role = "prefill" if i < pools[0] else "decode"
            slot_overrides = {**overrides, "role": slot_role}
        return SubprocessReplica(
            lambda p: _serve_child_argv(
                uuid, p, mesh_axes, slot_overrides, expected_devices
            ),
            env=replica_chip_env(i, chips, host_chips),
            # a chip-sized checkpoint takes minutes, not seconds, to read
            ready_timeout_s=600.0,
            stderr_path=str(log_dir / f"replica-{i}.stderr"),
        )

    # one registry for manager + router so restart counters land on the
    # same /metricsz scrape as the router_* series
    registry = MetricsRegistry()
    manager = ReplicaSetManager(
        factory, replicas=n,
        fleet=fleet if fleet.configured else None,
        chips_per_replica=chips,
        name=f"serve-{uuid[:8]}",
        registry=registry,
    )
    autoscale = None
    if autoscale_max is not None:
        autoscale = AutoscalePolicy(min_replicas=n, max_replicas=autoscale_max)
    # prefix affinity: CLI --no-affinity wins, else the run spec's
    # serving.prefixAffinity, else on (it is a no-op without /kvz heads)
    affinity = not no_affinity and (
        serving_spec.prefix_affinity if serving_spec is not None else True
    )
    router = Router(
        manager.endpoints,
        registry=registry,
        scaler=manager if autoscale is not None else None,
        autoscale=autoscale,
        trace=overrides.get("trace", True),
        affinity=affinity,
    )
    manager.attach_router(router)
    click.echo(f"starting {n} replica(s)...")
    try:
        manager.start()
    except Exception as e:
        manager.stop(drain=False)
        raise click.ClickException(f"replica startup failed: {e}")
    bound = router.start(host=host, port=port)
    mesh_note = (
        " mesh=" + ",".join(f"{k}={v}" for k, v in (mesh_axes or {}).items())
        if mesh_axes else ""
    )
    click.echo(
        f"routing {n} replica(s){mesh_note} on http://{host}:{bound} — "
        "POST /generate, GET /healthz, GET /readyz, GET /statsz, "
        "GET /metricsz"
        + (f"; autoscale up to {autoscale_max}" if autoscale_max else "")
    )
    import signal
    import threading

    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    try:
        stop.wait()
    finally:
        click.echo("draining fleet...")
        router.stop()
        manager.stop()


@cli.command()
@click.option("-f", "--file", "fpath", required=True, type=click.Path(exists=True))
@click.option("-P", "--param", "params", multiple=True, help="override: name=value")
@click.option("--namespace", default="polyaxon")
def convert(fpath, params, namespace):
    """Render the k8s manifests for a polyaxonfile (TPU topology included)."""
    from ..k8s import convert_operation

    try:
        op = read_polyaxonfile(fpath, params=_params_to_dict(params))
        compiled = compile_operation(op, base_dir=None)
        manifests = convert_operation(compiled, namespace=namespace)
    except (PolyaxonfileError, CompilationError) as e:
        raise click.ClickException(str(e))
    import yaml as _yaml

    click.echo(_yaml.safe_dump_all(manifests, sort_keys=False))


@cli.group()
def config():
    """Client settings (~/.polyaxon/config.json + POLYAXON_* env)."""


@config.command("show")
def config_show():
    from .. import settings

    click.echo(json.dumps(settings.show(), indent=1))


@config.command("get")
@click.argument("key")
def config_get(key):
    from .. import settings

    try:
        click.echo(settings.get(key))
    except KeyError as e:
        raise click.ClickException(str(e))


@config.command("set")
@click.argument("key")
@click.argument("value")
def config_set(key, value):
    from .. import settings

    try:
        settings.set_value(key, value)
    except KeyError as e:
        raise click.ClickException(str(e))
    click.echo(f"{key} = {value}")


@cli.group()
def project():
    """Project registry."""


@project.command("create")
@click.argument("name")
@click.option("--description", default="")
def project_create(name, description):
    from ..client import ClientError, ProjectClient

    try:
        p = ProjectClient(RunStore()).create(name, description)
    except ClientError as e:
        raise click.ClickException(str(e))
    click.echo(f"project {p['name']} created")


@project.command("ls")
def project_ls():
    from ..client import ProjectClient

    for p in ProjectClient(RunStore()).list():
        click.echo(f"{p['name']:<24} {p.get('runs', 0):>5} runs  {p.get('description', '')}")


@project.command("get")
@click.argument("name")
def project_get(name):
    from ..client import ClientError, ProjectClient

    try:
        click.echo(json.dumps(ProjectClient(RunStore()).get(name), indent=1))
    except ClientError as e:
        raise click.ClickException(str(e))


@cli.group()
def queues():
    """Named run queues (priority + concurrency per queue)."""


@queues.command("ls")
def queues_ls():
    """Queues with settings, backlog, and the current head-of-line wait."""
    import time as _time

    from ..scheduler.queue import QueueRegistry

    registry = QueueRegistry(RunStore())
    now = _time.time()
    for row in registry.stats():
        entries = registry.get(row["name"]).peek_all()
        stamps = [e["enqueued_at"] for e in entries if e.get("enqueued_at")]
        if stamps:
            row["oldest_wait_s"] = round(max(0.0, now - min(stamps)), 1)
        click.echo(json.dumps(row))


@queues.command("set")
@click.argument("name")
@click.option("--concurrency", default=1, type=int)
@click.option("--priority", default=0, type=int)
def queues_set(name, concurrency, priority):
    from ..scheduler.queue import QueueRegistry

    QueueRegistry(RunStore()).set_queue(
        name, concurrency=concurrency, priority=priority
    )
    click.echo(f"queue {name}: concurrency={concurrency} priority={priority}")


@cli.group()
def fleet():
    """Device fleet: inventory, gang reservations, quotas.

    With a configured fleet the agent admits runs through the scheduler
    (chip reservations, quotas, priority preemption) instead of bare
    queue concurrency. Unconfigured = everything behaves as before."""


@fleet.command("init")
@click.option("--topology", default=None,
              help="ICI torus, e.g. 4x8 or 4x4x4 (reservations become "
              "axis-aligned sub-blocks)")
@click.option("--chips", default=None, type=int,
              help="flat pool size; omit both to derive from jax.devices()")
def fleet_init(topology, chips):
    """Configure the fleet's capacity and enable scheduler admission."""
    from ..scheduler.fleet import Fleet

    try:
        cfg = Fleet(RunStore()).configure(topology=topology, chips=chips)
    except ValueError as e:
        raise click.ClickException(str(e))
    click.echo(f"fleet configured: {json.dumps(cfg)}")


@cli.group()
def scenario():
    """Scenario engine: trace-driven replay, chaos, soak simulation.

    Named scenarios compose a seeded traffic trace, an optional chaos
    ingredient (replica kill, tiny KV pool, small queue), and
    declarative assertions (max shed rate, p99 bound, zero hung, zero
    leaked KV pages). `run` drives them against a live in-process
    router+replica rig (mode=real) or the discrete-event serving twin
    (mode=twin, million-user soaks in seconds)."""


@scenario.command("ls")
def scenario_ls():
    """Named scenarios, one JSON line each."""
    from ..scenarios.registry import scenario_table

    for row in scenario_table():
        click.echo(json.dumps(row))


@scenario.command("run")
@click.argument("name")
@click.option("--mode", default=None,
              type=click.Choice(["real", "twin"]),
              help="real = live router+replica rig; twin = discrete-event "
              "simulation (default: real, or twin for twin-only scenarios)")
@click.option("--smoke", is_flag=True,
              help="small CI configuration of the scenario's trace")
@click.option("--seed", default=None, type=int,
              help="override the scenario's trace/chaos seed")
@click.option("--replicas", default=2, type=int,
              help="rig size for mode=real")
@click.option("--out", default=None, type=click.Path(),
              help="write the full result JSON here (stdout stays a "
              "one-line summary + assertion verdicts)")
def scenario_run(name, mode, smoke, seed, replicas, out):
    """Run one named scenario and evaluate its assertions (exit 1 on
    any failed assertion)."""
    from ..scenarios.registry import SCENARIOS, run_scenario
    from ..utils.jax_platform import apply_platform_env

    if name not in SCENARIOS:
        raise click.ClickException(
            f"unknown scenario {name!r} "
            f"(have: {', '.join(sorted(SCENARIOS))})"
        )
    scn = SCENARIOS[name]
    if mode is None:
        mode = "twin" if scn.twin_only else "real"
    if mode == "real":
        apply_platform_env()  # before any jax init in the rig
    try:
        result = run_scenario(
            name, mode=mode, smoke=smoke, seed=seed, replicas=replicas
        )
    except ValueError as e:
        raise click.ClickException(str(e))
    if out:
        with open(out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2, default=str)
    summary = dict(result["summary"])
    summary.pop("shed_reasons", None)
    click.echo(json.dumps({
        "scenario": name, "mode": mode, "pass": result["pass"],
        **{k: v for k, v in summary.items()
           if k in ("offered", "ok", "shed", "disconnected", "error",
                    "hung", "shed_rate")},
    }))
    for v in result["assertions"]:
        click.echo(json.dumps(v))
    if not result["pass"]:
        raise SystemExit(1)


@fleet.command("show")
def fleet_show():
    """Inventory, reservations, and per-project usage (the /fleetz body)."""
    from ..scheduler.fleet import Fleet

    click.echo(json.dumps(Fleet(RunStore()).snapshot(), indent=1))


@fleet.group("quota")
def fleet_quota():
    """Per-project and per-queue admission quotas."""


@fleet_quota.command("set")
@click.argument("scope")
@click.option("--max-chips", default=None, type=int,
              help="cap on concurrently reserved chips")
@click.option("--max-runs", default=None, type=int,
              help="cap on concurrent admitted runs")
@click.option("--weight", default=1.0, type=float,
              help="fair-share weight at equal priority (higher = more)")
def fleet_quota_set(scope, max_chips, max_runs, weight):
    """SCOPE is a project name, or queue:<name> for a queue-wide quota."""
    from ..schemas.quota import V1QuotaSpec
    from ..scheduler.admission import QuotaManager

    try:
        spec = V1QuotaSpec(
            scope=scope, max_chips=max_chips, max_runs=max_runs, weight=weight
        )
    except Exception as e:  # pydantic ValidationError → clean CLI error
        raise click.ClickException(str(e))
    QuotaManager(RunStore()).set(spec)
    click.echo(f"quota {scope}: {json.dumps(spec.to_dict())}")


@fleet_quota.command("ls")
def fleet_quota_ls():
    from ..scheduler.admission import QuotaManager

    for spec in QuotaManager(RunStore()).all():
        click.echo(json.dumps(spec.to_dict()))


@fleet_quota.command("rm")
@click.argument("scope")
def fleet_quota_rm(scope):
    from ..scheduler.admission import QuotaManager

    if QuotaManager(RunStore()).remove(scope):
        click.echo(f"quota {scope} removed")
    else:
        raise click.ClickException(f"no quota for scope {scope!r}")


@cli.group()
def admin():
    """Platform administration."""


@admin.command("deploy")
@click.option("--namespace", default="polyaxon")
@click.option("--image", default="polyaxon-tpu/cli:latest")
@click.option("--store-size", default="50Gi")
@click.option("--dry-run", is_flag=True, help="print manifests instead of writing")
@click.option("--out", default="deploy/", help="output dir for manifests")
def admin_deploy(namespace, image, store_size, dry_run, out):
    """Render the control-plane manifests (agent, streams, store PVC)."""
    from ..k8s.deploy import render_deploy, write_deploy

    manifests = render_deploy(
        namespace=namespace, image=image, store_size=store_size
    )
    if dry_run:
        import yaml as _yaml

        click.echo(_yaml.safe_dump_all(manifests, sort_keys=False))
        return
    paths = write_deploy(manifests, out)
    click.echo(f"wrote {len(paths)} manifests to {out} (kubectl apply -f {out})")


@admin.command("upgrade")
@click.option("--namespace", default="polyaxon")
@click.option("--image", required=True, help="new control-plane image")
@click.option("--store-size", default="50Gi")
@click.option("--out", default="deploy/", help="manifest dir to upgrade in place")
def admin_upgrade(namespace, image, store_size, out):
    """Re-render the control plane with a new image; state (the store PVC)
    is untouched, so runs and queues survive the upgrade."""
    import os as _os

    from ..k8s.deploy import render_deploy, write_deploy

    if not _os.path.isdir(out):
        raise click.ClickException(
            f"{out} does not exist — `polyaxon admin deploy` first"
        )
    manifests = render_deploy(namespace=namespace, image=image, store_size=store_size)
    paths = write_deploy(manifests, out)
    click.echo(
        f"re-rendered {len(paths)} manifests with image {image} "
        f"(kubectl apply -f {out} performs a rolling update; PVC unchanged)"
    )


@admin.command("teardown")
@click.option("--namespace", default="polyaxon")
@click.option("--keep-store/--delete-store", default=True,
              help="keep the run-store PVC (default) or delete it too")
def admin_teardown(namespace, keep_store):
    """Print the teardown commands (services first, store last — and only
    with --delete-store; run data is not deletable by default)."""
    cmds = [
        f"kubectl -n {namespace} delete deployment polyaxon-agent polyaxon-streams",
        f"kubectl -n {namespace} delete service polyaxon-streams",
    ]
    if not keep_store:
        cmds.append(f"kubectl -n {namespace} delete pvc polyaxon-store")
        cmds.append(f"kubectl delete namespace {namespace}")
    for c in cmds:
        click.echo(c)
    if keep_store:
        click.echo(
            f"# run store kept: pvc/polyaxon-store in {namespace} "
            "(re-deploy reattaches it)"
        )


@cli.command()
@click.argument("ref")
@click.option("--follow/--no-follow", default=False,
              help="keep tailing the run's event log over the watch cursor")
@click.option("--timeout", default=0.5, type=float, show_default=True,
              help="per-wait long-poll bound while following")
def events(ref, follow, timeout):
    """Run history straight from the event log, one JSON record per line.

    With --follow, rides the store's watch cursor: replays the committed
    history, then blocks on commits (no sleep-polling, no directory
    scans) until the run reaches a terminal status.
    """
    from ..schemas.lifecycle import DONE_STATUSES
    from ..store.local import UnknownRunError

    store = RunStore()
    try:
        uid = store.resolve(ref)
    except UnknownRunError as e:
        raise click.ClickException(str(e.args[0]) if e.args else str(e))
    if not follow:
        for rec in store.get_history(uid):
            click.echo(json.dumps(rec, default=str))
        return
    store.get_history(uid)  # force legacy import so the log has the run

    def _terminal() -> bool:
        try:
            return V1Statuses(
                store.get_status(uid).get("status", "")
            ) in DONE_STATUSES
        except ValueError:
            return False

    # cursor "0:0" = full history first; `stop` is checked after each
    # wait round, so the terminal record itself is always emitted
    for rec in store.watch("0:0", timeout=timeout, stop=_terminal):
        if rec.get("r") == uid:
            click.echo(json.dumps(rec, default=str))


@cli.command()
@click.argument("ref")
@click.option("--url", default=None,
              help="streams server base URL (default: read the local "
                   "store directly)")
@click.option("--json", "as_json", is_flag=True, default=False,
              help="emit raw timeline entries, one JSON object per line")
def timeline(ref, url, as_json):
    """A run's causally ordered story, folded from its event log.

    Status transitions, retries, preemptions and resumes, elastic
    resizes, and checkpoint-tier fallbacks in commit order — one per-run
    log read, no directory scans. With --url, asks a streams server's
    /runs/<ref>/timeline instead of the local store.
    """
    if url is not None:
        entries = _http_json(
            f"{url.rstrip('/')}/runs/{ref}/timeline"
        )["timeline"]
    else:
        from ..store.local import UnknownRunError

        store = RunStore()
        try:
            uid = store.resolve(ref)
        except UnknownRunError as e:
            raise click.ClickException(str(e.args[0]) if e.args else str(e))
        entries = store.timeline(uid)
    if as_json:
        for e in entries:
            click.echo(json.dumps(e, default=str))
        return
    import datetime

    for e in entries:
        ts = e.get("ts")
        when = (
            datetime.datetime.fromtimestamp(ts).strftime("%H:%M:%S")
            if isinstance(ts, (int, float))
            else "--:--:--"
        )
        click.echo(
            f"#{e.get('seq', '?'):<5} {when}  "
            f"{e.get('kind', '?'):<11} {e.get('label', '')}"
        )


@cli.command()
@click.option("--url", default="http://127.0.0.1:8080", show_default=True,
              help="router base URL (fleet serving)")
@click.option("--interval", default=2.0, type=float, show_default=True,
              help="refresh interval (seconds)")
@click.option("--once", is_flag=True, default=False,
              help="print one frame and exit (no screen clearing)")
def top(url, interval, once):
    """Live cluster dashboard: fleet, router replicas, SLO burn, runs.

    Fleet chips and active runs come from the local store's event-log
    watch cursor (zero directory scans between frames); replica health,
    queue wait, and cluster rollups come from the router's federated
    /statsz; SLO burn from /sloz. Ctrl-C exits."""
    from .top import run_top

    run_top(RunStore(), url.rstrip("/"), interval=interval, once=once)


@cli.group("store")
def store_cmd():
    """Run-store maintenance: event-log migration and recovery."""


@store_cmd.command("migrate")
def store_migrate():
    """Import legacy per-run JSON dirs into the event log and stamp the
    layout version. Idempotent — safe to re-run any time."""
    store = RunStore()
    before = store.store_format()
    n = store.migrate()
    click.echo(
        f"migrated {n} run(s); store format {before} -> {store.store_format()}"
    )


@store_cmd.command("recover")
@click.option("-uid", "--uid", default=None,
              help="one run only (default: the whole store)")
def store_recover(uid):
    """Heal interrupted appends, truncate torn tails, quarantine corrupt
    segments, and refresh the status views."""
    store = RunStore()
    if uid is not None:
        from ..store.local import UnknownRunError

        try:
            store.recover(store.resolve(uid))
        except UnknownRunError as e:
            raise click.ClickException(str(e.args[0]) if e.args else str(e))
        click.echo(f"recovered {uid}")
        return
    n = store.recover()
    click.echo(f"recovered {n} run(s)")


def main():
    # `POLYAXON_JAX_PLATFORM=cpu POLYAXON_NUM_CPU_DEVICES=8 polyaxon run ...`
    # drives a virtual 8-device slice on a laptop/CI box
    from ..utils.jax_platform import PlatformEnvError, apply_platform_env

    try:
        apply_platform_env()
    except PlatformEnvError as e:
        raise click.ClickException(str(e))
    except RuntimeError as e:  # backend already up — surface, don't crash
        click.echo(f"warning: could not apply platform env: {e}", err=True)
    cli()


if __name__ == "__main__":
    main()
