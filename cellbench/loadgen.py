"""The load generator: one child process, one thread, asyncio streams.

It never imports JAX (the parent holds the chip), makes its requests from
the seed with `cellbench/traffic.py`, sends them to `POST /generate?stream=1`
and records, on the system-wide monotonic clock, when each was sent (and,
in an open loop, when it was due), when each frame of tokens arrived and
what it held. The parent reads the record from a file once the child has
ended. Copied in spirit from `polyaxon_tpu/scenarios/driver.py::_stream`
(client-side time to the first token frame); see PERF.md, Open questions.

    python3 cellbench/loadgen.py <job.json>

job: {"base": url, "out": path, "mode": "waves" | "closed_loop" | "open_loop",
      "traffic": {...}, "vocab": n, "seed": n,
      "t_open": monotonic seconds, "seconds": window length,
      "waves": [{"lead": [prompt_len, max_new], "followers": [[prompt_len, max_new], ...]}, ...]}

Closed loop: `traffic.clients` callers start at once (before `t_open`: the
window opens on a system in steady state) and each sends its next request
when the last token of the previous one has arrived; none is sent after the
window has closed, and those in flight are waited for. Open loop: each
request is sent when it is due, counted from `t_open`.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from pathlib import Path
from urllib.parse import urlsplit

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from cellbench import traffic  # noqa: E402

WAIT_PAST_CLOSE_S = 60.0


async def one_request(host: str, port: int, req: dict, due: float | None, sink: list,
                      first: asyncio.Event | None = None) -> dict:
    """Send one request and read its frames to the end. The record is in
    `sink` from the moment of sending: one that never ends is still there."""
    body = json.dumps({
        "tokens": [req["tokens"]], "maxNewTokens": req["max_new"],
        "temperature": 0.0, "seed": req["index"],
    }).encode()
    rec = {
        "index": req["index"], "prompt_len": len(req["tokens"]),
        "max_new": req["max_new"], "due": due, "frames": [], "tokens": [],
        "status": None, "error": None, "t_done": None,
    }
    rec["t_send"] = time.monotonic()
    sink.append(rec)
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(
            b"POST /generate?stream=1 HTTP/1.1\r\nHost: " + host.encode()
            + b"\r\nContent-Type: application/json\r\nContent-Length: "
            + str(len(body)).encode() + b"\r\nConnection: close\r\n\r\n" + body
        )
        await writer.drain()
        status = await reader.readline()
        rec["status"] = int(status.split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if rec["status"] != 200:
            rec["error"] = (await reader.read()).decode(errors="replace")[:300]
            return rec
        while True:
            raw = await reader.readline()
            if not raw:
                break
            if not raw.startswith(b"data: "):
                continue
            now = time.monotonic()
            ev = json.loads(raw[6:])
            toks = ev.get("tokens") or []
            if toks:
                if first is not None:
                    first.set()
                rec["frames"].append([now, len(toks)])
                rec["tokens"].extend(int(t) for t in toks)
            if ev.get("error"):
                rec["error"] = str(ev["error"])[:300]
            if ev.get("done") and "row" not in ev:
                rec["t_done"] = now
    except (OSError, ValueError, IndexError, asyncio.IncompleteReadError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        if first is not None:
            first.set()
        if writer is not None:
            writer.close()
    if rec["t_done"] is None and rec["error"] is None:
        rec["error"] = "stream ended without a done frame"
    return rec


async def finish(tasks, timeout: float) -> None:
    """Wait for every request, a minute past the close if need be; one that
    has not ended by then never came, and its record says so."""
    if not tasks:
        return
    _, pending = await asyncio.wait(tasks, timeout=timeout)
    for t in pending:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def run_waves(host, port, job) -> list[dict]:
    """Warm-up. A wave is {"lead": [prompt_len, max_new] or null,
    "followers": [[prompt_len, max_new], ...]}: the lead is sent alone, and
    once its first token has come (it decodes) the followers are sent
    together, so that they join the lead's decode lane one after another."""
    out, index = [], [0]

    def make(plen, max_new):
        i = index[0]
        index[0] += 1
        return {"index": i, "max_new": int(max_new),
                "tokens": traffic.prompt_tokens(job["vocab"], int(plen), job["seed"], i)}

    for wave in job["waves"]:
        tasks = []
        if wave.get("lead"):
            first = asyncio.Event()
            tasks.append(asyncio.ensure_future(
                one_request(host, port, make(*wave["lead"]), None, out, first)))
            await first.wait()
        for plen, max_new in wave.get("followers", []):
            tasks.append(asyncio.ensure_future(
                one_request(host, port, make(plen, max_new), None, out)))
        await finish(tasks, WAIT_PAST_CLOSE_S * 5)
    return out


async def run_closed(host, port, job) -> list[dict]:
    gen = traffic.generator("closed_loop")(job["traffic"], job["vocab"], job["seed"])
    t_close = job["t_open"] + job["seconds"]
    out: list[dict] = []

    async def client():
        while time.monotonic() < t_close:
            await one_request(host, port, next(gen), None, out)

    clients = [asyncio.ensure_future(client()) for _ in range(int(job["traffic"]["clients"]))]
    await finish(clients, max(1.0, t_close - time.monotonic()) + WAIT_PAST_CLOSE_S)
    return out


async def run_open(host, port, job) -> list[dict]:
    gen = traffic.generator("open_loop")(job["traffic"], job["vocab"], job["seed"])
    t_open, seconds = job["t_open"], job["seconds"]
    tasks, out = [], []
    for req in gen:
        if req["due_s"] >= seconds:
            break
        due = t_open + req["due_s"]
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(one_request(host, port, req, due, out)))
    await finish(tasks, WAIT_PAST_CLOSE_S)
    return out


def main(argv) -> int:
    job = json.loads(Path(argv[1]).read_text())
    parts = urlsplit(job["base"])
    runner = {"waves": run_waves, "closed_loop": run_closed, "open_loop": run_open}[job["mode"]]
    records = asyncio.run(runner(parts.hostname, parts.port, job))
    for rec in records:
        if rec["t_done"] is None and rec["error"] is None:
            rec["error"] = "never came"
    Path(job["out"]).write_text(json.dumps({"records": records}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
