"""HTTP from outside, as a user's client: blocking JSON calls for set-up
(standard library) and one asyncio loop of streaming requests for the
open-loop window (aiohttp, which the program already depends on)."""

from __future__ import annotations

import asyncio
import dataclasses
import http.client
import json
import re
import time

from .launcher import Failed


def open_200(port: int, method: str, path: str, body, timeout: float,
             patience_s: float, waited: list):
    """One request; returns (connection, response) once the status is 200.
    A 503 with Retry-After is the router saying "not now" (its probe can
    miss a server busy compiling): wait as told, for at most
    ``patience_s``; every wait is appended to ``waited``."""
    give_up = time.monotonic() + patience_s
    while True:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        try:
            conn.request(method, path,
                         None if body is None else json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status == 200:
                return conn, resp
            raw = resp.read()
            wait = resp.getheader("Retry-After")
        except BaseException:
            conn.close()
            raise
        conn.close()
        if not (resp.status == 503 and wait
                and time.monotonic() + float(wait) < give_up):
            raise Failed(f"{method} {path} -> {resp.status}: {raw[:300]!r}")
        waited.append(path)
        time.sleep(float(wait))


def http_json(port: int, method: str, path: str, body=None,
              timeout: float = 60.0, patience_s: float = 0.0,
              waited: "list | None" = None):
    conn, resp = open_200(port, method, path, body, timeout, patience_s,
                          [] if waited is None else waited)
    try:
        return json.loads(resp.read())
    finally:
        conn.close()


def stream_docs(port: int, path: str, body: dict, timeout: float,
                patience_s: float, waited: list) -> list:
    """A blocking streaming request, read to ``data: [DONE]``; returns the
    decoded ``data:`` frames. Set-up uses streams even where it wants one
    answer: a request that triggers a minutes-long compile survives on
    the server's keep-alive comments, where a plain one dies at the
    router's read timeout."""
    body = dict(body, stream=True, stream_options={"include_usage": True})
    conn, resp = open_200(port, "POST", path, body, timeout, patience_s,
                          waited)
    docs, done = [], False
    try:
        for raw in resp:
            if not raw.startswith(b"data:"):
                continue
            data = raw[5:].strip()
            if data == b"[DONE]":
                done = True
                break
            doc = json.loads(data)
            if "error" in doc:
                raise Failed(f"{path}: stream error frame {data[:300]!r}")
            docs.append(doc)
    finally:
        conn.close()
    if not done:
        raise Failed(f"{path}: stream ended without [DONE]")
    return docs


_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str) -> list:
    """Prometheus exposition as [(name, {label: value}, float)]."""
    out = []
    for line in text.splitlines():
        m = None if line.startswith("#") else _SAMPLE.match(line)
        if m:
            try:
                out.append((m.group(1),
                            dict(_LABEL.findall(m.group(2) or "")),
                            float(m.group(3))))
            except ValueError:
                pass
    return out


def scrape(port: int, timeout: float = 30.0) -> list:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", "/metrics")
        return parse_metrics(conn.getresponse().read().decode())
    finally:
        conn.close()


def metric_sum(samples: list, name: str, **labels) -> float:
    return sum(v for n, lab, v in samples if n == name
               and all(lab.get(k) == w for k, w in labels.items()))


def metric_values(samples: list, name: str, **labels) -> list:
    return [v for n, lab, v in samples if n == name
            and all(lab.get(k) == w for k, w in labels.items())]


# --------------------------------------------------------------------------
# the open loop
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    """What the client saw of one request. Times are ``time.monotonic()``."""
    index: int
    part: str                   # "preroll" | "window"
    due: float
    prompt_tokens: int
    max_tokens: int
    sent: float = 0.0
    status: int = 0
    events: list = dataclasses.field(default_factory=list)  # (t, n_tokens)
    finish_reason: str = ""
    finished_at: float = 0.0
    done: bool = False
    usage: "dict | None" = None
    error: str = ""

    @property
    def ok(self) -> bool:
        return (self.status == 200 and self.done and not self.error
                and bool(self.finish_reason))

    @property
    def tokens(self) -> int:
        return sum(n for _, n in self.events)


def request_body(model: str, mix: dict, prompt: str, max_tokens: int) -> dict:
    """Greedy streaming completion. ``logprobs: 1`` is what makes the
    server write one chunk per delivered token group: under random
    weights it serves the byte tokenizer, which cannot spell most ids of
    a 32000-token vocabulary, and a chunk with no text and no logprob
    entry is not written at all."""
    return {"model": model, "prompt": prompt, "max_tokens": max_tokens,
            "temperature": mix.get("temperature", 0), "stream": True,
            "logprobs": 1, "stream_options": {"include_usage": True}}


async def _fire(session, url: str, body: dict, rec: Record) -> None:
    import aiohttp

    rec.sent = time.monotonic()
    try:
        async with session.post(url, json=body) as resp:
            rec.status = resp.status
            if resp.status != 200:
                rec.error = (await resp.read())[:200].decode("utf-8",
                                                              "replace")
                return
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue        # ": ping" comments, blank separators
                data = raw[5:].strip()
                now = time.monotonic()
                if data == b"[DONE]":
                    rec.done = True
                    continue
                doc = json.loads(data)
                if "error" in doc:
                    rec.error = json.dumps(doc["error"])[:200]
                    continue
                if doc.get("usage"):
                    rec.usage = doc["usage"]
                for ch in doc.get("choices", ()):
                    lp = ch.get("logprobs")
                    n = len(lp.get("tokens", ())) if lp else 0
                    if n:
                        rec.events.append((now, n))
                    if ch.get("finish_reason"):
                        rec.finish_reason = ch["finish_reason"]
                        rec.finished_at = now
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
            ValueError) as e:
        rec.error = f"{type(e).__name__}: {e}"[:200]


async def open_loop(port: int, model: str, mix: dict, parts: list,
                    drain_limit_s: float, side_tasks=()) -> dict:
    """Offer ``parts`` = [(name, [Planned, ...], span_s), ...] back to
    back, each request at its due time whatever the earlier ones are
    doing, then wait at most ``drain_limit_s`` for the stragglers.
    ``side_tasks`` are ``async fn(t_window, t_end)`` run beside the load
    (the traced run's pollers): the start of the part named "window" and
    the end of the last part. Returns records and the parts' boundaries."""
    import aiohttp

    url = f"http://127.0.0.1:{port}{mix.get('endpoint', '/v1/completions')}"
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None)
    records: list = []
    tasks: list = []
    bounds: dict = {}
    async with aiohttp.ClientSession(connector=conn,
                                     timeout=timeout) as session:
        t = time.monotonic() + 0.05
        starts = []
        for name, planned, span in parts:
            starts.append(t)
            bounds[name] = (t, t + span)
            t += span
        t_end = t
        t_window = bounds.get("window", (starts[-1], t_end))[0]
        side = [asyncio.create_task(fn(t_window, t_end))
                for fn in side_tasks]
        for (name, planned, span), t0 in zip(parts, starts):
            for i, p in enumerate(planned):
                rec = Record(len(records), name, t0 + p.due_s,
                             p.prompt_tokens, p.max_tokens)
                records.append(rec)
                delay = rec.due - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                tasks.append(asyncio.create_task(_fire(
                    session, url,
                    request_body(model, mix, p.prompt, p.max_tokens), rec)))
        delay = t_end - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        if tasks:
            _, pending = await asyncio.wait(tasks, timeout=drain_limit_s)
            for task in pending:
                task.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
        bounds["drained"] = (t_end, time.monotonic())
        side_out = await asyncio.gather(*side, return_exceptions=True)
    return {"records": records, "bounds": bounds, "side": side_out}
