"""Load generator process: sends ``POST /v1/search`` on keep-alive
connections and records every request.  It never imports JAX.

Run by the harness as ``python loadgen.py <plan.json>``; it talks to its
parent in lines on stdin/stdout:

  -> ``encoded``            every request body is encoded (JSON, once)
  <- ``url <base url>``     the server is up: connect and warm each connection
  -> ``ready``
  <- ``go <t0> <t1>``       the window, on the shared monotonic clock
  -> ``done``               records written to the plan's ``out`` file

Open loop (``mode: open``): request ``i`` is due at ``t0 + offset[i]`` and is
sent then on the first idle connection, whether or not earlier replies have
come; its latency is counted from the due time, and ``sent - due`` is the
generator's own lateness.  Closed loop (``mode: closed``): each connection is
one client that sends its next query when the reply to the last one arrives,
from ``t0`` until ``t1``.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from urllib.parse import urlsplit

import numpy as np


class Conn:
    """One keep-alive HTTP/1.1 connection."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.r = self.w = None

    async def open(self) -> "Conn":
        self.r, self.w = await asyncio.open_connection(self.host, self.port)
        return self

    def close(self) -> None:
        if self.w is not None:
            self.w.close()
            self.r = self.w = None

    async def post(self, head: bytes, body: bytes):
        if self.w is None:
            await self.open()
        self.w.write(head + body)
        line = await self.r.readline()
        if not line:
            raise ConnectionError("server closed the connection")
        status = int(line.split()[1])
        length = 0
        while True:
            h = await self.r.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            name, _, value = h.partition(b":")
            if name.strip().lower() == b"content-length":
                length = int(value)
        return status, await self.r.readexactly(length)


def encode(queries: np.ndarray, idx, k: int):
    """{pool index: JSON request body} for the indices used."""
    return {i: json.dumps({"query": queries[i].tolist(), "k": k}).encode()
            for i in sorted(set(int(x) for x in idx))}


def head_for(body: bytes, host: str) -> bytes:
    return (f"POST /v1/search HTTP/1.1\r\nHost: {host}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()


class Recorder:
    def __init__(self):
        self.rows = []                 # (qidx, due, sent, done, status, body)

    def add(self, *row):
        self.rows.append(row)

    def save(self, path: str, k: int) -> None:
        n = len(self.rows)
        ids = np.full((n, k), -1, np.int32)
        scores = np.full((n, k), np.nan, np.float32)
        lat = np.full(n, np.nan)
        queue = np.full(n, np.nan)
        compute = np.full(n, np.nan)
        cols = list(zip(*self.rows)) if n else [[]] * 6
        for j, (status, body) in enumerate(zip(cols[4], cols[5])):
            if status != 200:
                continue
            p = json.loads(body)
            got = p["ids"][:k]
            ids[j, :len(got)] = got
            scores[j, :len(got)] = p["scores"][:k]
            lat[j] = p["latency_ms"]
            queue[j] = p["spans"]["queue_ms"]
            compute[j] = p["spans"]["compute_ms"]
        np.savez(path, qidx=np.asarray(cols[0], np.int64),
                 due=np.asarray(cols[1], np.float64),
                 sent=np.asarray(cols[2], np.float64),
                 done=np.asarray(cols[3], np.float64),
                 status=np.asarray(cols[4], np.int32),
                 ids=ids, scores=scores, server_latency_ms=lat,
                 queue_ms=queue, compute_ms=compute)


async def _one(conn: Conn, head: bytes, body: bytes, timeout: float):
    try:
        status, payload = await asyncio.wait_for(conn.post(head, body),
                                                 timeout)
    except (OSError, ConnectionError, asyncio.IncompleteReadError,
            asyncio.TimeoutError, ValueError, IndexError):
        conn.close()                   # reopened by the next post
        return 0, b""
    return status, payload


async def open_loop(plan, bodies, heads, conns, t0, rec):
    idle: asyncio.Queue = asyncio.Queue()
    for c in conns:
        idle.put_nowait(c)
    timeout = float(plan["timeout_s"])

    async def fire(qi: int, due: float):
        conn = await idle.get()
        sent = time.monotonic()
        status, payload = await _one(conn, heads[qi], bodies[qi], timeout)
        rec.add(qi, due, sent, time.monotonic(), status, payload)
        idle.put_nowait(conn)

    tasks = []
    for off, qi in zip(plan["offsets"], plan["qidx"]):
        due = t0 + off
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(fire(int(qi), due)))
    await asyncio.gather(*tasks)


async def closed_loop(plan, bodies, heads, conns, t0, t1, rec):
    order = plan["order"]
    timeout = float(plan["timeout_s"])

    async def client(conn: Conn, start: int):
        i = start
        delay = t0 - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        while time.monotonic() < t1:
            qi = int(order[i % len(order)])
            i += 1
            sent = time.monotonic()
            status, payload = await _one(conn, heads[qi], bodies[qi], timeout)
            rec.add(qi, sent, sent, time.monotonic(), status, payload)

    await asyncio.gather(*(client(c, s)
                           for c, s in zip(conns, plan["starts"])))


async def session(plan) -> None:
    queries = np.load(plan["pool"])
    used = plan["qidx"] if plan["mode"] == "open" else plan["order"]
    bodies = encode(queries, used, int(plan["k"]))
    del queries
    say("encoded")
    url = urlsplit(await ask("url"))
    heads = {i: head_for(b, url.netloc) for i, b in bodies.items()}
    conns = [await Conn(url.hostname, url.port).open()
             for _ in range(int(plan["connections"]))]
    warm = sorted(bodies)[: len(conns)]
    for c, qi in zip(conns, warm * len(conns)):
        status, _ = await c.post(heads[qi], bodies[qi])
        if status != 200:
            raise RuntimeError(f"warm-up search returned {status}")
    say("ready")
    t0, t1 = (float(x) for x in (await ask("go")).split())
    rec = Recorder()
    if plan["mode"] == "open":
        await open_loop(plan, bodies, heads, conns, t0, rec)
    else:
        await closed_loop(plan, bodies, heads, conns, t0, t1, rec)
    for c in conns:
        c.close()
    rec.save(plan["out"], int(plan["k"]))
    say("done")


def say(word: str) -> None:
    sys.stdout.write(word + "\n")
    sys.stdout.flush()


async def ask(word: str) -> str:
    line = await asyncio.get_running_loop().run_in_executor(
        None, sys.stdin.readline)
    tag, _, rest = line.strip().partition(" ")
    if tag != word:
        raise RuntimeError(f"expected {word!r} from the harness, got {line!r}")
    return rest


def main() -> int:
    with open(sys.argv[1]) as f:
        plan = json.load(f)
    asyncio.run(session(plan))
    return 0


if __name__ == "__main__":
    sys.exit(main())
