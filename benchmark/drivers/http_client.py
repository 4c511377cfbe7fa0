"""The load generator's HTTP client: asyncio on one thread, one
connection per request, timestamps from ``time.monotonic`` as each token
line is parsed off the socket."""

from __future__ import annotations

import asyncio
import json
import time


async def post(host: str, port: int, path: str, body: dict,
               timeout: float) -> dict:
    """POSTs ``body``; returns ``{"status", "sent_t", "first_t", "last_t",
    "tokens", "token_t", "reply"}``. A streamed reply is one JSON token per
    chunk line (``tokens``, each with its arrival time in ``token_t``); a
    plain reply is one JSON object (``reply``)."""
    out = {"status": None, "sent_t": None, "first_t": None, "last_t": None,
           "tokens": [], "token_t": [], "reply": None, "error": None}
    writer = None
    try:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(host, port), timeout)
        payload = json.dumps(body).encode()
        out["sent_t"] = time.monotonic()
        writer.write(b"POST %s HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: %d\r\nConnection: close\r\n\r\n"
                     % (path.encode(), len(payload)) + payload)
        await writer.drain()
        await asyncio.wait_for(_read_reply(reader, out), timeout)
    except (OSError, asyncio.TimeoutError, asyncio.IncompleteReadError,
            ValueError) as e:
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()
    return out


async def _read_reply(reader, out: dict) -> None:
    status = await reader.readline()
    out["status"] = int(status.split()[1])
    headers = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        k, _, v = line.decode().partition(":")
        headers[k.strip().lower()] = v.strip()
    if headers.get("transfer-encoding") == "chunked":
        while True:
            size = int((await reader.readline()).strip() or b"0", 16)
            if size == 0:
                break
            chunk = await reader.readexactly(size + 2)
            now = time.monotonic()
            for line in chunk.splitlines():
                if line.strip():
                    out["tokens"].append(json.loads(line))
                    out["token_t"].append(now)
                    if out["first_t"] is None:
                        out["first_t"] = now
                    out["last_t"] = now
    else:
        raw = await reader.readexactly(int(headers.get("content-length", 0)))
        now = time.monotonic()
        out["first_t"] = out["last_t"] = now
        out["reply"] = json.loads(raw) if raw else None


async def run_open(host: str, port: int, path: str, requests: list,
                   t_start: float, window_s: float, grace_s: float) -> list:
    """Sends each request at ``t_start + due_s`` regardless of the others;
    gives up on what is unfinished ``grace_s`` after the window."""
    deadline = t_start + window_s + grace_s

    async def one(req):
        due = t_start + req["due_s"]
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        body = {"prompt": req["prompt"], "max_tokens": req["max_tokens"],
                "temperature": 0.0, "stream": req["stream"],
                "seed": req["id"]}
        res = await post(host, port, path, body,
                         max(0.5, deadline - time.monotonic()))
        res.update(id=req["id"], due_t=due, want=req["max_tokens"],
                   prompt_len=len(req["prompt"]))
        return res

    return list(await asyncio.gather(*(one(r) for r in requests)))


async def run_closed(host: str, port: int, path: str, requests: list,
                     clients: int, t_first: float, stagger_s: float,
                     t_stop: float, grace_s: float) -> list:
    """``clients`` callers share ``requests``: each takes the list's next
    when its last has returned, until ``t_stop``; a request in flight then
    may finish within ``grace_s``. Client ``i`` starts at ``t_first + i *
    stagger_s``, so the first requests reach the program one at a time."""
    results = []
    queue = iter(requests)

    async def client(i):
        delay = t_first + i * stagger_s - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        while time.monotonic() < t_stop:
            req = next(queue, None)
            if req is None:
                raise RuntimeError(
                    "the closed loop ran out of requests: raise "
                    "requests_per_client in the traffic file")
            body = {"prompt": req["prompt"], "max_tokens": req["max_tokens"],
                    "temperature": 0.0, "stream": req["stream"],
                    "seed": req["id"]}
            res = await post(host, port, path, body,
                             max(0.5, t_stop + grace_s - time.monotonic()))
            res.update(id=req["id"], client=i, due_t=res["sent_t"],
                       want=req["max_tokens"],
                       prompt_len=len(req["prompt"]))
            results.append(res)

    await asyncio.gather(*(client(i) for i in range(clients)))
    return results
