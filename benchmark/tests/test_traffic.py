"""The generators: the same seed gives the same inputs, another seed
another order of the SAME sizes; the open loop's clock runs from when a
request was due."""

import asyncio
import json
import time

import numpy as np
import pytest

from benchmark.drivers import http_client, serve
from benchmark.traffic import closed_loop, open_loop, token_stream

CHAT = {"generator": "open_loop", "rate_per_s": 4.0, "shape_seed": 7,
        "prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.9,
                       "min": 32, "max": 1024},
        "output_len": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                       "min": 16, "max": 256}, "stream": True}


def _sizes(plan):
    return sorted((len(r["prompt"]), r["max_tokens"])
                  for r in plan["requests"])


def test_open_loop_is_deterministic_and_seeds_change_only_content():
    a = open_loop.plan(CHAT, 3000000001, 30.0, 49152)
    b = open_loop.plan(CHAT, 3000000001, 30.0, 49152)
    c = open_loop.plan(CHAT, 5, 30.0, 49152)
    assert a == b
    assert len(a["requests"]) == 120
    assert [r["prompt"] for r in a["requests"]] != \
        [r["prompt"] for r in c["requests"]]
    # the same schedule for every seed: sizes, gaps and their order
    assert [(len(r["prompt"]), r["max_tokens"], r["due_s"])
            for r in a["requests"]] == \
        [(len(r["prompt"]), r["max_tokens"], r["due_s"])
         for r in c["requests"]]
    due = [r["due_s"] for r in a["requests"]]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 30.0
    assert all(32 <= len(r["prompt"]) <= 1024 and 16 <= r["max_tokens"]
               <= 256 for r in a["requests"])
    # the gaps are exponential (Poisson arrivals), scaled to the window
    full = np.random.default_rng(7).exponential(1.0, size=120)
    assert np.allclose(np.diff(due), (full * 30.0 / full.sum())[1:])


def test_closed_loop_and_token_stream():
    mix = {"clients_per_slot": 2, "requests_per_client": 3, "shape_seed": 1,
           "prompt_len": {"dist": "uniform", "min": 128, "max": 512},
           "output_len": {"dist": "fixed", "value": 128}}
    a = closed_loop.plan(mix, 1, 10.0, 1000, deployment={"num_slots": 4})
    b = closed_loop.plan(mix, 2, 10.0, 1000, deployment={"num_slots": 4})
    assert a["clients"] == 8 and len(a["requests"]) == 24
    assert [r["id"] for r in a["requests"]] == list(range(24))
    flat = lambda p: [len(r["prompt"]) for r in p["requests"]]
    assert flat(a) == flat(b) and a != b
    assert a == closed_loop.plan(mix, 1, 10.0, 1000,
                                 deployment={"num_slots": 4})
    pre = {"seq": 32, "zipf_a": 1.1, "shape_seed": 3}
    x = next(token_stream.batches(pre, 11, 4, 500))
    y = next(token_stream.batches(pre, 11, 4, 500))
    z = next(token_stream.batches(pre, 12, 4, 500))
    assert x.shape == (4, 33) and (x == y).all() and (x != z).any()
    assert x.min() >= 0 and x.max() < 500
    big = next(token_stream.batches(pre, 1, 64, 500))
    top = np.bincount(big.ravel(), minlength=500).max() / big.size
    assert top > 0.05  # Zipf: the commonest token is common


async def _slow_server(delay_s):
    async def handle(reader, writer):
        await reader.readuntil(b"\r\n\r\n")
        await asyncio.sleep(delay_s)
        writer.write(b"HTTP/1.1 200 OK\r\nTransfer-Encoding: chunked\r\n\r\n")
        for tok in (5, 6, 7):
            chunk = json.dumps(tok).encode() + b"\n"
            writer.write(b"%x\r\n%s\r\n" % (len(chunk), chunk))
            await writer.drain()
            await asyncio.sleep(0.02)
        writer.write(b"0\r\n\r\n")
        await writer.drain()
        writer.close()

    return await asyncio.start_server(handle, "127.0.0.1", 0)


def test_open_loop_clock_runs_from_due_time():
    async def go():
        server = await _slow_server(0.05)
        port = server.sockets[0].getsockname()[1]
        reqs = [{"id": 0, "due_s": 0.0, "prompt": [1], "max_tokens": 3,
                 "stream": True}]
        # the window "started" 0.3 s ago: the request is sent 0.3 s late
        res = await http_client.run_open("127.0.0.1", port, "/x", reqs,
                                         time.monotonic() - 0.3, 1.0, 5.0)
        server.close()
        return res

    (r,) = asyncio.run(go())
    assert r["tokens"] == [5, 6, 7] and r["status"] == 200
    assert 0.3 <= r["sent_t"] - r["due_t"] < 0.4
    r["problem"] = serve._check_result(r, vocab=10)
    assert r["problem"] == ""
    ctx = serve._context([r], [], None, r["due_t"], r["due_t"] + 1.0,
                         1.0, {"tokens_generated": 3},
                         {"tokens_generated": 0},
                         {"deployment": {"num_slots": 1}})
    assert 350 <= ctx["series"]["ttft_ms"][0] < 480  # lateness + service
    assert 300 <= ctx["series"]["late_ms"][0] < 400
    assert 15 <= ctx["series"]["tpot_ms"][0] < 40  # (last - first) / 2
    assert ctx["counters"]["out_tokens"] == 3


def test_closed_loop_clients_share_one_list_and_start_apart():
    async def go():
        server = await _slow_server(0.05)
        port = server.sockets[0].getsockname()[1]
        reqs = [{"id": j, "prompt": [1], "max_tokens": 3, "stream": True}
                for j in range(40)]
        t_first = time.monotonic() + 0.05
        res = await http_client.run_closed(
            "127.0.0.1", port, "/x", reqs, 3, t_first, 0.1,
            t_first + 0.6, 5.0)
        server.close()
        return t_first, res

    t_first, res = asyncio.run(go())
    by_sent = sorted(res, key=lambda r: r["sent_t"])
    # the k-th request sent is the list's k-th, whichever client sends it
    assert [r["id"] for r in by_sent] == list(range(len(res)))
    assert {r["client"] for r in res} == {0, 1, 2} and len(res) > 6
    first = {c: min(r["sent_t"] for r in res if r["client"] == c) - t_first
             for c in range(3)}
    assert all(0.1 * c <= first[c] < 0.1 * c + 0.08 for c in range(3))
    # nobody sends after the stop; a list that runs out is an error
    assert all(r["sent_t"] < t_first + 0.6 for r in res)
    with pytest.raises(RuntimeError, match="ran out of requests"):
        asyncio.run(http_client.run_closed(
            "127.0.0.1", 1, "/x", [], 1, time.monotonic(), 0.0,
            time.monotonic() + 1.0, 1.0))
