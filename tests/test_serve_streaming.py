"""Serve data plane: HTTP keep-alive + chunked streaming responses + LLM
token streaming (the streaming half of the reference's starlette proxy,
``serve/_private/http_proxy.py:218``)."""

import asyncio
import contextlib
import http.client
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import cloudpickle
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve._private import replica as replica_mod
from ray_tpu.serve._private.replica import ServeReplica


@contextlib.contextmanager
def serving(**http):
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        yield serve.start(serve.HTTPOptions(host="127.0.0.1", port=0, **http))
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


@pytest.fixture
def serve_instance():
    with serving() as client:
        yield client


def test_keep_alive_connection_reuse(serve_instance):
    @serve.deployment
    def echo(request):
        return {"n": request.json()["n"]}

    serve.run(echo.bind(), port=0)
    host, port = serve.get_http_address()
    conn = http.client.HTTPConnection(host, port, timeout=60)
    try:
        for i in range(3):  # same socket, three request/response cycles
            body = json.dumps({"n": i})
            conn.request("POST", "/echo", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["n"] == i
    finally:
        conn.close()


def test_streaming_response_chunks_arrive_incrementally(serve_instance):
    @serve.deployment
    class Streamer:
        def __call__(self, request):
            def gen():
                for i in range(4):
                    yield f"chunk-{i}\n"
                    time.sleep(0.8)

            return serve.StreamingResponse(gen())

    serve.run(Streamer.bind(), port=0)
    host, port = serve.get_http_address()
    conn = http.client.HTTPConnection(host, port, timeout=120)
    try:
        t0 = time.time()
        conn.request("GET", "/Streamer")
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.headers.get("Transfer-Encoding") == "chunked"
        first_at = None
        data = b""
        while True:
            piece = resp.read(16)
            if not piece:
                break
            if first_at is None:
                first_at = time.time() - t0
            data += piece
        total = time.time() - t0
        assert data.decode().splitlines() == [f"chunk-{i}" for i in range(4)]
        # the producer sleeps 0.8s per chunk (~3.2s total); the first chunk
        # must arrive long before the stream finishes
        assert first_at is not None and first_at < total - 1.5, (first_at, total)
    finally:
        conn.close()


def test_llm_token_streaming_over_http(serve_instance):
    from ray_tpu.serve.llm import llm_deployment

    dep = llm_deployment(
        "gpt2", "tiny",
        engine_kwargs=dict(n_slots=2, max_new_tokens=6,
                           decode_chunk_steps=3, prefill_buckets=(8,)),
        config_kwargs=dict(dtype=jnp.float32),
    )
    serve.run(dep.bind(), port=0, timeout_s=300)
    host, port = serve.get_http_address()

    def post(payload):
        conn = http.client.HTTPConnection(host, port, timeout=300)
        try:
            conn.request("POST", "/llm", body=json.dumps(payload),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200, resp.read()
            return resp.read()
        finally:
            conn.close()

    plain = json.loads(post({"tokens": [3, 5, 7], "max_new_tokens": 6}))
    streamed = post({"tokens": [3, 5, 7], "max_new_tokens": 6,
                     "stream": True})
    toks = [int(x) for x in streamed.decode().split()]
    assert toks == plain["tokens"]  # greedy: identical either way


# -- a pull parks on the replica until the stream has something ----------


@pytest.fixture
def on_loop():
    """Run a coroutine on an event loop that lives on a thread of its own,
    as a worker runs an actor's coroutine methods; gives its future."""
    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    yield lambda coro: asyncio.run_coroutine_threadsafe(coro, loop)
    loop.call_soon_threadsafe(loop.stop)
    thread.join(timeout=10)
    loop.close()


def _replica() -> ServeReplica:
    return ServeReplica("d", "d#0", cloudpickle.dumps(lambda request: None),
                        (), {})


def _open(replica: ServeReplica, gen) -> str:
    return replica._register_stream(
        serve.StreamingResponse(gen))["__serve_stream__"]


@pytest.mark.parametrize("by", ["put", "end", "error", "cancel", "timeout"])
def test_a_parked_pull_returns_at_its_wake(on_loop, by):
    """``next_chunks`` with ``wait_s`` on an idle stream parks (a task of
    the loop, nothing else) and answers the moment a chunk is put, the
    producer ends with nothing left or fails, the stream is cancelled, or
    ``wait_s`` runs out; the replica counts each reply by kind."""
    go, hold = threading.Event(), threading.Event()

    def gen():
        go.wait(30)
        if by == "put":
            yield "late\n"
            hold.wait(30)  # the producer lives on after its put
        if by == "error":
            raise ValueError("boom")

    replica = _replica()
    sid = _open(replica, gen())
    try:
        fut = on_loop(replica.next_chunks(
            sid, 16, 0.5 if by == "timeout" else 30.0))
        time.sleep(0.2)
        assert not fut.done() and replica._parked == 1
        t0 = time.perf_counter()
        if by == "cancel":
            assert replica.cancel_stream(sid)
        elif by != "timeout":
            go.set()
        out = fut.result(timeout=10)
        took = time.perf_counter() - t0
    finally:
        go.set(), hold.set()
        replica.cancel_stream(sid)
    assert replica._parked == 0
    assert took < (0.1 if by != "timeout" else 1.0), took
    assert out["parked"] is True
    assert out["chunks"] == ([b"late\n"] if by == "put" else [])
    assert out["done"] is (by in ("end", "error", "cancel"))
    assert out.get("error") == ("ValueError: boom" if by == "error" else None)
    kind = "timed_out" if by == "timeout" else "woken"
    assert replica.stats()["stream_pulls"] == {
        "woken": 0, "timed_out": 0, "unparked": 0, kind: 1}


def test_stream_pull_kinds_add_up_to_the_replies(on_loop):
    """Every ``next_chunks`` reply is one of three kinds: after a park that
    a wake ended, after one that ran out, without one (data at once, no
    ``wait_s``, an unknown stream)."""
    def gen():
        for i in range(3):
            time.sleep(0.25)
            yield f"{i}\n"

    replica = _replica()
    sid = _open(replica, gen())
    replies, body = 0, b""
    assert on_loop(replica.next_chunks("no-such-stream", 16, 1.0)).result(
        10) == {"chunks": [], "done": True, "parked": False}
    assert on_loop(replica.next_chunks(sid, 16)).result(10)["parked"] is False
    replies += 2
    while True:
        out = on_loop(replica.next_chunks(sid, 16, 0.1)).result(10)
        replies += 1
        body += b"".join(out["chunks"])
        if out["done"]:
            break
    pulls = replica.stats()["stream_pulls"]
    assert body == b"0\n1\n2\n"
    assert sum(pulls.values()) == replies
    assert pulls["woken"] >= 3 and pulls["timed_out"] >= 3
    assert pulls["unparked"] >= 2


def test_a_pull_past_the_replicas_share_of_parks_answers_at_once(
        on_loop, monkeypatch):
    """At most ``MAX_PARKED_PULLS`` pulls park on a replica (half of what
    their concurrency group runs at once); one more answers at once, empty
    and marked, and the proxy paces it.  So does a second pull of a stream
    that already has one parked."""
    monkeypatch.setattr(replica_mod, "MAX_PARKED_PULLS", 2)
    assert replica_mod.STREAM_GROUP_CONCURRENCY >= 2 * 128  # a proxy's share
    hold = threading.Event()

    def gen():
        hold.wait(30)
        yield "x"

    replica = _replica()
    sids = [_open(replica, gen()) for _ in range(3)]
    try:
        parked = [on_loop(replica.next_chunks(sid, 16, 30.0))
                  for sid in sids[:2]]
        time.sleep(0.2)
        assert replica._parked == 2 and not any(f.done() for f in parked)
        for sid in (sids[2], sids[0]):
            t0 = time.perf_counter()
            out = on_loop(replica.next_chunks(sid, 16, 30.0)).result(10)
            assert time.perf_counter() - t0 < 0.1
            assert out == {"chunks": [], "done": False, "error": None,
                           "parked": False}
        hold.set()
        assert [f.result(10)["chunks"] for f in parked] == [[b"x"], [b"x"]]
    finally:
        hold.set()
        for sid in sids:
            replica.cancel_stream(sid)
    assert replica.stats()["stream_pulls"] == {
        "woken": 2, "timed_out": 0, "unparked": 2}


def test_no_wake_up_is_lost_between_many_producers_and_parked_pulls(on_loop):
    """The parker publishes its waker and THEN looks at the queue again; the
    producer puts and THEN looks for a waker.  More streams than cores, the
    interpreter switching threads every 10 us: were a put to slip between a
    pull's look and its park unseen, that pull would sleep its ``wait_s``
    out (5 s) with a chunk in the queue, and be counted ``timed_out``."""
    import random
    import sys

    n_streams, n_chunks = 24, 150

    def gen(i):
        rng = random.Random(i)
        for j in range(n_chunks):
            if rng.random() < 0.3:
                time.sleep(rng.random() * 1e-3)
            yield f"{i}:{j}\n"

    replica = _replica()
    sids = [_open(replica, gen(i)) for i in range(n_streams)]

    async def read(sid):
        body = b""
        while True:
            out = await replica.next_chunks(sid, 4, 5.0)
            body += b"".join(out["chunks"])
            if out["done"]:
                return body

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t0 = time.perf_counter()
        bodies = [f.result(timeout=60)
                  for f in [on_loop(read(sid)) for sid in sids]]
        took = time.perf_counter() - t0
    finally:
        sys.setswitchinterval(was)
        for sid in sids:
            replica.cancel_stream(sid)
    assert bodies == [
        "".join(f"{i}:{j}\n" for j in range(n_chunks)).encode()
        for i in range(n_streams)]
    pulls = replica.stats()["stream_pulls"]
    assert pulls["timed_out"] == 0 and took < 5.0, (pulls, took)
    assert pulls["woken"] > n_streams and replica._parked == 0


def _replica_stats(deployment: str) -> dict:
    from ray_tpu.serve._private.controller import (
        CONTROLLER_NAME, SERVE_NAMESPACE)

    controller = ray_tpu.get_actor(CONTROLLER_NAME, namespace=SERVE_NAMESPACE)
    info = ray_tpu.get(controller.get_routing_info.remote(deployment),
                       timeout=30)
    (_, handle), = info["replicas"]
    return ray_tpu.get(handle.stats.remote(), timeout=30)


def _slow_streamer(n_chunks: int, gap_s: float):
    @serve.deployment
    class Slow:
        def __init__(self):
            self.inside = self.peak = 0

        def __call__(self, request):
            if request.method != "POST":
                self.inside += 1
                self.peak = max(self.peak, self.inside)
                time.sleep(float(request.query_params.get("nap", 0)))
                self.inside -= 1
                return {"unary": True, "peak": self.peak}

            def gen():
                for i in range(n_chunks):
                    yield f"chunk-{i}\n"
                    time.sleep(gap_s)

            return serve.StreamingResponse(gen())

    return Slow


def _read_stream(host, port, path, first_seen=None):
    conn = http.client.HTTPConnection(host, port, timeout=120)
    try:
        conn.request("POST", path, body=b"{}")
        resp = conn.getresponse()
        assert resp.status == 200
        data = resp.read(8)  # the first chunk is on the wire
        if first_seen is not None:
            first_seen.release()
        return data + resp.read()
    finally:
        conn.close()


def test_unary_request_is_answered_past_more_parked_pulls_than_max_concurrency(
        serve_instance):
    """A plain deployment's replica runs ONE request at a time
    (``max_concurrency`` 1).  Four idle streams park four pulls on it: they
    are tasks of its event loop in a lane of their own, so a unary request
    is still answered at once, by the one executor thread."""
    serve.run(_slow_streamer(2, 2.5).bind(), port=0)
    host, port = serve.get_http_address()
    first_seen = threading.Semaphore(0)
    with ThreadPoolExecutor(4) as pool:
        streams = [pool.submit(_read_stream, host, port, "/Slow", first_seen)
                   for _ in range(4)]
        for _ in range(4):
            assert first_seen.acquire(timeout=60)
        time.sleep(0.3)  # every stream's next pull has parked
        conn = http.client.HTTPConnection(host, port, timeout=60)
        try:
            t0 = time.perf_counter()
            conn.request("GET", "/Slow")
            resp = conn.getresponse()
            assert json.loads(resp.read())["unary"] is True
            assert time.perf_counter() - t0 < 1.0
        finally:
            conn.close()
        assert not any(f.done() for f in streams)  # ... while they waited
        for f in streams:
            assert f.result(timeout=60) == b"chunk-0\nchunk-1\n"

        def nap(_):
            conn = http.client.HTTPConnection(host, port, timeout=60)
            try:
                conn.request("GET", "/Slow?nap=0.15")
                return json.loads(conn.getresponse().read())["peak"]
            finally:
                conn.close()

        # the coroutine method did not make it an async actor's 1000
        assert max(pool.map(nap, range(3))) == 1
    pulls = _replica_stats("Slow")["stream_pulls"]
    assert pulls["woken"] >= 4 and pulls["timed_out"] >= 4


def test_streams_past_the_proxys_share_of_parks_poll_and_all_complete():
    """Parked pulls hold at most half of the proxy's executor pool (2 of 4
    threads here); the streams past that pull without parking and sleep
    between empty replies, as every stream did before, and all complete."""
    with serving(num_exec_threads=4):
        serve.run(_slow_streamer(3, 0.4).bind(), port=0)
        host, port = serve.get_http_address()
        with ThreadPoolExecutor(5) as pool:
            bodies = list(pool.map(
                lambda _: _read_stream(host, port, "/Slow"), range(5)))
        assert bodies == [b"chunk-0\nchunk-1\nchunk-2\n"] * 5
        pulls = _replica_stats("Slow")["stream_pulls"]
    # three streams polled every 20 ms through gaps of 0.4 s ...
    assert pulls["unparked"] >= 30, pulls
    assert pulls["woken"] >= 3, pulls  # ... while two were woken


def test_threaded_ingress_streams_the_same_bytes():
    with serving(async_ingress=False) as client:
        assert ray_tpu.get(client.proxy.ingress_stats.remote(),
                           timeout=30)["mode"] == "threaded"
        serve.run(_slow_streamer(4, 0.3).bind(), port=0)
        host, port = serve.get_http_address()
        t0 = time.perf_counter()
        first_seen = threading.Semaphore(0)
        with ThreadPoolExecutor(1) as pool:
            fut = pool.submit(_read_stream, host, port, "/Slow", first_seen)
            assert first_seen.acquire(timeout=60)
            first_at = time.perf_counter() - t0
            assert fut.result(timeout=60) == b"".join(
                f"chunk-{i}\n".encode() for i in range(4))
        total = time.perf_counter() - t0
        pulls = _replica_stats("Slow")["stream_pulls"]
    assert first_at < total - 0.6, (first_at, total)  # as produced
    # the connection's thread parks with its pull: woken a chunk, no poll
    assert pulls["woken"] >= 3 and pulls["unparked"] <= 6, pulls
