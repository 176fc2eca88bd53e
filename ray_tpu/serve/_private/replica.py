"""ServeReplica: the actor hosting one copy of a deployment's callable.

Analog of ``python/ray/serve/_private/replica.py:250`` (RayServeReplica):
constructs the user's class (or wraps a function), executes requests,
applies ``user_config`` through ``reconfigure``, and answers health checks.
TPU-backed deployments get here with ``ray_actor_options={"num_tpus": 1}``
so the scheduler pins a chip before the model loads.
"""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, Optional, Tuple

import cloudpickle

from ray_tpu.serve.exceptions import ReplicaDrainingError
from ray_tpu.util import tracing

# The longest a ``next_chunks`` pull parks on an idle stream before it
# answers empty and the proxy asks again: a safety net under the wake-ups
# (a put, the producer's end, a cancel), far below the 120 s the proxy gives
# a pull's ``get``.  Nothing a deployment would tune: it is only ever read by
# a stream whose producer is silent for longer.
PULL_WAIT_S = 1.0
# ``next_chunks`` runs in a concurrency group of its own (the controller
# declares it on every replica, as it does "control"): the head's dispatch
# window and the worker's bound on running coroutines are a group's, so
# parked pulls take nothing from the lane ``handle_request`` runs in.  At
# most half of the group's calls may be PARKED; a pull past that answers at
# once (``parked: False``) and the proxy paces it, so a pull is never queued
# behind sleepers.
STREAM_GROUP = "stream"
STREAM_GROUP_CONCURRENCY = 256
MAX_PARKED_PULLS = STREAM_GROUP_CONCURRENCY // 2


def _wake(state: Dict[str, Any]) -> None:
    """Wake the pull parked on this stream, if one is (any thread).  The
    parker publishes ``waker`` BEFORE it looks at the queue again and the
    producer looks for it AFTER its put, so one of them sees the other."""
    waker = state["waker"]
    if waker is not None:
        waker()


class ServeReplica:
    def __init__(
        self,
        deployment_name: str,
        replica_tag: str,
        serialized_def: bytes,
        init_args: Tuple,
        init_kwargs: Dict,
        user_config: Optional[Any] = None,
    ):
        self.deployment_name = deployment_name
        self.replica_tag = replica_tag
        func_or_class = cloudpickle.loads(serialized_def)
        if isinstance(func_or_class, type):
            self.callable = func_or_class(*init_args, **init_kwargs)
            self._is_function = False
        else:
            self.callable = func_or_class
            self._is_function = True
        if user_config is not None:
            self.reconfigure(user_config)
        # lock-guarded: batched replicas serve requests from concurrent
        # threads, and a bare += (or a max() read-modify-write) can lose or
        # regress counts under preemption
        import threading

        self._stats_lock = threading.Lock()
        self._num_requests = 0
        self._inflight = 0
        self._draining = False
        self._drain_deadline: Optional[float] = None
        self._start_time = time.time()
        # live streaming responses: stream id -> iterator (the proxy pulls
        # batches of chunks with next_chunks until exhausted)
        self._streams: Dict[str, Any] = {}
        self._streams_lock = threading.Lock()
        # pulls parked right now, and every reply ``next_chunks`` made by
        # kind; both touched on the worker's event loop alone
        self._parked = 0
        self._stream_pulls = {"woken": 0, "timed_out": 0, "unparked": 0}

    def handle_request(self, method_name: str, args: Tuple, kwargs: Dict) -> Any:
        """Run one request (``replica.py:250`` handle_request analog).
        ``method_name='__call__'`` hits the callable itself.  During a
        drain's graceful window, requests that raced past a stale routing
        table still EXECUTE (the drain loop waits for them too — a handle
        caller must not see an error on a request the pre-drain replica
        would have served); only once the window has lapsed — when the
        controller is about to kill the actor anyway — does the typed
        refusal fire, so the caller gets a cleanly retryable error
        instead of a mid-execution RayActorError."""
        with self._stats_lock:
            if self._draining and (
                    self._drain_deadline is None
                    or time.monotonic() >= self._drain_deadline):
                raise ReplicaDrainingError(self.replica_tag)
            self._num_requests += 1
            self._inflight += 1
        ctx = tracing.current_context()
        if ctx is not None and "t_root" in ctx:
            # ingress (the root's start) -> the router submitted this call:
            # both stamps are the proxy's, read here so that the stage
            # lands in the aggregate of the process that reports it.  The
            # tree already has the router's admission span over it
            tracing.fold("serve.route", max(0.0, ctx["t"] - ctx["t_root"]))
        try:
            return self._run_request(method_name, args, kwargs)
        finally:
            with self._stats_lock:
                self._inflight -= 1

    def _run_request(self, method_name: str, args: Tuple, kwargs: Dict) -> Any:
        if self._is_function:
            if method_name not in ("__call__", None):
                raise AttributeError(
                    f"function deployment {self.deployment_name!r} has no "
                    f"method {method_name!r}"
                )
            result = self.callable(*args, **kwargs)
        elif method_name == "__call__":
            if not callable(self.callable):
                raise TypeError(
                    f"deployment {self.deployment_name!r} defines no __call__; "
                    "invoke a named method via handle.<method>.remote()"
                )
            result = self.callable(*args, **kwargs)
        else:
            result = getattr(self.callable, method_name)(*args, **kwargs)
        from ray_tpu.serve._private.http_util import (
            Request as _HttpRequest,
            StreamingResponse,
        )

        if isinstance(result, StreamingResponse):
            if not (args and isinstance(args[0], _HttpRequest)):
                raise TypeError(
                    "StreamingResponse is only supported for HTTP requests "
                    "(the proxy drains it incrementally); a DeploymentHandle "
                    "caller should return/iterate the data directly")
            return self._register_stream(result)
        return result

    def _register_stream(self, result) -> Dict[str, Any]:
        """Drain the generator on a dedicated thread into a bounded queue
        so follow-up ``next_chunks`` pulls never BLOCK a replica executor
        thread between chunks (N slow streams would otherwise pin N
        threads and exhaust max_concurrency).  A pull that finds the queue
        empty PARKS, but as a task of the worker's event loop
        (``next_chunks`` is a coroutine): this thread wakes it after a put
        and at its end, and no executor thread waits with it."""
        import queue as queue_mod
        import threading
        import uuid

        from ray_tpu.serve._private.http_util import encode_chunk

        sid = uuid.uuid4().hex
        # the request's trace context: the generator's body first runs on
        # the stream thread, which adopts it (an engine request made there
        # chains under this replica task); the first ``next_chunks`` reply
        # with data closes the stages up to the first reply under it, the
        # finishing one those of the stream (``stage_ctx``, cleared then).
        # Clock reads: one at the first put, one when the producer ends
        # (``ended_t``: the last put has just returned), one a reply that
        # carries data; none a chunk
        ctx = tracing.current_context()
        state = {"q": queue_mod.Queue(maxsize=64), "done": False,
                 "error": None, "stop": threading.Event(),
                 "stage_ctx": ctx if ctx and "t_root" in ctx else None,
                 "first_put_t": None, "ended_t": None,
                 "first_data_t": None, "last_data_t": None, "n_chunks": 0,
                 "waker": None}

        def drain(it=iter(result.iterable)):
            token = tracing.adopt(ctx)
            try:
                for chunk in it:
                    data = encode_chunk(chunk)
                    if state["first_put_t"] is None:
                        # stamped BEFORE the put: a pull that finds the
                        # chunk must find its time too
                        state["first_put_t"] = time.perf_counter()
                    while not state["stop"].is_set():
                        try:
                            state["q"].put(data, timeout=0.2)
                            _wake(state)
                            break
                        except queue_mod.Full:
                            continue
                    if state["stop"].is_set():
                        if hasattr(it, "close"):
                            it.close()
                        return
            except Exception as e:  # noqa: BLE001 — surfaced to the proxy
                state["error"] = f"{type(e).__name__}: {e}"
            finally:
                state["ended_t"] = time.perf_counter()  # before ``done``
                state["done"] = True
                _wake(state)
                tracing.restore(token)

        threading.Thread(target=drain, daemon=True,
                         name=f"serve-stream-{sid[:8]}").start()
        with self._streams_lock:
            self._streams[sid] = state
        return {"__serve_stream__": sid, "content_type": result.content_type}

    async def next_chunks(self, sid: str, max_n: int = 16,
                          wait_s: float = 0.0) -> Dict[str, Any]:
        """Up to ``max_n`` buffered chunks; ``done`` unregisters the stream,
        ``error`` carries a producer failure.  With ``wait_s`` a pull that
        finds the queue empty and the producer alive PARKS until a chunk is
        put, the producer ends, the stream is cancelled or ``wait_s`` runs
        out, and says so (``parked``; an empty reply without it asks the
        proxy to pace itself).  Parked, it is a task of the worker's event
        loop awaiting a future that the stream's thread resolves through
        ``call_soon_threadsafe``: it pins no executor thread, and the group
        it runs in (``STREAM_GROUP``) keeps it out of the requests' lane."""
        import queue as queue_mod

        with self._streams_lock:
            state = self._streams.get(sid)
        if state is None:
            self._stream_pulls["unparked"] += 1
            return {"chunks": [], "done": True, "parked": False}
        kind = "unparked"
        if (wait_s > 0 and state["waker"] is None
                and self._parked < MAX_PARKED_PULLS):
            kind = await self._park(state, wait_s)
        self._stream_pulls[kind] += 1
        parked = kind != "unparked"
        if state["stop"].is_set():  # cancelled: the producer is winding up
            return {"chunks": [], "done": True, "parked": parked}
        chunks = []
        for _ in range(max_n):
            try:
                chunks.append(state["q"].get_nowait())
            except queue_mod.Empty:
                break
        finished = state["done"] and state["q"].empty()
        if finished:
            self.cancel_stream(sid)
        ctx = state["stage_ctx"]
        if ctx is not None and chunks:
            now = time.perf_counter()
            if state["first_data_t"] is None:
                # the first reply that carries data: how long the first
                # chunk lay in the queue before this pull returned it, and,
                # on its own two clock reads, the whole way from ingress to
                # here (the stage the others must add up to)
                state["first_data_t"] = now
                tracing.emit_stage(
                    "serve.pickup", now - state["first_put_t"], ctx)
                tracing.emit_stage(
                    "serve.first_reply", tracing.since(ctx["t_root"]), ctx)
            state["last_data_t"] = now
            state["n_chunks"] += len(chunks)
        if ctx is not None and finished:
            state["stage_ctx"] = None
            if state["first_data_t"] is not None:
                self._emit_stream_stages(state, ctx)
        return {"chunks": chunks, "done": finished,
                "error": state["error"] if finished else None,
                "parked": parked}

    async def _park(self, state: Dict[str, Any], wait_s: float) -> str:
        """Wait, as a task of the running loop, until the stream has
        something to say.  The kind of pull this made: ``"woken"`` by the
        producer (a put, its end) or a cancel, ``"timed_out"`` after
        ``wait_s``, ``"unparked"`` if there was something by the time the
        waker was published (nothing was waited for)."""
        loop = asyncio.get_running_loop()
        woke = loop.create_future()

        def wake(kind: str) -> None:
            if not woke.done():
                woke.set_result(kind)

        state["waker"] = lambda: loop.call_soon_threadsafe(wake, "woken")
        self._parked += 1
        try:
            if (not state["q"].empty() or state["done"]
                    or state["stop"].is_set()):
                return "unparked"
            timer = loop.call_later(wait_s, wake, "timed_out")
            try:
                return await woke
            finally:
                timer.cancel()
        finally:
            self._parked -= 1
            state["waker"] = None

    @staticmethod
    def _emit_stream_stages(state: Dict[str, Any], ctx) -> None:
        """The finishing reply of a stream that delivered data.  Both stages
        end at the LAST reply that carried data (this one may be empty, a
        pull later): how long the last chunk lay in the queue, and, on its
        own two clock reads, first data reply -> last data reply, which the
        request's other decode stages must add up to (``serve.llm.STAGES``);
        the same over the gaps between the chunks delivered is folded as
        the pace this replica gave the request."""
        last, n = state["last_data_t"], state["n_chunks"]
        ended = time.time() - (time.perf_counter() - last)
        tracing.emit_stage(
            "serve.last_pickup", max(0.0, last - state["ended_t"]), ctx,
            ts=ended)
        streamed = last - state["first_data_t"]
        tracing.emit_stage(
            "serve.stream", streamed, ctx, ts=ended, stream_chunks=n)
        if n > 1:
            tracing.fold("serve.stream_per_chunk", streamed / (n - 1))

    def cancel_stream(self, sid: str) -> bool:
        with self._streams_lock:
            state = self._streams.pop(sid, None)
        if state is not None:
            state["stop"].set()
            _wake(state)
        return state is not None

    def reconfigure(self, user_config: Any) -> bool:
        """Apply a new ``user_config`` in place (deployment_state reconciler
        calls this instead of restarting the replica)."""
        if not self._is_function and hasattr(self.callable, "reconfigure"):
            self.callable.reconfigure(user_config)
        return True

    def ping(self) -> str:
        """Liveness probe: a dead worker fails the call with RayActorError,
        which is the controller's death signal."""
        return "pong"

    def stats(self) -> Dict[str, Any]:
        import os

        with self._stats_lock:
            inflight = self._inflight
            draining = self._draining
        return {
            "deployment": self.deployment_name,
            "replica_tag": self.replica_tag,
            "num_requests": self._num_requests,
            "inflight": inflight,
            "draining": draining,
            "pid": os.getpid(),
            "uptime_s": time.time() - self._start_time,
            # ``next_chunks`` replies by kind: after a park that a put, the
            # producer's end or a cancel ended / after one that ran out /
            # without one (data at once, or not allowed to park)
            "stream_pulls": dict(self._stream_pulls),
        }

    # -- graceful draining ---------------------------------------------
    def prepare_for_drain(self, grace_s: Optional[float] = None) -> Dict[str, Any]:
        """Begin draining: the controller calls this AFTER pulling the
        replica from the routing set, then polls :meth:`drain_status`
        until in-flight work hits zero (or the graceful window lapses)
        before killing the actor.  ``grace_s`` bounds the window in
        which racing requests are still served (see handle_request);
        None refuses new work immediately."""
        with self._stats_lock:
            self._draining = True
            self._drain_deadline = (
                time.monotonic() + grace_s if grace_s is not None else None)
        return self.drain_status()

    def drain_status(self) -> Dict[str, Any]:
        """{"inflight": n, "streams": m, "draining": bool} — zero inflight
        AND zero live streams means the replica is safe to terminate
        without losing accepted work."""
        with self._stats_lock:
            inflight = self._inflight
            draining = self._draining
        with self._streams_lock:
            streams = len(self._streams)
        return {"inflight": inflight, "streams": streams,
                "draining": draining}

    def prepare_for_shutdown(self) -> bool:
        """Graceful-shutdown hook: user callables may define ``__del__`` or
        ``shutdown``; call the latter if present."""
        if not self._is_function and hasattr(self.callable, "shutdown"):
            try:
                self.callable.shutdown()
            except Exception:
                pass
        return True
