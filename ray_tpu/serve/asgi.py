"""Asyncio ingress: concurrent HTTP proxy with streaming + ASGI support.

Reference capability: the uvicorn/starlette proxy
(python/ray/serve/_private/http_proxy.py:230,399 — an asyncio event
loop multiplexes thousands of in-flight requests; responses may stream;
user apps may be ASGI applications via @serve.ingress).  Dependency-free
here: a hand-rolled HTTP/1.1 server on asyncio.start_server, chunked
transfer-encoding for iterator results, and a minimal ASGI 3.0 driver
for ingress apps.

Routes stay in a local table refreshed by the controller's long-poll
host — the proxy never reaches into controller state per request
(reference: proxy route table via LongPollClient).
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
from typing import Any, Optional
from urllib.parse import unquote, urlparse

from ray_tpu.serve.deployment import Deployment, DeploymentOptions
from ray_tpu.serve.http_proxy import _jsonable
from ray_tpu.serve.long_poll import LongPollClient
from ray_tpu.util import tracing


class _ASGIReplica:
    """Replica body driving a user ASGI app: one request-response cycle
    per call, messages collected and returned as a plain dict so the
    result crosses process boundaries."""

    def __init__(self, app):
        self._app = app

    def handle_asgi(self, scope: dict, body: bytes) -> dict:
        async def drive():
            sent_body = False
            messages: list = []

            async def receive():
                nonlocal sent_body
                if sent_body:
                    return {"type": "http.disconnect"}
                sent_body = True
                return {"type": "http.request", "body": body,
                        "more_body": False}

            async def send(msg):
                messages.append(msg)

            full_scope = dict(scope)
            full_scope.setdefault("type", "http")
            full_scope.setdefault("asgi", {"version": "3.0"})
            await self._app(full_scope, receive, send)
            return messages

        messages = asyncio.run(drive())
        status, headers, chunks = 200, [], []
        for m in messages:
            if m["type"] == "http.response.start":
                status = m["status"]
                headers = [(bytes(k).decode("latin1"),
                            bytes(v).decode("latin1"))
                           for k, v in m.get("headers", [])]
            elif m["type"] == "http.response.body":
                chunks.append(bytes(m.get("body", b"")))
        return {"status": status, "headers": headers,
                "body": b"".join(chunks)}


def ingress(asgi_app, *, name: Optional[str] = None,
            num_replicas: int = 1,
            max_concurrent_queries: int = 32) -> Deployment:
    """Wrap an ASGI application as a deployment (reference:
    @serve.ingress(fastapi_app), serve/api.py ingress)."""
    dep = Deployment(_ASGIReplica, DeploymentOptions(
        name=name or getattr(asgi_app, "__name__", "asgi_app"),
        num_replicas=num_replicas,
        max_concurrent_queries=max_concurrent_queries),
        init_args=(asgi_app,))
    dep.is_asgi = True
    return dep


def _shed_retry_after(e: BaseException):
    """Seconds from a fleet ShedError (duck-typed so this module never
    imports the fleet/inference stack), else None."""
    ra = getattr(e, "retry_after_s", None)
    try:
        return float(ra) if ra is not None else None
    except (TypeError, ValueError):
        return None


# the loop's time between two ``front.account`` spans, checked where a
# streamed chunk has been written
ACCOUNT_EVERY_NS = 1_000_000_000


class AsyncHttpProxy:
    """Concurrent HTTP/1.1 ingress on an asyncio loop thread.

    Each connection is an asyncio task; replica calls run on the default
    executor so slow handlers never stall the accept loop.  Iterator /
    generator results stream as chunked transfer-encoding.  Fleet-shed
    requests (admission refusal) come back as ``429`` with a
    ``Retry-After`` header instead of a generic 500."""

    def __init__(self, controller, host: str = "127.0.0.1", port: int = 0):
        self.controller = controller
        self._host_arg, self._port_arg = host, port
        self.host: str = host
        self.port: int = 0
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._server = None
        self._started = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # dedicated, sized pool for blocking replica calls: the loop's
        # default executor is shared and small, which would head-of-line
        # block unrelated requests behind slow handlers.  Sized for
        # fleet-scale ingress: each in-flight request holds one worker
        # for its full latency, and admission (not this pool) must be
        # what says no — a too-small pool is an invisible unbounded
        # queue in FRONT of the admission controller
        from concurrent.futures import ThreadPoolExecutor
        self._executor = ThreadPoolExecutor(
            max_workers=256, thread_name_prefix="raytpu-serve-call")
        # long-polled route table: never touch controller state per
        # request (reference: proxy LongPollClient on route updates)
        self._routes: set[str] = set(controller.deployments.keys())
        # the gaps between a streamed response's chunks as they were
        # written and drained, counted always; the loop thread is their
        # one writer and carries them to the ring (``_chunk_written``)
        self._write_gaps = tracing.Histogram()
        self._profiled = False
        self._account_t1_ns = time.monotonic_ns()
        self._lp = LongPollClient(
            controller.long_poll, ["routes"],
            lambda key, snapshot: self._set_routes(snapshot))

    def _set_routes(self, snapshot) -> None:
        self._routes = set(snapshot or ())

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="raytpu-serve-asgi")
        self._thread.start()
        if not self._started.wait(timeout=10):
            raise RuntimeError("asyncio proxy failed to start")

    def _run(self) -> None:
        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)

        async def boot():
            self._server = await asyncio.start_server(
                self._handle_conn, self._host_arg, self._port_arg)
            self.port = self._server.sockets[0].getsockname()[1]
            self._started.set()
        self._loop.run_until_complete(boot())
        try:
            self._loop.run_forever()
        finally:
            self._loop.close()

    def stop(self) -> None:
        self._lp.stop()
        self._executor.shutdown(wait=False)
        if self._loop is None:
            return

        def _shutdown():
            if self._server is not None:
                self._server.close()
            self._loop.stop()
        self._loop.call_soon_threadsafe(_shutdown)
        if self._thread is not None:
            self._thread.join(timeout=5)

    # ------------------------------------------------------------- serving

    async def _handle_conn(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                try:
                    req = await self._read_request(reader)
                except ValueError:   # malformed framing (bad length)
                    await self._respond_json(writer, 400,
                                             {"error": "bad request"})
                    break
                if req is None:
                    break
                try:
                    keep_alive = await self._dispatch(writer, *req)
                except (ConnectionError, asyncio.IncompleteReadError):
                    raise
                except Exception as e:
                    # last-resort 500: a dispatch bug (or a replica
                    # iterator raising mid-stream) must never silently
                    # drop the connection; if headers already went out
                    # the write fails and the close signals truncation
                    try:
                        await self._respond_json(writer, 500,
                                                 {"error": str(e)})
                    except Exception:
                        pass
                    break
                if not keep_alive:
                    break
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        finally:
            try:
                writer.close()
            except Exception:
                pass

    async def _read_request(self, reader):
        line = await reader.readline()
        if not line:
            return None
        try:
            method, target, _version = line.decode("latin1").split()
        except ValueError:
            return None
        headers: dict[str, str] = {}
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            k, _, v = h.decode("latin1").partition(":")
            headers[k.strip().lower()] = v.strip()
        n = int(headers.get("content-length") or 0)
        body = await reader.readexactly(n) if n else b""
        return method, target, headers, body

    async def _dispatch(self, writer, method, target, headers,
                        body) -> bool:
        parsed = urlparse(target)
        path = unquote(parsed.path)
        stripped = path.strip("/")
        if stripped == "-/healthz":
            await self._respond_json(writer, 200, {"status": "ok"})
            return True
        if stripped == "-/routes":
            await self._respond_json(writer, 200, sorted(self._routes))
            return True
        name = stripped.split("/")[0]
        if name not in self._routes:
            await self._respond_json(writer, 404,
                                     {"error": f"no route /{name}"})
            return True
        try:
            state = self.controller.get(name)
        except KeyError:
            await self._respond_json(writer, 404,
                                     {"error": f"no route /{name}"})
            return True
        # always on: the root of the request's trace, from the parsed
        # body to the last byte written
        with tracing.span("front.request", kind="server", always=True,
                          route=name) as front:
            return await self._call_replica(writer, state, method, path,
                                            parsed, headers, body, front)

    async def _call_replica(self, writer, state, method, path, parsed,
                            headers, body, front) -> bool:
        """Call the deployment and write its answer.  The call crosses
        to an executor thread, which does not carry contextvars: the
        span's context goes with it explicitly."""
        ctx = front.context()
        loop = asyncio.get_running_loop()
        if getattr(state.deployment, "is_asgi", False):
            scope = {
                "type": "http", "method": method, "path": path,
                "raw_path": path.encode(), "root_path": "",
                "query_string": parsed.query.encode(),
                "headers": [(k.encode("latin1"), v.encode("latin1"))
                            for k, v in headers.items()],
            }
            from ray_tpu.serve.handle import DeploymentHandle
            handle = DeploymentHandle(state, "handle_asgi")
            try:
                out = await loop.run_in_executor(
                    self._executor, tracing.call_in_context, ctx,
                    lambda: handle.remote(scope, body).result(timeout=120))
            except Exception as e:
                # same contract as the JSON path: app errors become 500s,
                # never dropped connections
                await self._respond_json(writer, 500, {"error": str(e)})
                return True
            await self._respond_raw(writer, out["status"], out["headers"],
                                    out["body"])
            return True

        try:
            arg = json.loads(body) if body else None
        except json.JSONDecodeError:
            arg = body.decode("utf-8", "replace")
        if isinstance(arg, dict) and getattr(state, "fleet", None) \
                is not None:
            # fleet envelope fields may ride headers (curl-friendly);
            # the JSON body wins when both are present
            for header, field in (("x-priority", "priority"),
                                  ("x-model", "model")):
                v = headers.get(header)
                if v is not None:
                    arg.setdefault(field, v)
        from ray_tpu.serve.handle import DeploymentHandle
        handle = DeploymentHandle(state)
        try:
            out = await loop.run_in_executor(
                self._executor, tracing.call_in_context, ctx,
                lambda: handle.remote(arg).result(timeout=120))
        except Exception as e:
            retry_after = _shed_retry_after(e)
            if retry_after is not None:
                # admission refusal: explicit load shedding, not a
                # server fault — tell the client when to come back
                import math
                await self._respond_json(
                    writer, 429, {"error": str(e),
                                  "retry_after_s": retry_after},
                    extra_headers=[("Retry-After",
                                    str(max(1, math.ceil(retry_after))))])
                return True
            await self._respond_json(writer, 500, {"error": str(e)})
            return True
        if hasattr(out, "__next__") or hasattr(out, "__anext__"):
            try:
                await self._respond_stream(writer, out, loop, front)
            except (ConnectionError, asyncio.IncompleteReadError):
                raise
            except Exception:
                # headers are already on the wire: injecting a 500 would
                # corrupt the chunked framing, so close WITHOUT the
                # terminating 0-chunk — truncation is the error signal
                pass
            finally:
                # ALWAYS close the result generator: an abandoned
                # consumer (client disconnect mid-stream) must propagate
                # GeneratorExit into the replica body so the engine
                # request is cancelled and its slot freed — GC timing is
                # not a cancellation policy.  Async generators expose
                # aclose(), not close().
                aclose = getattr(out, "aclose", None)
                close = getattr(out, "close", None)
                try:
                    if aclose is not None:
                        await aclose()
                    elif close is not None:
                        await loop.run_in_executor(self._executor, close)
                except Exception:
                    pass
            return False   # chunked stream ends the connection
        await self._respond_json(writer, 200, {"result": _jsonable(out)})
        return True

    # ------------------------------------------------------------ responses

    async def _respond_json(self, writer, status: int, payload,
                            extra_headers=()) -> None:
        body = json.dumps(payload).encode()
        await self._respond_raw(
            writer, status,
            [("Content-Type", "application/json"), *extra_headers], body)

    async def _respond_raw(self, writer, status: int, headers, body: bytes):
        lines = [f"HTTP/1.1 {status} X".encode()]
        seen = {k.lower() for k, _ in headers}
        hdrs = list(headers)
        if "content-length" not in seen:
            hdrs.append(("Content-Length", str(len(body))))
        for k, v in hdrs:
            lines.append(f"{k}: {v}".encode("latin1"))
        writer.write(b"\r\n".join(lines) + b"\r\n\r\n" + body)
        await writer.drain()

    def _chunk_written(self, since_ns: int) -> int:
        """A streamed chunk has been written and drained, its
        response's last one at ``since_ns`` (0: this is its first) ->
        now.  The time between the two goes to the proxy's histogram,
        and once the loop has spent ``ACCOUNT_EVERY_NS`` since the last
        one the histogram so far goes to the ring as a ``front.account``
        span (``tracing.record_account``); nothing is written while
        nothing streams."""
        now = time.monotonic_ns()
        if since_ns:
            self._write_gaps.add(now - since_ns)
        if tracing.profiling():
            self._profiled = True
        if now - self._account_t1_ns >= ACCOUNT_EVERY_NS:
            touched, self._profiled = self._profiled, False
            self._account_t1_ns = tracing.record_account(
                "front.account", self._account_t1_ns, now, touched,
                proxy=f"{self.host}:{self.port}",
                write_gaps=self._write_gaps.snapshot())
        return now

    async def _respond_stream(self, writer, it, loop, front) -> None:
        """Chunked transfer-encoding over a (sync) iterator result —
        each chunk flushes as the replica produces it (reference:
        StreamingResponse through the proxy).  ``front`` (the request's
        span) is told when the first chunk is written and drained."""
        written_ns = 0
        writer.write(b"HTTP/1.1 200 X\r\n"
                     b"Content-Type: application/octet-stream\r\n"
                     b"Transfer-Encoding: chunked\r\n"
                     b"Connection: close\r\n\r\n")
        await writer.drain()

        async def write_chunk(chunk):
            nonlocal written_ns
            data = (chunk if isinstance(chunk, bytes)
                    else json.dumps(_jsonable(chunk)).encode())
            writer.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
            await writer.drain()
            first = not written_ns
            written_ns = self._chunk_written(written_ns)
            if first:
                front.set(first_chunk_ns=written_ns)

        if hasattr(it, "__anext__"):
            # async generator results drive directly on this loop
            async for chunk in it:
                await write_chunk(chunk)
        else:
            _SENTINEL = object()

            def next_chunk():
                try:
                    return next(it)
                except StopIteration:
                    return _SENTINEL

            while True:
                chunk = await loop.run_in_executor(self._executor, next_chunk)
                if chunk is _SENTINEL:
                    break
                await write_chunk(chunk)
        writer.write(b"0\r\n\r\n")
        await writer.drain()
