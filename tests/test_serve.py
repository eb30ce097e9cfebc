"""Serve tests (reference analogue: python/ray/serve/tests — HTTP against
a local serve instance, handle calls, batching, autoscaling logic)."""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from ray_tpu import serve


@pytest.fixture(autouse=True)
def _cleanup():
    yield
    serve.shutdown()


def test_handle_call_inproc():
    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return 2 * x

    h = serve.run(Doubler, use_actors=False)
    assert h.remote(21).result() == 42


def test_function_deployment_and_methods():
    @serve.deployment(name="adder")
    def add_one(x):
        return x + 1

    h = serve.run(add_one, use_actors=False)
    assert h.remote(1).result() == 2

    @serve.deployment
    class Multi:
        def __call__(self, x):
            return x

        def square(self, x):
            return x * x

    h2 = serve.run(Multi, use_actors=False)
    assert h2.square.remote(5).result() == 25


def test_bind_init_args():
    @serve.deployment
    class Scaled:
        def __init__(self, k):
            self.k = k

        def __call__(self, x):
            return self.k * x

    h = serve.run(Scaled.bind(10), use_actors=False)
    assert h.remote(4).result() == 40


def test_num_replicas_and_status():
    @serve.deployment(num_replicas=3)
    class Echo:
        def __call__(self, x):
            return x

    serve.run(Echo, use_actors=False)
    st = serve.status()
    assert st["Echo"]["replicas"] == 3


def test_http_proxy_roundtrip():
    @serve.deployment
    class Greeter:
        def __call__(self, req):
            name = (req or {}).get("name", "world")
            return {"hello": name}

    serve.run(Greeter, use_actors=False, http=True)
    addr = serve.proxy_address()
    with urllib.request.urlopen(f"{addr}/-/healthz", timeout=10) as r:
        assert json.load(r)["status"] == "ok"
    req = urllib.request.Request(
        f"{addr}/Greeter", data=json.dumps({"name": "tpu"}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        assert json.load(r)["result"] == {"hello": "tpu"}
    with urllib.request.urlopen(f"{addr}/-/routes", timeout=10) as r:
        assert json.load(r) == ["Greeter"]


def test_batching_collects():
    calls = []

    @serve.deployment(max_concurrent_queries=16)
    class Batched:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
        def handle(self, items):
            calls.append(len(items))
            return [i * 10 for i in items]

        def __call__(self, x):
            return self.handle(x)

    h = serve.run(Batched, use_actors=False)
    rs = [h.remote(i) for i in range(8)]
    out = sorted(r.result(timeout=30) for r in rs)
    assert out == [0, 10, 20, 30, 40, 50, 60, 70]
    assert max(calls) > 1  # at least one real batch formed


def test_batching_wrong_length_raises_clearly():
    """A batched fn returning the wrong number of results must fail every
    caller with an error naming the function and both lengths — never
    fan out misaligned results."""

    # the batch of four forms when the fourth caller arrives, never on
    # the timer: at 10 ms a loaded box flushed the threads' calls in
    # smaller batches, whose errors name other lengths than 3 and 4
    @serve.batch(max_batch_size=4, batch_wait_timeout_s=30)
    def truncating(items):
        return items[:-1]               # one result short

    import threading
    errs = []

    def call(i):
        try:
            truncating(i)
        except Exception as e:
            errs.append(e)

    threads = [threading.Thread(target=call, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(errs) == 4               # every caller fails, none hang
    msg = str(errs[0])
    assert isinstance(errs[0], ValueError)
    assert "truncating" in msg and "3" in msg and "4" in msg


def test_batching_non_sequence_result_raises_clearly():
    """dict / str / generator results of the 'right length' would zip
    apart into keys / characters / nothing — rejected with a TypeError
    up front (this was the silent-mismatch fan-out gap)."""

    for bad, typename in (
            ({"a": 1, "b": 2}, "dict"),            # len matches batch!
            ("ab", "str"),
            ((i for i in range(2)), "generator")):

        @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.01)
        def bad_fn(items, _bad=bad):
            return _bad

        import threading
        errs = []

        def call(i):
            try:
                bad_fn(i)
            except Exception as e:
                errs.append(e)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert len(errs) == 2, typename
        assert isinstance(errs[0], TypeError), typename
        assert typename in str(errs[0])
        assert "bad_fn" in str(errs[0])


def test_actor_replicas(rt_init):
    @serve.deployment(num_replicas=2)
    class PidEcho:
        def __call__(self, _):
            import os
            return os.getpid()

    h = serve.run(PidEcho, use_actors=True)
    pids = {h.remote(None).result(timeout=60) for _ in range(6)}
    assert len(pids) >= 1
    import os
    assert os.getpid() not in pids  # really ran out-of-process


def test_autoscaling_math():
    from ray_tpu.serve.controller import DeploymentState
    from ray_tpu.serve.deployment import (AutoscalingConfig, Deployment,
                                          DeploymentOptions)

    @serve.deployment(autoscaling_config={"min_replicas": 1,
                                          "max_replicas": 3,
                                          "target_ongoing_requests": 1.0})
    class Slow:
        def __call__(self, x):
            return x

    st = DeploymentState(Slow, use_actors=False)
    assert len(st.replicas) == 1
    st.replicas[0].ongoing = 5  # fake load
    st.autoscale_tick()
    assert len(st.replicas) == 2
    for r in st.replicas:
        r.ongoing = 0
    st.autoscale_tick()
    assert len(st.replicas) == 1


def test_batching_per_instance_isolation():
    @serve.deployment
    class Stateful:
        def __init__(self):
            self.seen = []

        @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.02)
        def handle(self, items):
            self.seen.extend(items)
            return [(id(self), i) for i in items]

        def __call__(self, x):
            return self.handle(x)

    a, b = Stateful.build_replica(), Stateful.build_replica()
    ra = a.handle(1)
    rb = b.handle(2)
    assert a.seen == [1] and b.seen == [2]  # no cross-instance leakage
    assert ra[1] != rb[1] or ra[0] != rb[0]


# -- asyncio proxy / streaming / ASGI / graphs / long-poll ------------------

def test_async_proxy_json_roundtrip():
    @serve.deployment
    class Echo:
        def __call__(self, req):
            return {"echo": req}

    serve.run(Echo, use_actors=False, http=True, proxy="asyncio")
    addr = serve.proxy_address()
    with urllib.request.urlopen(f"{addr}/-/healthz", timeout=10) as r:
        assert json.load(r)["status"] == "ok"
    req = urllib.request.Request(
        f"{addr}/Echo", data=json.dumps({"x": 3}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        assert json.load(r)["result"] == {"echo": {"x": 3}}
    with urllib.request.urlopen(f"{addr}/-/routes", timeout=10) as r:
        assert json.load(r) == ["Echo"]


def test_async_proxy_streaming_response():
    @serve.deployment
    class Streamer:
        def __call__(self, req):
            def gen():
                for i in range((req or {}).get("n", 3)):
                    yield {"i": i}
            return gen()

    serve.run(Streamer, use_actors=False, http=True, proxy="asyncio")
    addr = serve.proxy_address()
    req = urllib.request.Request(
        f"{addr}/Streamer", data=json.dumps({"n": 4}).encode())
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.headers.get("Transfer-Encoding") == "chunked"
        body = r.read()   # urllib de-chunks transparently
    payloads = [json.loads(x) for x in
                body.replace(b"}{", b"}\x00{").split(b"\x00")]
    assert payloads == [{"i": i} for i in range(4)]


def test_asgi_ingress():
    async def app(scope, receive, send):
        msg = await receive()
        body = msg.get("body", b"")
        await send({"type": "http.response.start", "status": 201,
                    "headers": [(b"content-type", b"text/plain"),
                                (b"x-path", scope["path"].encode())]})
        await send({"type": "http.response.body",
                    "body": b"got:" + body})

    dep = serve.ingress(app, name="api")
    serve.run(dep, use_actors=False, http=True, proxy="asyncio")
    addr = serve.proxy_address()
    req = urllib.request.Request(f"{addr}/api/items", data=b"payload")
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.status == 201
        assert r.headers["x-path"] == "/api/items"
        assert r.read() == b"got:payload"


def test_deployment_graph_inproc():
    @serve.deployment
    class Preprocess:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Model:
        def __init__(self, pre):
            self.pre = pre

        def __call__(self, x):
            return self.pre.remote(x).result() + 1

    graph = Model.bind(Preprocess)
    h = serve.run(graph, use_actors=False)
    assert h.remote(10).result() == 21
    # both nodes deployed
    assert set(serve.status().keys()) == {"Model", "Preprocess"}


def test_deployment_graph_actors(rt_init):
    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Chain:
        def __init__(self, inner):
            self.inner = inner   # unpickles as RemoteDeploymentHandle

        def __call__(self, x):
            return self.inner.remote(x).result() + 5

    h = serve.run(Chain.bind(Doubler), use_actors=True)
    assert h.remote(7).result(timeout=120) == 19


def test_long_poll_host_and_route_push():
    from ray_tpu.serve.long_poll import LongPollHost

    host = LongPollHost()
    assert host.listen({"k": 0}, timeout=0.05) == {}
    host.notify("k", ["a"])
    out = host.listen({"k": 0}, timeout=5)
    assert out["k"][0] == 1 and out["k"][1] == ["a"]
    # blocked listener wakes on notify
    got = {}

    def wait():
        got.update(host.listen({"k": 1}, timeout=10))
    t = threading.Thread(target=wait)
    t.start()
    time.sleep(0.1)
    host.notify("k", ["a", "b"])
    t.join(timeout=5)
    assert got["k"][1] == ["a", "b"]

    # end-to-end: the asyncio proxy's route table follows deploys
    @serve.deployment
    class A:
        def __call__(self, _):
            return 1

    @serve.deployment
    class B:
        def __call__(self, _):
            return 2

    serve.run(A, use_actors=False, http=True, proxy="asyncio")
    addr = serve.proxy_address()
    serve.run(B, use_actors=False)
    deadline = time.time() + 10
    while time.time() < deadline:
        with urllib.request.urlopen(f"{addr}/-/routes", timeout=10) as r:
            routes = json.load(r)
        if routes == ["A", "B"]:
            break
        time.sleep(0.1)
    assert routes == ["A", "B"]
