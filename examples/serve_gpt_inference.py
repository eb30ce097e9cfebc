"""Serve a GPT with the continuous-batching inference engine and stream
a generation over HTTP.

Run (CPU, tiny config):  JAX_PLATFORMS=cpu python examples/serve_gpt_inference.py
On a TPU host the same script serves from the chip: the engine runs in
THIS process (``use_actors=False`` — one process holds the chip), the
HTTP proxy is a CPU-pinned worker.  The full-width GPT-2 124M proof is
``python chip_smoke.py``.  Compiled programs are cached in
$JAX_COMPILATION_CACHE_DIR, else in <checkout>/.jax_cache.
(see ARCHITECTURE.md "Inference engine" for the slot lifecycle)."""

import json
import os
import socket
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax.numpy as jnp

from ray_tpu import serve
from ray_tpu.inference import (EngineConfig, build_gpt_deployment,
                               parse_stream_chunks)
from ray_tpu.models import gpt


def main():
    from ray_tpu._compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32)   # swap for gpt2_124m()
    serve.run(build_gpt_deployment(
        cfg=cfg, engine_cfg=EngineConfig(max_slots=8), seed=0),
        use_actors=False, http=True)
    addr = serve.proxy_address()
    print(f"serving at {addr}/v1/generate")

    # one-shot JSON
    import urllib.request
    req = urllib.request.Request(
        addr + "/v1/generate",
        data=json.dumps({"prompt": [3, 1, 4, 1, 5],
                         "max_tokens": 8}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        print("json:", json.loads(resp.read())["result"]["tokens"])

    # chunked token streaming (raw socket: urllib buffers whole bodies)
    host, port = addr[len("http://"):].split(":")
    body = json.dumps({"prompt": "hello", "max_tokens": 16,
                       "stream": True}).encode()
    with socket.create_connection((host, int(port)), timeout=120) as s:
        s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                  b"Content-Type: application/json\r\n"
                  + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        buf = b""
        while b"0\r\n\r\n" not in buf:
            data = s.recv(4096)
            if not data:   # truncated stream (server signals errors by
                break      # closing without the terminal 0-chunk)
            buf += data
    payload = buf.split(b"\r\n\r\n", 1)[1]
    for chunk in parse_stream_chunks(payload):
        print("chunk:", chunk)

    serve.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
