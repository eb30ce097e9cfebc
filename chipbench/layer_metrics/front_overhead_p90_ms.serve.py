"""What the serve front adds to a request's time to first token (ms):
p90 over the window's requests of (``front.request`` start to its
``first_chunk_ns``: body parsed to first streamed chunk written and
drained) minus (``request.queue`` + ``request.prefill``) of the same
trace: proxy, handle, the two thread hops, the stream's executor round
trip and the chunk write."""

from chipbench import spans


def read(obs):
    values = []
    for r in spans.window_requests(obs):
        front = r.get("front.request")
        if front is None or "request.prefill" not in r \
                or "first_chunk_ns" not in front["attributes"]:
            continue
        to_first_chunk = (front["attributes"]["first_chunk_ns"]
                          - front["t0_ns"]) / 1e6
        values.append(to_first_chunk - spans.ms(r["request.queue"])
                      - spans.ms(r["request.prefill"]))
    return spans.p90_ms(values)
