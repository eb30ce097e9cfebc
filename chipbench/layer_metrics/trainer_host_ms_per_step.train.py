"""The trainer loop's own host work per step (ms): summed
``train.next_batch`` + ``train.shard_batch`` + ``train.report`` less
``train.fetch`` (inside ``train.report``: the wait for the device, not
host work) over the traced steps, i.e. those with a ``train.step`` span.
The benchmark starts and stops its profiler inside ``next()``: the
first traced step has no ``train.next_batch`` span, and the one that
holds the profiler's stop belongs to a step that is not traced."""

from chipbench import spans

ADD = ("train.next_batch", "train.shard_batch", "train.report")


def read(obs):
    all_spans = spans.finished_spans(obs)
    steps = {s["attributes"]["step"]
             for s in spans.named(all_spans, "train.step")}
    if not steps:
        return None
    total = 0.0
    for s in all_spans:
        if s["attributes"].get("step") not in steps:
            continue
        if s["name"] in ADD:
            total += spans.ms(s)
        elif s["name"] == "train.fetch":
            total -= spans.ms(s)
    return total / len(steps)
