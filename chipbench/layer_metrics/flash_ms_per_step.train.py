"""Summed device time of the Pallas kernels per step (ms).

In this step the only Pallas kernels are the flash-attention calls of
``ops/flash_attention.py``: per layer the forward, the forward run
again by the backward pass (remat policy ``dots`` saves no attention
output) and the two backward kernels.  The looked-at trace (PR 25)
shows them as ``%tpu_custom_call.N`` with ``kernel_metadata={}``, so no
kernel's own name can be matched; ``trace_reduce.short_name`` marks
every Mosaic custom call ``pallas:``.
"""


def read(obs):
    t = obs.get("trace")
    steps = obs.get("trace_steps")
    if not t or not steps:
        return None
    secs = sum(s for name, s in t["op_seconds"].items()
               if name.startswith("pallas:"))
    return 1e3 * secs / steps if secs else None
