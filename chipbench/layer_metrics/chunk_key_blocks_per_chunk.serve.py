"""Key blocks a prompt chunk's queries walked, summed over the layers
that walk (blocks): the engine's counter ``chunk_key_blocks_walked``
over ``chunk_passes``, both from the ``counters`` of the window's
``engine.account`` spans (``chipbench/pass_ledger.py``; every chained
interval, a profiler session or not: a count is not stretched).  It
grows with how deep in their prompts the chunks are; 0 where a row's
table is one key block.  None without a chunk, or for a program whose
account carries no counters."""

from chipbench import pass_ledger


def read(obs):
    led = pass_ledger.engine(obs, every=True)
    if led is None or not led["counters"]["chunk_passes"]:
        return None
    return (led["counters"]["chunk_key_blocks_walked"]
            / led["counters"]["chunk_passes"])
