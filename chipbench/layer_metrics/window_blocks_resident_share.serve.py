"""What the rows hold of the window layers' pool beside what ONE table a
row would hold for them (%): the blocks of the window layers' pool in
use, summed over the window's decode passes (the engine's
``window_blocks_resident_sum``), over the blocks of the full layers'
pool in use at the same passes (``window_blocks_one_table_sum``: a row's
whole context, which is what the window layers would keep too under one
table).  Lower is better: 100 % is a cache that gives nothing back
behind the window."""


def read(obs):
    c = obs.get("counters") or {}
    if not c.get("window_blocks_one_table_sum") \
            or "window_blocks_resident_sum" not in c:
        return None
    return 100.0 * c["window_blocks_resident_sum"] \
        / c["window_blocks_one_table_sum"]
