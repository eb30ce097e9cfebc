"""Operations and bytes the ``deepseek_v2`` layout's two attention forms
must do, from shapes and the window's counters (the roofline shares of
``layer_metrics/latent_decode_roofline.serve.py`` and
``latent_prefill_roofline.serve.py`` divide the least time they take at
``peaks.json``'s rates by the traced time).

Only what the ALGORITHM needs is counted, whatever implements it:

  * the one-token form, a decode pass: every block that holds a key of a
    live row (the engine's ``kv_blocks_attended`` counter) read ONCE a
    layer at the 576 cached lanes (the pool stores 640; the padding is
    read by the kernel and not credited), and for each of its keys and
    each head one product over 576 lanes (scores) and one over 512
    (values);
  * the window form, a chunk: every key in reach of the window (the
    engine's ``chunk_keys``: position + real tokens) decompressed ONCE
    (``c_kv W_kvb``, 512 -> heads x 256), and each (query, key) pair
    under the causal mask (``chunk_query_keys``; real queries only)
    attended in every head over 192 lanes (scores) and 128 (values).  A
    form that decompresses a key once a query block, or attends masked
    or padded pairs, does more and reads as a LOWER share.
"""

from __future__ import annotations

BF16 = 2


def _dims(published: dict) -> tuple:
    return (published["num_attention_heads"], published["kv_lora_rank"],
            published["qk_nope_head_dim"], published["qk_rope_head_dim"],
            published["v_head_dim"])


def decode_kernel_work(published: dict, layers: int, block_size: int,
                       blocks_per_pass: float) -> tuple:
    """-> (flops, bytes) of the one-token latent attention in ONE decode
    pass that attends ``blocks_per_pass`` blocks a layer."""
    h, rank, _dn, dr, _dv = _dims(published)
    keys = blocks_per_pass * block_size
    flops = 2.0 * layers * keys * h * ((rank + dr) + rank)
    return flops, float(layers * keys * (rank + dr) * BF16)


def window_work(published: dict, layers: int, keys_per_chunk: float,
                pairs_per_chunk: float) -> tuple:
    """-> (flops, bytes) of the window form in ONE chunk pass whose
    window reaches ``keys_per_chunk`` keys and attends
    ``pairs_per_chunk`` (query, key) pairs."""
    h, rank, dn, dr, dv = _dims(published)
    decompress = 2.0 * keys_per_chunk * rank * h * (dn + dv)
    attend = 2.0 * pairs_per_chunk * h * ((dn + dr) + dv)
    bytes_ = keys_per_chunk * (rank + dr) * BF16 + rank * h * (dn + dv) * BF16
    return layers * (decompress + attend), float(layers * bytes_)


def least_seconds(work: tuple, peaks: dict) -> float:
    flops, bytes_ = work
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])


def per_decode(obs: dict, counter: str):
    c = obs.get("counters") or {}
    if not c.get("decode_iterations") or counter not in c:
        return None
    return c[counter] / c["decode_iterations"]


def per_chunk(obs: dict, counter: str):
    c = obs.get("counters") or {}
    if not c.get("chunk_passes") or counter not in c:
        return None
    return c[counter] / c["chunk_passes"]
