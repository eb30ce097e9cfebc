"""The seam between the GPT's ONE layer function and its attention step.

``gpt._transformer_layer`` is handed ``attend(q, k, v)``: training and
the full-width prefill hand it the causal attention over the window's
own keys (``gpt.causal_attend``), the four paged programs the paged one
(``decode.paged_attend``: commit the window's K/V to the pool, read the
rows' tables back, attend what the mask or the lengths admit).  Here
the paged ``attend`` is held to the causal one directly, and each paged
program to ``gpt.forward`` at its window's positions.  Float32 at
GPTConfig.tiny, on the CPU."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference.cache import BlockPool, PoolLayout
from ray_tpu.inference.decode import (make_chunk_prefill_fn,
                                      make_paged_decode_step,
                                      make_paged_draft_step,
                                      make_spec_verify_step, pack_chunk,
                                      pack_step, paged_attend)
from ray_tpu.models import gpt
from ray_tpu.parallel.sharding import DEFAULT_LLM_RULES

BS, T = 8, 4                     # 4 blocks of 8: a cache 32 wide
CFG = gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=BS * T)
LAYER = 1                        # not the first: the layer offset counts

# window -> (first position of each row, live lanes of each row)
WINDOWS = {
    "decode[3,1]": ([5, 8, 0], [1, 1, 1]),
    "chunk[1,16]": ([4], [16]),              # blocks 0, 1 and 2
    "verify[2,5]": ([9, 14], [5, 4]),        # row 1's last lane is dead
}


def _causal(q, k, v):
    """[n, h, hd] each -> what ``gpt._attend`` gives over the whole
    sequence, [n, h, hd]."""
    o = gpt._attend(*(t.transpose(1, 0, 2)[None] for t in (q, k, v)),
                    CFG, None, DEFAULT_LLM_RULES)
    return o[0].transpose(1, 0, 2)


@pytest.mark.parametrize("window", WINDOWS)
def test_paged_attend_is_the_causal_attend_at_the_windows_positions(window):
    starts, live = WINDOWS[window]
    b, w = len(starts), max(live)
    h, hd = CFG.n_heads, CFG.head_dim
    S = BS * T
    pool = BlockPool(CFG, b * T, BS)
    lay = PoolLayout.of(CFG, pool.k)
    tables = jnp.arange(1, b * T + 1, dtype=jnp.int32).reshape(b, T)
    q, k, v = (jax.random.normal(key, (b, S, h, hd), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(3), 3))

    # the past of every row goes into the pools through ``commit``
    pools = (pool.k, pool.v)
    for r, start in enumerate(starts):
        at = jnp.arange(start)
        pools = tuple(lay.commit(p, LAYER, tables[r, at // BS], at % BS,
                                 x[r, :start]) for p, x in zip(pools, (k, v)))

    # the window: lane j of row r sits at starts[r] + j; dead lanes go to
    # the scratch block and attend key 0 only (the programs' own rule)
    pos = np.asarray(starts)[:, None] + np.arange(w)[None, :]
    alive = np.arange(w)[None, :] < np.asarray(live)[:, None]
    safe = np.where(alive, pos, 0)
    rows = np.arange(b)[:, None]
    blocks = jnp.asarray(np.where(alive, np.asarray(tables)[rows, safe // BS],
                                  0), jnp.int32)
    offsets = jnp.asarray(np.where(alive, pos % BS, 0), jnp.int32)
    win = jnp.asarray(safe)
    qw, kw, vw = (jnp.take_along_axis(t, win[:, :, None, None], axis=1)
                  for t in (q, k, v))                       # [b, w, h, hd]
    if window.startswith("decode"):
        mask = {"kv_lengths": jnp.asarray(starts, jnp.int32) + 1}
        blocks, offsets = blocks[:, 0], offsets[:, 0]
    else:
        mask = {"mask": jnp.asarray(
            np.arange(S)[None, None, :] <= safe[:, :, None])[:, None]}
        if window.startswith("chunk"):       # one row: indices [C]
            blocks, offsets = blocks[0], offsets[0]
    attend_for, held = paged_attend(lay, pools, blocks, offsets, tables,
                                    **mask)
    o = attend_for(LAYER)(qw.transpose(0, 2, 1, 3), kw, vw)
    assert o.shape == (b, h, w, hd)

    for r, (start, n) in enumerate(zip(starts, live)):
        end = start + n
        want = _causal(q[r, :end], k[r, :end], v[r, :end])[start:]
        np.testing.assert_allclose(o[r, :, :n].transpose(1, 0, 2), want,
                                   rtol=2e-5, atol=2e-5)
        # and the closure left the window's K/V where the table says
        for p, x in zip(held["pools"], (k, v)):
            got = lay.unpack(lay.read(p, LAYER, tables[r]))[:end]
            np.testing.assert_array_equal(got, x[r, :end])
    # no other layer's rows were written
    for p in held["pools"]:
        other = lay.unpack(lay.read(p, 0, tables))
        assert not np.asarray(other).any()


# ---------------------------------------------------------------------------
# the programs against gpt.forward

ROWS, CHUNK, WIDTH = 2, 16, 3


@functools.lru_cache(maxsize=None)
def _programs_and_forward():
    """Every paged program once, on one pool: two rows prefilled by a
    chunk each, then a decode step, a verify window and a draft burst
    at position CHUNK; and the full forward over the same tokens."""
    params = gpt.init_params(CFG, jax.random.PRNGKey(7))
    kw = dict(block_size=BS, n_table=T)
    pool = BlockPool(CFG, ROWS * T, BS)
    k, v = pool.k, pool.v
    tables = jnp.arange(1, ROWS * T + 1, dtype=jnp.int32).reshape(ROWS, T)
    toks = jax.random.randint(jax.random.PRNGKey(1), (ROWS, BS * T), 0,
                              CFG.vocab_size, jnp.int32)
    got = {}
    chunk = make_chunk_prefill_fn(CFG, chunk=CHUNK, **kw)
    rows = []
    feed = jnp.zeros(ROWS, jnp.int32)       # the rows' next tokens
    for r in range(ROWS):
        logits, first, k, v, feed = chunk(params, k, v, feed, pack_chunk(
            tables[r], toks[r, :CHUNK], 0, r, CHUNK))
        # the program's own greedy token: its last real position's, and
        # what the row feeds its first decode step
        assert first.dtype == jnp.int32 \
            and first.tolist() == [int(logits[CHUNK - 1].argmax())] \
            == [int(feed[r])]
        rows.append(logits)
    got["chunk"] = jnp.stack(rows)
    at = jnp.full((ROWS,), CHUNK, jnp.int32)
    live = jnp.ones((ROWS,), bool)
    want_all = jnp.full((ROWS,), WIDTH, jnp.int32)
    got["decode"], greedy, k, v, feed = make_paged_decode_step(
        CFG, **kw)(
        params, k, v, feed, pack_step(tables, toks[:, CHUNK], at, live))
    assert greedy.dtype == jnp.int32 and greedy.tolist() \
        == got["decode"].argmax(-1).tolist() == feed.tolist()
    got["verify"], k, v = make_spec_verify_step(CFG, width=WIDTH, **kw)(
        params, k, v, tables, toks[:, CHUNK:CHUNK + WIDTH], at, live,
        want_all)
    got["draft"], k, v = make_paged_draft_step(
        CFG, draft_layers=1, k=WIDTH, **kw)(
        params, k, v, tables, toks[:, CHUNK], at, want_all)

    full = gpt.forward(params, toks, CFG)
    want = {"chunk": full[:, :CHUNK], "decode": full[:, CHUNK],
            "verify": full[:, CHUNK:CHUNK + WIDTH]}
    # the drafter is the model cut to its first layer, decoding greedily
    cut = dataclasses.replace(CFG, n_layers=1)
    trunk = {**params, "layers": jax.tree.map(lambda a: a[:1],
                                              params["layers"])}
    seq = toks[:, :CHUNK + 1]
    for _ in range(WIDTH):
        nxt = jnp.argmax(gpt.forward(trunk, seq, cut)[:, -1], axis=-1)
        seq = jnp.concatenate([seq, nxt[:, None].astype(jnp.int32)], axis=1)
    want["draft"] = seq[:, CHUNK + 1:]
    return jax.tree.map(np.asarray, (got, want))


@pytest.mark.parametrize("program", ["decode", "chunk", "verify", "draft"])
def test_paged_programs_match_forward(program):
    got, want = _programs_and_forward()
    assert got[program].shape == want[program].shape
    if program == "draft":
        np.testing.assert_array_equal(got[program], want[program])
        return
    np.testing.assert_allclose(got[program], want[program],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(got[program].argmax(-1),
                                  want[program].argmax(-1))
