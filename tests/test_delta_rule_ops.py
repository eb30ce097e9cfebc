"""The gated delta rule (ops/delta_rule.py) at small sizes on the CPU:
the window kernel and the one-token kernel (both in interpret mode)
against the token-by-token recurrence, ragged ``n_valid``, a row that
sits a pass out left bit-identical, head counts that are no multiple of
anything, and windows split at and off the block's boundaries."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import delta_rule as dr

# float32 products: the sums of 64-token blocks differ from the
# recurrence's by their order alone
ATOL = 5e-6


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(b, s, H, K, V, seed=0):
    """q, k as the mixer hands them (k unit length, q unit / sqrt(K)),
    a decay in (0, 1], beta in (0, 2), a non-zero incoming state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, s, H, K))
    k = jax.random.normal(ks[1], (b, s, H, K))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(K)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, H, V))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (b, s, H)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, H)))
    state = jax.random.normal(ks[5], (b, H, K, V))
    return (q, k, v, g, beta), state


def _stored(state):
    """[b, H, K, V] -> the pool's [b, K, H * V]."""
    b, H, K, V = state.shape
    return state.transpose(0, 2, 1, 3).reshape(b, K, H * V)


@pytest.mark.parametrize("s, H, K, V", [
    (150, 3, 8, 16),        # two whole blocks and a partial one
    (64, 5, 16, 8),         # exactly one block, V < K
    (7, 30, 8, 16),         # shorter than a block; 30 heads
    (129, 1, 24, 40),       # one head; one token into the third block
    (192, 2, 96, 192),      # the published head: two heads are 3 lane tiles
])
def test_window_form_equals_the_recurrence(s, H, K, V):
    xs, state = _inputs(2, s, H, K, V)
    want_o, want_s = dr.delta_recurrence(*xs, state)
    o, got = dr.delta_window(*xs, state, jnp.full((2,), s))
    np.testing.assert_allclose(o, want_o, atol=ATOL)
    np.testing.assert_allclose(got, want_s, atol=ATOL)


@pytest.mark.parametrize("s, n_valid", [
    (100, [100, 37, 0]),
    (150, [131, 150, 0]),   # no multiple of 64; a row ends inside the last
    (64, [0, 64, 1]),       # block; the row that sits out comes first
])
def test_window_form_ragged_rows_and_a_row_that_sits_out(s, n_valid):
    xs, state = _inputs(3, s, 3, 8, 16, seed=1)
    o, got = dr.delta_window(*xs, state, jnp.array(n_valid))
    for row, n in enumerate(n_valid):
        want_o, want_s = dr.delta_recurrence(
            *(x[row:row + 1, :n] for x in xs), state[row:row + 1])
        np.testing.assert_allclose(o[row, :n], want_o[0], atol=ATOL)
        np.testing.assert_allclose(got[row], want_s[0], atol=ATOL)
    # the padding is the identity, not nearly so
    out = n_valid.index(0)
    assert bool((got[out] == state[out]).all())


@pytest.mark.parametrize("cut", [64, 50, 1, 127])
def test_a_window_split_in_two_equals_one(cut):
    xs, state = _inputs(1, 128, 3, 8, 16, seed=2)
    n = jnp.array([128])
    whole_o, whole_s = dr.delta_window(*xs, state, n)
    o1, mid = dr.delta_window(*(x[:, :cut] for x in xs), state,
                              jnp.array([cut]))
    o2, end = dr.delta_window(*(x[:, cut:] for x in xs), mid,
                              jnp.array([128 - cut]))
    np.testing.assert_allclose(jnp.concatenate([o1, o2], axis=1), whole_o,
                               atol=ATOL)
    np.testing.assert_allclose(end, whole_s, atol=ATOL)


def test_unit_lower_solve_is_the_inverse_where_the_series_overflows():
    """64 keys of 8 lanes at beta 2: the powers of the strict part reach
    ~1e15 before they vanish; substitution in the diagonal blocks and
    the blockwise merges (the kernel's solve, here on its own) never
    form them."""
    k = jax.random.normal(jax.random.PRNGKey(3), (64, 8))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    n = jnp.tril(2.0 * k @ k.T, -1)
    row, col = jnp.indices((1, 64, 64), dtype=jnp.int32)[1:]
    inv = dr._unit_lower_solve(n[None], row, col)[0]
    np.testing.assert_allclose(inv @ (jnp.eye(64) + n), jnp.eye(64),
                               atol=1e-4)
    assert bool((jnp.triu(inv, 1) == 0).all())


@pytest.mark.parametrize("live, H, K, V", [
    ([1, 0, 1, 1], 3, 8, 16),
    ([0, 1, 0, 0], 30, 8, 16),      # 30 heads: two bf16 sublane tiles
    ([1, 1], 1, 16, 128),           # one head of one whole lane tile
    ([1, 0, 0, 1, 1, 0], 5, 8, 256),    # H * V = 1280: tiles of 1280
    ([1, 1, 1], 15, 8, 256),        # H * V = 3840: two tiles of 1920
])
def test_one_token_kernel_equals_the_recurrence(live, H, K, V):
    b = len(live)
    xs, state = _inputs(b, 1, H, K, V, seed=4)
    # the pool holds two more layers, which the kernel must not touch
    other = jax.random.normal(jax.random.PRNGKey(5), (b, K, H * V))
    pool = jnp.stack([other, _stored(state), other + 1.0])
    o, got = jax.jit(dr.delta_step)(*xs, pool, jnp.int32(1),
                                    jnp.array(live, jnp.int32))
    want_o, want_s = dr.delta_recurrence(*xs, state)
    assert bool((got[0] == pool[0]).all() and (got[2] == pool[2]).all())
    for row, on in enumerate(live):
        if on:
            np.testing.assert_allclose(o[row], want_o[row], atol=ATOL)
            np.testing.assert_allclose(got[1, row], _stored(want_s)[row],
                                       atol=ATOL)
        else:       # n_valid 0: neither read nor written, o = 0
            assert bool((got[1, row] == pool[1, row]).all())
            assert float(jnp.abs(o[row]).max()) == 0.0


def test_one_token_kernel_with_no_row_live_returns_the_pool():
    xs, state = _inputs(3, 1, 3, 8, 16, seed=6)
    pool = jnp.stack([_stored(state)] * 2)
    o, got = dr.delta_step(*xs, pool, 0, jnp.zeros((3,), jnp.int32))
    assert bool((got == pool).all()) and float(jnp.abs(o).max()) == 0.0


def test_steps_after_a_window_equal_one_longer_window():
    """The two forms hand the state to each other through the pool's
    stored layout (``delta_rule`` dispatches on the window's width)."""
    xs, state = _inputs(2, 70, 3, 8, 16, seed=7)
    pool = jnp.zeros((1, 2, 8, 48))
    o, pool = dr.delta_rule(*(x[:, :67] for x in xs), pool, 0,
                            jnp.array([67, 67]))
    outs = [o]
    for t in range(67, 70):
        o, pool = dr.delta_rule(*(x[:, t:t + 1] for x in xs), pool, 0,
                                jnp.array([1, 1]))
        outs.append(o)
    want_o, want_s = dr.delta_recurrence(*xs, jnp.zeros_like(state))
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want_o,
                               atol=ATOL)
    np.testing.assert_allclose(pool[0], _stored(want_s), atol=ATOL)


def test_split3_parts_sum_to_the_number():
    x = jax.random.normal(jax.random.PRNGKey(8), (64, 32)) * 3.0
    parts = dr._split3(x)
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    total = sum(p.astype(jnp.float32) for p in parts)
    assert float(jnp.abs(total - x).max()) <= 2.0 ** -22 * 3.0 * 4
