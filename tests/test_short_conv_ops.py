"""ops/short_conv.py: the window form, the one-token step and the state
after any token of a window, each held to the recurrence run token by
token from zero state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import short_conv

D = 24


def _inputs(s, L, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (s, D), jnp.float32),
            jax.random.uniform(k2, (L, D), minval=-0.6, maxval=0.6))


@pytest.mark.parametrize("L", [2, 3, 4])
def test_window_from_zero_state_is_the_loop(L):
    v, w = _inputs(19, L)
    c, full = short_conv.conv_window(v[None], jnp.zeros((1, L - 1, D)), w)
    np.testing.assert_allclose(c[0], short_conv.conv_loop(v, w), atol=1e-6)
    assert full.shape == (1, L - 1 + 19, D)


@pytest.mark.parametrize("L,cut", [(3, 1), (3, 7), (3, 18), (4, 2), (2, 5)])
def test_window_in_two_windows_carries_its_state(L, cut):
    """A window cut anywhere: the second part from ``state_at`` of the
    first is the whole window's; with fewer tokens than taps the state
    still holds what the window before it left."""
    v, w = _inputs(19, L, seed=1)
    zero = jnp.zeros((1, L - 1, D))
    whole, _ = short_conv.conv_window(v[None], zero, w)
    first, full = short_conv.conv_window(v[None, :cut], zero, w)
    state = short_conv.state_at(full, jnp.array([cut]), L - 1)
    second, _ = short_conv.conv_window(v[None, cut:], state, w)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=1e-6)


def test_state_at_every_count_is_the_last_inputs():
    L = 3
    v, w = _inputs(11, L, seed=2)
    before = jnp.arange(2 * D, dtype=jnp.float32).reshape(1, 2, D)
    _, full = short_conv.conv_window(v[None], before, w)
    np.testing.assert_array_equal(
        short_conv.state_at(full, jnp.array([0]), 2), before)
    for n in (1, 2, 5, 11):
        want = jnp.concatenate([before[0], v])[n:n + 2]
        np.testing.assert_array_equal(
            short_conv.state_at(full, jnp.array([n]), 2)[0], want)


@pytest.mark.parametrize("L", [2, 3, 4])
def test_steps_are_the_loop_and_idle_rows_keep_their_state(L):
    """Three rows stepped together, the middle one sitting every second
    step out: each row's outputs are the loop's over the tokens it
    took."""
    w = _inputs(1, L)[1]
    vs = [_inputs(12, L, seed=s)[0] for s in (3, 4, 5)]
    state = jnp.zeros((3, L - 1, D))
    took, outs = [0, 0, 0], [[], [], []]
    for t in range(12):
        active = np.array([1, t % 2, 1])
        v = jnp.stack([vs[r][took[r]] for r in range(3)])
        c, new = short_conv.conv_step(v, state, w, jnp.asarray(active))
        for r in range(3):
            if active[r]:
                outs[r].append(c[r])
                took[r] += 1
            else:
                np.testing.assert_array_equal(new[r], state[r])
        state = new
    for r in range(3):
        np.testing.assert_allclose(
            jnp.stack(outs[r]), short_conv.conv_loop(vs[r][:took[r]], w),
            atol=1e-6)


def test_step_after_window_is_the_loop():
    L = 3
    v, w = _inputs(10, L, seed=6)
    _, full = short_conv.conv_window(v[None, :9], jnp.zeros((1, 2, D)), w)
    state = short_conv.state_at(full, jnp.array([9]), 2)
    c, state = short_conv.conv_step(v[9][None], state, w, jnp.array([1]))
    np.testing.assert_allclose(c[0], short_conv.conv_loop(v, w)[9],
                               atol=1e-6)
    np.testing.assert_array_equal(state[0], v[8:10])


def test_state_keeps_its_dtype():
    v, w = _inputs(6, 3, seed=7)
    state = jnp.zeros((1, 2, D), jnp.bfloat16)
    _, new = short_conv.conv_step(v[:1].astype(jnp.bfloat16), state, w,
                                  jnp.array([1]))
    assert new.dtype == jnp.bfloat16
