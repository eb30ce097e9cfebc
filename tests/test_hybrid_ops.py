"""The ops of the hybrid Mamba-2 / attention MoE family against their
definitions, at tiny widths on the CPU: the state-space window scan and
its one-step forms (ops/ssm.py), the grouped matmul and the routing of
the expert layer (ops/routed_experts.py), packed attention with grouped
queries.  The ops agree with each other and with the definition; the
model they make is held to its references in tests/test_hybrid_model.py
and served through the engine in tests/test_hybrid_engine.py (one file
until PR 43: under `--dist loadfile` it held one worker for more than
half of a tier-1 run).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import routed_experts as rx
from ray_tpu.ops import ssm
from ray_tpu.ops.attention import mha_reference, packed_attention

def _ssm_inputs(b, s, seed=0, groups=1):
    """``groups`` B/C groups: head h reads group h // (H / groups)."""
    H, P, N = 4, 8, 16
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(k[0], (b, s, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (b, s, H)) - 2),
        A=-jnp.exp(jax.random.normal(k[2], (H,))),
        B=jax.random.normal(k[3], (b, s, groups, N)),
        C=jax.random.normal(k[4], (b, s, groups, N)), D=jnp.ones((H,)),
        state=jax.random.normal(k[5], (b, H, P, N)))


GROUPS = pytest.mark.parametrize("groups", [1, 2, 4])


# ---------------------------------------------------------------- ops/ssm

@GROUPS
@pytest.mark.parametrize("s", [8, 13, 37])      # one chunk / partial / 4+5
def test_window_scan_equals_recurrence(s, groups):
    a = _ssm_inputs(2, s, groups=groups)
    y0, s0 = ssm.ssd_recurrence(**a)
    y1, s1 = ssm.ssd_window(**a, n_valid=jnp.full((2,), s), chunk=8)
    np.testing.assert_allclose(y1, y0, atol=2e-5)
    np.testing.assert_allclose(s1, s0, atol=2e-5)


def _pool_of(state, layers=1, layer=0):
    """[b, H, P, N] state as ``layer`` of a pool [layers, b, H * P, N]
    whose other layers hold other numbers."""
    b, H, P, N = state.shape
    pool = jax.random.normal(jax.random.PRNGKey(7), (layers, b, H * P, N))
    return pool.at[layer].set(state.reshape(b, H * P, N))


@GROUPS
def test_one_step_form_equals_recurrence(groups):
    a = _ssm_inputs(2, 11, groups=groups)
    y0, s0 = ssm.ssd_recurrence(**a)
    pool, ys = _pool_of(a["state"]), []
    step = jax.jit(ssm.ssd, static_argnames="chunk")
    for t in range(11):
        y, pool = step(a["x"][:, t:t + 1], a["dt"][:, t:t + 1], a["A"],
                       a["B"][:, t:t + 1], a["C"][:, t:t + 1], a["D"],
                       pool, 0, jnp.ones((2,), jnp.int32), chunk=8)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y0, atol=2e-5)
    np.testing.assert_allclose(pool[0].reshape(s0.shape), s0, atol=2e-5)


def test_tokens_past_n_valid_leave_the_state_alone():
    a = _ssm_inputs(2, 21)
    n_valid = jnp.array([0, 13])
    y, state = ssm.ssd_window(**a, n_valid=n_valid, chunk=8)
    np.testing.assert_array_equal(state[0], a["state"][0])
    cut = {k: (v[1:, :13] if k in ("x", "dt", "B", "C") else v)
           for k, v in a.items()}
    cut["state"] = a["state"][1:]
    y13, s13 = ssm.ssd_recurrence(**cut)
    np.testing.assert_allclose(y[1, :13], y13[0], atol=2e-5)
    np.testing.assert_allclose(state[1], s13[0], atol=2e-5)
    # the one-token form: a row that sits the pass out
    pool = _pool_of(a["state"])
    _, st = ssm.ssd_step(a["x"][:, :1], a["dt"][:, :1], a["A"],
                         a["B"][:, :1], a["C"][:, :1], a["D"], pool, 0,
                         jnp.array([0, 1]))
    np.testing.assert_array_equal(st[0, 0], pool[0, 0])
    assert not np.allclose(st[0, 1], pool[0, 1])


@pytest.mark.parametrize("live", [
    (1, 1, 1, 1, 1), (0, 1, 1, 1, 1), (1, 1, 0, 1, 1), (1, 1, 1, 1, 0),
    (0, 0, 0, 1, 0), (0, 0, 0, 0, 0)],
    ids=["all", "first-idle", "middle-idle", "last-idle", "one", "none"])
@GROUPS
def test_one_step_kernel_touches_live_rows_of_its_layer_only(live, groups):
    """The one-token kernel on a pool of three layers: the live rows of
    ITS layer advance as the definition says; idle rows and the other
    layers come back bit for bit — also from a pass in which no row
    advances at all."""
    a = _ssm_inputs(5, 1, seed=2, groups=groups)
    state = a.pop("state")
    pool = _pool_of(state, layers=3, layer=1)
    n_valid = jnp.array(live, jnp.int32)
    y, new = jax.jit(ssm.ssd_step)(**a, pool=pool, layer=jnp.int32(1),
                                   n_valid=n_valid)
    y0, s0 = ssm.ssd_recurrence(**a, state=state)
    on = np.array(live, bool)
    np.testing.assert_allclose(y[on], y0[on], atol=2e-5)
    np.testing.assert_allclose(new[1][on], s0.reshape(pool.shape[1:])[on],
                               atol=2e-5)
    np.testing.assert_array_equal(new[1][~on], pool[1][~on])
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[2], pool[2])


def test_causal_conv_window_equals_token_by_token():
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    b, s, C, K = 2, 9, 6, 4
    x = jax.random.normal(k[0], (b, s, C))
    w, bias = jax.random.normal(k[1], (K, C)), jax.random.normal(k[2], (C,))
    st0 = jax.random.normal(k[3], (b, K - 1, C))
    n_valid = jnp.array([9, 4])
    y, st = ssm.causal_conv(x, st0, w, bias, n_valid)
    state, ys = st0, []
    for t in range(s):
        yt, new = ssm.causal_conv(x[:, t:t + 1], state, w, bias,
                                  (t < n_valid).astype(jnp.int32))
        state = new
        ys.append(yt)
    np.testing.assert_allclose(jnp.concatenate(ys, 1)[0], y[0], atol=1e-6)
    np.testing.assert_allclose(jnp.concatenate(ys, 1)[1, :4], y[1, :4],
                               atol=1e-6)
    np.testing.assert_allclose(state, st, atol=1e-6)
    # the state after 4 real tokens is the last 3 of them
    np.testing.assert_allclose(st[1], x[1, 1:4], atol=1e-6)


# ------------------------------------------------------ ops/routed_experts

def _expert_weights(seed=5, E=8, d=64, f=32):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (d, E)) * 0.1,
            jax.random.normal(k[1], (E, d, 2 * f)) * 0.1,
            jax.random.normal(k[2], (E, f, d)) * 0.1,
            jax.random.normal(k[3], (40, d)))


@pytest.mark.parametrize("m, k, n", [
    (40, 256, 256),     # k and n in 256s: two n tiles
    (87, 64, 128),      # whole-k tiles, n one lane tile
    (300, 384, 640)],   # n an odd number of lane tiles, rows padded
    ids=["even-tiles", "one-tile", "odd-tiles"])
def test_grouped_matmul_equals_group_by_group(m, k, n):
    """``grouped_matmul``: every group's rows times its own matrix, an
    empty group among them; rows past the last group are nobody's."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x, w = jax.random.normal(ks[0], (m, k)), jax.random.normal(ks[1],
                                                               (5, k, n))
    sizes = np.array([m // 3, 0, m // 4, 1, m // 5], np.int32)
    got = np.asarray(rx.grouped_matmul(x, w, jnp.asarray(sizes)))
    at = 0
    for i, size in enumerate(sizes):
        np.testing.assert_allclose(
            got[at:at + size], np.asarray(x[at:at + size] @ w[i]),
            atol=1e-4)
        at += size
    assert got.shape == (m, n)


def test_every_token_to_one_expert_loses_none():
    w_r, w_in, w_out, h = _expert_weights()
    # the router sends everything to expert 5: dropless means all 40
    # tokens are computed, none capped
    w_r = jnp.zeros_like(w_r).at[:, 5].set(jnp.sign(h.sum(0)))
    h = jnp.abs(h) * jnp.sign(h.sum(0))
    out, counts, total = rx.routed_experts(h, w_r, w_in, w_out, top_k=1,
                                           held=(0, 8))
    assert counts.tolist() == [0, 0, 0, 0, 0, 40, 0, 0] and int(total) == 40
    np.testing.assert_allclose(out, rx.mlp(h, w_in[5], w_out[5]),
                               atol=1e-5)


def test_bias_chooses_and_unbiased_scores_weigh():
    """Selection by ``score + bias``, weights from the scores WITHOUT
    it: a seeded non-zero bias tells the three apart — choosing by the
    score alone picks other experts, and weighing by the biased score
    gives other weights."""
    w_r, _, _, h = _expert_weights()
    bias = jax.random.normal(jax.random.PRNGKey(11), (8,)) * 0.3
    experts, gates = rx.route(h, w_r, 3, bias, 2.5)
    scores = np.asarray(jax.nn.sigmoid(h @ w_r))
    want = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(want, -1))
    assert (np.sort(want, -1)
            != np.sort(np.argsort(-scores, -1)[:, :3], -1)).any()
    chosen = np.take_along_axis(scores, np.asarray(experts), -1)
    np.testing.assert_allclose(gates, chosen / chosen.sum(-1, keepdims=True)
                               * 2.5, rtol=1e-6)
    biased = chosen + np.asarray(bias)[np.asarray(experts)]
    assert np.abs(np.asarray(gates) - biased
                  / biased.sum(-1, keepdims=True) * 2.5).max() > 1e-2
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-6)


def test_padding_is_not_counted():
    w_r, w_in, w_out, h = _expert_weights()
    valid = jnp.arange(40) < 25
    out, counts, total = rx.routed_experts(h, w_r, w_in[2:6], w_out[2:6],
                                           top_k=3, held=(2, 6),
                                           valid=valid)
    experts, _ = rx.route(h[:25], w_r, 3)
    want = [(np.asarray(experts) == e).sum() for e in range(2, 6)]
    assert counts.tolist() == want and int(total) == 75
    assert out.shape == h.shape


@pytest.mark.parametrize("sets", [1, 2])
def test_padding_joins_no_experts_run(sets, monkeypatch):
    """A decode pass's empty rows and a last chunk's tail choose experts
    like any token; none of them may be in a run (an expert that only
    padding chose is not streamed), their routed part is nothing, and
    the real tokens' is what it is without them."""
    w_r, w_in, w_out, h = _expert_weights()
    valid = jnp.arange(40) < 25
    if sets == 2:       # a window in two parts: real if real in either
        valid = jnp.stack([jnp.arange(40) < 10,
                           (jnp.arange(40) >= 10) & (jnp.arange(40) < 25)])
    seen = []
    grouped = rx.grouped_matmul
    monkeypatch.setattr(rx, "grouped_matmul", lambda x, w, sizes: (
        seen.append(np.asarray(sizes)), grouped(x, w, sizes))[1])
    out, counts, _ = rx.routed_experts(h, w_r, w_in[2:6], w_out[2:6],
                                       top_k=3, held=(2, 6), valid=valid)
    alone, _, _ = rx.routed_experts(h[:25], w_r, w_in[2:6], w_out[2:6],
                                    top_k=3, held=(2, 6))
    np.testing.assert_allclose(out[:25], alone, atol=1e-6)
    assert not np.asarray(out[25:]).any()
    assert seen[0].tolist() == np.asarray(counts).reshape(
        sets, 4).sum(0).tolist()
    experts, _ = rx.route(h[25:], w_r, 3)
    assert ((np.asarray(experts) >= 2) & (np.asarray(experts) < 6)).any()


# ------------------------------------------------------------ attention

def test_packed_attention_grouped_queries():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    b, h, kv, nq, S, hd = 2, 8, 2, 3, 20, 16
    q = jax.random.normal(k[0], (b, h, nq, hd))
    K = jax.random.normal(k[1], (b, S, kv * hd))
    V = jax.random.normal(k[2], (b, S, kv * hd))
    lens = jnp.array([7, 20])
    got = packed_attention(q, K, V, q_per_kv=h // kv, scale=0.1,
                           kv_lengths=lens)

    def heads(t):
        return jnp.repeat(t.reshape(b, S, kv, hd).transpose(0, 2, 1, 3),
                          h // kv, 1)
    want = mha_reference(q, heads(K), heads(V), causal=False, scale=0.1,
                         kv_lengths=lens)
    np.testing.assert_allclose(got, want, atol=1e-5)
