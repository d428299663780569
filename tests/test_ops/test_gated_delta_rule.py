"""The gated delta rule's chunked form against its definition, the recurrence
token by token, on the CPU in float32 at small shapes: 2 key heads of 16
serving 4 value heads of 8, forward and gradient of every input, at lengths
that are and are not multiples of the chunk, with no write (``beta`` 0), with
decay strong enough to underflow a chunk's cumulative sum, and with a carried
``initial_state``.

Tolerances: both sides sum the same terms in float32 at ``highest`` precision
in different orders; outputs of order 1 agree to about 1e-6 and gradients, sums
of a few hundred such terms, to 1e-5 of their largest entry (under the strong
decay a chunk's cumulative log-decay reaches -2,000: the op sums each decay's
segment as it stands, where the difference of two such sums read 2e-5).  A term
left out (the decay, the delta's read of the state, the write) moves them by
tenths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.ops import gated_delta_rule as op

ATOL, GRAD_RTOL = 5e-6, 1e-5


def token_by_token(q, k, v, g, beta, state=None):
    """``S_t = e^g S + beta k (v - e^g S^T k)^T``, ``o_t = S_t^T q_t``, one token at a time; value head ``h``
    reads key head ``h // (Hv / Hk)``."""
    b, n, hk, dk = q.shape
    hv, dv = v.shape[2:]
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
    state = jnp.zeros((b, hv, dk, dv)) if state is None else state

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhk,bhkv->bhv", k_t, s)
        s = s + b_t[..., None, None] * k_t[..., :, None] * (v_t - read)[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s)

    with jax.default_matmul_precision("highest"):
        state, o = jax.lax.scan(step, state, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def inputs(seed, n, decay=0.1, write=True, b=2, hk=2, hv=4, dk=16, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, n, hk, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, n, hk, dk)))
    v = jax.random.normal(ks[2], (b, n, hv, dv))
    g = -decay * jnp.exp(jax.random.normal(ks[3], (b, n, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, n, hv))) if write else jnp.zeros((b, n, hv))
    state = 0.3 * jax.random.normal(ks[5], (b, hv, dk, dv))
    return q, k, v, g, beta, state


CASES = {
    "one chunk and a part": dict(n=150),
    "whole chunks": dict(n=128),
    "shorter than a chunk": dict(n=5),
    "one token": dict(n=1),
    "no write": dict(n=150, write=False),
    "strong decay": dict(n=150, decay=30.0),
}


@pytest.mark.parametrize("carried", [False, True])
@pytest.mark.parametrize("case", sorted(CASES))
def test_matches_the_recurrence(case, carried):
    q, k, v, g, beta, state = inputs(0, **CASES[case])
    state = state if carried else None
    o, final = op.gated_delta_rule(q, k, v, g, beta, state)
    want_o, want_final = token_by_token(q, k, v, g, beta, state)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=ATOL)
    np.testing.assert_allclose(np.asarray(final), np.asarray(want_final), atol=ATOL)
    if case == "no write":  # the state only decays, and every output reads the decayed start
        assert not carried or float(jnp.abs(final).max()) > 0
        decayed = (state if carried else 0.0) * jnp.exp(g.sum(1))[..., None, None]
        np.testing.assert_allclose(np.asarray(final), np.asarray(decayed + jnp.zeros_like(final)), atol=ATOL)


@pytest.mark.parametrize("case", ["one chunk and a part", "strong decay", "one token"])
def test_gradients_match_the_recurrence(case):
    """The gradient of every input and of the initial state, through a loss on the outputs and on the
    final state."""
    args = inputs(1, **CASES[case])
    weights = jnp.cos(jnp.arange(args[2].size, dtype=jnp.float32)).reshape(args[2].shape)

    def loss(f):
        return lambda *a: (lambda o, s: (o * weights).sum() + (s * s).sum())(*f(*a))

    got = jax.grad(loss(op.gated_delta_rule), argnums=range(6))(*args)
    want = jax.grad(loss(token_by_token), argnums=range(6))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta", "state"), got, want):
        scale = float(jnp.abs(b).max())
        assert scale > 0, name
        assert float(jnp.abs(a - b).max()) <= GRAD_RTOL * scale + 1e-7, name


def test_a_sequence_split_in_two_carries_its_state():
    """The chunked form over the first 70 positions, then over the rest from its final state, gives the
    whole sequence's outputs and state: what a prefill hands to the cached passes."""
    q, k, v, g, beta, _ = inputs(2, n=150)
    whole, final = op.gated_delta_rule(q, k, v, g, beta)
    first, state = op.gated_delta_rule(*(x[:, :70] for x in (q, k, v, g, beta)))
    rest, state = op.gated_delta_rule(*(x[:, 70:] for x in (q, k, v, g, beta)), state)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([first, rest], 1)), np.asarray(whole), atol=ATOL)
    np.testing.assert_allclose(np.asarray(state), np.asarray(final), atol=ATOL)


def test_each_shape_is_recorded_once_by_its_form():
    op.take_engaged()
    op._SEEN.clear()
    for n in (150, 150, 1):
        op.gated_delta_rule(*inputs(3, n=n)[:5])
    got = op.take_engaged()
    assert [(r["form"], r["chunk"], r["tokens"]) for r in got] == [("chunked", op.CHUNK, 150), ("recurrent", 1, 1)]
    assert {(r["key_heads"], r["value_heads"], r["d_k"], r["d_v"], r["dtype"]) for r in got} == {(2, 4, 16, 8, "float32")}
    assert op.take_engaged() == []


def test_shapes_that_do_not_agree_are_refused():
    q, k, v, g, beta, _ = inputs(4, n=8)
    with pytest.raises(ValueError, match="value heads cannot share"):
        op.gated_delta_rule(q, k, v[:, :, :3], g[:, :, :3], beta[:, :, :3])
    with pytest.raises(ValueError, match="do not agree"):
        op.gated_delta_rule(q, k[:, :4], v, g, beta)
