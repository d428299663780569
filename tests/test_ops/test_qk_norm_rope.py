"""The fused per-head q/k norm and rotary (``ops/qk_norm_rope.py``) against its
plain form, ``rope(rms_norm(x, g, eps), pos, theta)`` (``rms_norm`` alone for a
layer without positional embedding): the forward and both gradients, ``x``'s and
the gain's, through ``jax.vjp``.  The fused form runs through Pallas'
interpreter; ``tests/test_ops/test_tpu_compile.py`` compiles it for a v5e."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models.sdar_moe import rms_norm, rope
from sheeprl_tpu.ops import qk_norm_rope as op

EPS, THETA, D = 1e-6, 1e6, 128


def _inputs(shape, positions, dtype, seed=0):
    b, n = shape[0], shape[1]
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = (3.0 * jax.random.normal(k[0], shape, jnp.float32)).astype(dtype)
    g = 1.0 + 0.1 * jax.random.normal(k[1], (shape[-1],), jnp.float32)
    pos = {"per_token": jax.random.randint(k[2], (b, n), 0, 6000), "shared": jnp.arange(n) * 7, "nope": None}[positions]
    dy = jax.random.normal(k[3], shape, jnp.float32).astype(dtype)
    return x, g, pos, dy


def _plain(x, g, pos):
    """The reference in float32 from the same inputs: what the op computes before its one store."""
    y = rms_norm(x.astype(jnp.float32), g, EPS)
    return y if pos is None else rope(y, pos, THETA)


def _vjp(fn, x, g, dy):
    y, back = jax.vjp(fn, x, g)
    return (y, *back(dy))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("dtype,out", [(jnp.float32, None), (jnp.bfloat16, None), (jnp.float32, jnp.bfloat16)],
                         ids=["f32", "bf16", "f32_in_bf16_out"])
@pytest.mark.parametrize("positions", ["per_token", "shared", "nope"])
@pytest.mark.parametrize("heads", [32, 4])
def test_fused_matches_the_plain_form(heads, positions, dtype, out):
    # 200 positions: a tile of 128 and a last one that runs past the sequence
    x, g, pos, dy = _inputs((2, 200, heads, D), positions, dtype)
    dy = dy.astype(out or dtype)
    y, dx, dg = _vjp(lambda x, g: op.qk_norm_rope(x, g, EPS, pos, THETA, dtype=out, interpret=True), x, g, dy)
    y_ref, dx_ref, dg_ref = _vjp(lambda x, g: _plain(x, g, pos), x.astype(jnp.float32), g, dy.astype(jnp.float32))
    assert y.dtype == (out or dtype) and dx.dtype == dtype and dg.dtype == jnp.float32
    if (out or dtype) == jnp.float32:
        assert _rel(y, y_ref) < 1e-6 and _rel(dx, dx_ref) < 1e-5 and _rel(dg, dg_ref) < 1e-5
    else:  # each of y and dx is the float32 result rounded once to bf16 (dx then widened to x's dtype); dg in float32
        for got, ref in ((y, y_ref), (dx, dx_ref)):
            err = np.abs(np.asarray(got, np.float32) - np.asarray(ref))
            assert (err <= 2.0 ** -8 * np.abs(np.asarray(ref)) + 1e-5 * np.abs(np.asarray(ref)).max()).all()
        assert _rel(dg, dg_ref) < 1e-4


@pytest.mark.parametrize("positions", ["per_token", "shared", "nope"])
def test_below_one_tile_the_op_is_the_plain_form(positions):
    # a cached pass: a block of 4 positions; the composed form is the plain chain itself, bit for bit
    x, g, pos, dy = _inputs((12, 4, 4, D), positions, jnp.bfloat16)
    got = _vjp(lambda x, g: op.qk_norm_rope(x, g, EPS, pos, THETA, interpret=True), x, g, dy)
    want = _vjp(lambda x, g: _plain(x, g, pos).astype(x.dtype), x, g, dy)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_heads_narrower_than_a_lane_tile_take_the_plain_form():
    x, g, pos, dy = _inputs((2, 256, 4, 16), "shared", jnp.float32)
    got = _vjp(lambda x, g: op.qk_norm_rope(x, g, EPS, pos, THETA, interpret=True), x, g, dy)
    want = _vjp(lambda x, g: _plain(x, g, pos), x, g, dy)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_each_shape_is_recorded_once_with_its_form():
    op.take_engaged()
    x, g, pos, _ = _inputs((1, 136, 4, D), "shared", jnp.bfloat16)
    for _ in range(2):
        op.qk_norm_rope(x, g, EPS, pos, THETA, interpret=True)
    op.qk_norm_rope(x[:, :8], g, EPS, None, THETA, interpret=True)
    assert op.take_engaged() == [
        {"form": "fused", "batch": 1, "tokens": 136, "heads": 4, "head_dim": D, "rotate": True, "dtype": "bfloat16",
         "out": "bfloat16"},
        {"form": "composed", "batch": 1, "tokens": 8, "heads": 4, "head_dim": D, "rotate": False, "dtype": "bfloat16",
         "out": "bfloat16"},
    ]
    assert op.take_engaged() == []


def test_tables_fold_rotate_halfs_sign():
    x, _, pos, _ = _inputs((2, 16, 4, D), "per_token", jnp.float32)
    cos, sin = op.tables(pos, D, THETA)
    rolled = x * cos[..., None, :] + jnp.roll(x, D // 2, axis=-1) * sin[..., None, :]
    np.testing.assert_allclose(np.asarray(rolled), np.asarray(rope(x, pos, THETA)), rtol=0, atol=1e-6)
