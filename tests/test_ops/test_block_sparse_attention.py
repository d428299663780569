"""The blocked attention op under tiles of its own for each of its three kernels
(``ops/block_sparse_attention.py``): every family of ``BlockSizes`` the chooser can
return gives the dense-mask attention, forward and backward, through Pallas'
interpreter; and the chooser alone, on shapes (no kernel is built for it)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.attention_tile_readings import SHAPES as CELL_SHAPES
from benchmarks.attention_tile_readings import block_sizes, chooser_arguments, describe
from sheeprl_tpu.models.sdar_moe import EpisodeLayout
from sheeprl_tpu.ops import block_sparse_attention as op
from sheeprl_tpu.ops.block_sparse_attention import SegmentMask, attention_under, block_sparse_flash_attention


def _sizes(fwd, dkv, dq=None):
    return block_sizes(fwd, dkv, dq or (512, 512), fused=dq is None)


def _dense_attention(q, k, v, mask):
    rep = q.shape[-2] // k.shape[-2]
    k, v = jnp.repeat(k, rep, -2), jnp.repeat(v, rep, -2)
    scores = jnp.einsum("...qhd,...khd->...hqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.swapaxes(jnp.einsum("...hqk,...khd->...hqd", probs, v), -3, -2)


def _segments(s_q, s_k, seed):
    """A mask of both rules at unequal lengths: shared keys under a limit, and segments of their own."""
    rng = np.random.default_rng(seed)
    k_segment = rng.integers(0, 3, s_k)
    k_segment[0] = 0  # every query sees key 0
    return SegmentMask(q_limit=rng.integers(0, s_k, s_q), q_segment=rng.integers(1, 3, s_q),
                       k_index=np.arange(s_k), k_segment=k_segment)


# a family: the forward's (query, key, key compute) tiles, dK/dV's, dQ's (query, key) or None = the fused backward
FAMILIES = {
    "split_unequal_tiles": ((128, 256, 256), (256, 128, 128), (128, 384)),
    "memory_over_compute": ((128, 512, 128), (128, 512, 256), (256, 256)),
    "fused_backward": ((256, 128, 128), (128, 256, 128), None),
    "fused_one_compute_tile": ((128, 128, 128), (256, 256, 256), None),
}


@pytest.mark.parametrize("rep", [1, 2], ids=["own_heads", "shared_heads"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_every_family_of_tiles_matches_the_dense_mask(family, rep):
    """Output and the three gradients against ``SegmentMask.dense()`` attention in f32, at lengths (200
    queries, 330 keys) that are a multiple of no tile and differ, so both are padded, each to the
    least common multiple of the tiles laid over it."""
    s_q, s_k, d, d_v = 200, 330, 24, 16
    sizes = _sizes(*FAMILIES[family])
    mask = _segments(s_q, s_k, seed=len(family))
    p_q, p_k = op._padded(s_q, s_k, sizes)
    assert p_q >= s_q and p_k >= s_k and p_q % 128 == 0 and p_k % 128 == 0
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(s_q + rep), 4)
    q = jax.random.normal(kq, (2, s_q, 2 * rep, d))
    k = jax.random.normal(kk, (2, s_k, 2, d))
    v = jax.random.normal(kv, (2, s_k, 2, d_v))
    weight = jax.random.normal(kg, (2, s_q, 2 * rep, d_v))

    def blocked(q, k, v):
        return attention_under(q, k, v, mask, sizes, interpret=True)

    dense = jnp.asarray(mask.dense())
    with jax.default_matmul_precision("highest"):
        want = _dense_attention(q, k, v, dense)
        want_grads = jax.grad(lambda *a: (_dense_attention(*a, dense) * weight).sum(), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(jax.jit(blocked)(q, k, v)), np.asarray(want), atol=2e-5)
    grads = jax.jit(jax.grad(lambda *a: (blocked(*a) * weight).sum(), argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4)


@pytest.mark.parametrize("family", ["split_unequal_tiles", "fused_backward"])
def test_computed_causal_mask_gives_the_stored_masks_tables_and_results(family):
    """The library's ``CausalMask`` in place of the dense array: the same block tables, the same output and
    gradients; refused where the lengths are padded or the mask is not the causal one."""
    n = 768
    sizes = _sizes(*FAMILIES[family])
    mask = SegmentMask.causal(n, n)
    stored, computed = (op._splash_kernel(op._as_bytes(mask), 1, sizes, c, True) for c in (False, True))
    for info in ("fwd_mask_info", "dkv_mask_info", "dq_mask_info"):
        a, b = getattr(stored, info), getattr(computed, info)
        assert (a is None) == (b is None) == (info == "dq_mask_info" and sizes.use_fused_bwd_kernel)
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a.block_mask), np.asarray(b.block_mask))
            np.testing.assert_array_equal(np.asarray(a.data_next), np.asarray(b.data_next))
    assert computed.fwd_mask_info.partial_mask_blocks is None and stored.fwd_mask_info.partial_mask_blocks is not None
    q, k, v = (jax.random.normal(key, (1, n, 2, 16)) for key in jax.random.split(jax.random.PRNGKey(0), 3))
    loss = lambda c: lambda *a: (attention_under(*a, mask, sizes, computed_causal=c, interpret=True) ** 2).sum()  # noqa: E731
    for a, b in zip(jax.grad(loss(True), argnums=(0, 1, 2))(q, k, v), jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    with pytest.raises(ValueError, match="unpadded causal"):
        attention_under(q[:, :700], k[:, :700], v[:, :700], SegmentMask.causal(700, 700), sizes, computed_causal=True, interpret=True)
    with pytest.raises(ValueError, match="unpadded causal"):
        attention_under(q, k, v, _segments(n, n, 0), sizes, computed_causal=True, interpret=True)


# ------------------------------------------------------------------ the chooser alone
def _tile_values(sizes):
    return [getattr(sizes, name) for name in op._TILE_NAMES if getattr(sizes, name) is not None]


def _causal(n, rep=1):
    return lambda: (n, n, 128, 128, rep, SegmentMask.causal(n, n))


SHAPES = {  # name: what the op hands the chooser (lengths, q/k and v width, rep, mask); first the benchmark cells' shapes
    **{name: (lambda name=name: chooser_arguments(name)) for name in CELL_SHAPES},
    "odd_length": _causal(1000),
    "short": _causal(130, rep=2),
    "prime_tiles": _causal(37 * 128),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_chooser_returns_lane_multiples_and_pads_no_further_than_one_tile_of_512(shape):
    n, _, _, _, _, mask = arguments = SHAPES[shape]()
    sizes, computed = op._tiles(*arguments)
    assert all(t % 128 == 0 for t in _tile_values(sizes))
    assert sizes.block_kv % sizes.block_kv_compute == 0 and sizes.block_kv_dkv % sizes.block_kv_dkv_compute == 0
    assert sizes.use_fused_bwd_kernel == (sizes.block_q_dq is None) == (sizes.block_kv_dq is None)
    # the padded lengths are today's: the shortest multiple of min(512, the length in lanes), every tile divides them
    block = min(512, -(-n // 128) * 128)
    assert op._padded(n, n, sizes) == (-(-n // block) * block,) * 2 == op._padded(n, n, op._one_tile(block))
    if computed:
        assert op._is_causal(mask) and op._padded(n, n, sizes) == (n, n)


def _described(sizes, computed):
    return f"{describe(sizes)} {'computed' if computed else 'stored'}"


ALL_512 = "fwd 512/512/512 dkv 512/512/512 dq 512/512"


@pytest.mark.parametrize("shape,want", [
    # the benchmark cells' shapes: PERF.md §6's table
    ("joyai_update", "fwd 1024/1024/256 dkv 512/2048/256 fused computed"),  # dq summed from 4 parts
    ("joyai_loop_update", "fwd 768/768/256 dkv 512/1536/256 fused computed"),  # 3 parts
    ("joyai_prefill", ALL_512 + " computed"),  # one tile of 1,024 would execute a third more than three of 512
    ("sdar_update", ALL_512 + " stored"),  # the own-copy diagonal fills any coarser tile; 11 parts are too many to fuse
    ("sdar_prefill", ALL_512 + " stored"),
    # what the rule does elsewhere
    ("odd_length", ALL_512 + " stored"),  # padded: the causal mask is stored
    ("prime_tiles", "fwd 1024/1024/256 dkv 640/1280/128 fused stored"),  # 4,736 padded to 5,120
])
def test_chooser_gives_the_table(shape, want):
    assert _described(*op._tiles(*SHAPES[shape]())) == want


@pytest.mark.parametrize("n,want", [
    (2048, "fwd 1024/1024/256 dkv 1024/1024/256 dq 1024/1024 computed"),  # one key tile of 2,048 executes too much
    (16384, "fwd 1024/1024/256 dkv 1024/1024/256 dq 1024/1024 computed"),  # 8 parts are too many to fuse
])
def test_chooser_splits_where_the_fused_form_would_not_hold(n, want):
    assert _described(*op._tiles(n, n, 128, 128, 1, SegmentMask.causal(n, n))) == want
    # and heads wider than the sweep compiled go as they always did
    assert _described(*op._tiles(n, n, 384, 128, 1, SegmentMask.causal(n, n))) == ALL_512 + " computed"


@pytest.mark.parametrize("block", [128, 256, 512])
def test_an_integer_block_size_is_todays_eight_equal_tiles(block, monkeypatch):
    """``block_size`` as an integer never asks the chooser: every tile of every kernel is that wide (or the
    whole padded sequence where that is shorter), the backward split, the mask stored."""
    built = []
    monkeypatch.setattr(op, "attention_under", lambda q, k, v, mask, sizes, computed, interpret: built.append((sizes, computed)))
    monkeypatch.setattr(op, "_tiles", lambda *a: pytest.fail("the chooser was asked"))
    for n in (1000, 200):
        block_sparse_flash_attention(jnp.zeros((n, 2, 128)), jnp.zeros((n, 1, 128)), jnp.zeros((n, 1, 128)),
                                     SegmentMask.causal(n, n), block)
        sizes, computed = built.pop()
        assert _tile_values(sizes) == [min(block, -(-n // 128) * 128)] * 8 and not sizes.use_fused_bwd_kernel and not computed
        assert op._padded(n, n, sizes) == (math.ceil(n / _tile_values(sizes)[0]) * _tile_values(sizes)[0],) * 2


def test_census_counts_the_tiles_a_mask_leaves():
    """``tile_census`` at 128 and ``coarser`` from it equal a direct count over the dense mask."""
    layout = EpisodeLayout(256, 512, 4, 4)  # 768 clean + 2,048 noised positions
    n = layout.length
    dense, census = layout.mask.dense(), op.tile_census(layout.mask, n, n)
    for by_q, by_k in ((1, 1), (2, 2), (11, 2), (1, 11)):
        tq, tk = 128 * by_q, 128 * by_k
        tiles = dense.reshape(n // tq, tq, n // tk, tk)
        want = tiles.any(axis=(1, 3)).astype(np.int8) + tiles.all(axis=(1, 3))
        np.testing.assert_array_equal(op.coarser(census, by_q, by_k), want)
    assert (census > 0).sum() < census.size // 3  # as the kernel's own tables have it (test_sdar_moe)


def test_a_built_kernel_says_once_how_it_engaged():
    op._splash_kernel.cache_clear()
    op.take_engaged()
    q = jnp.zeros((1, 256, 2, 128))
    block_sparse_flash_attention(q, q, q, SegmentMask.causal(256, 256), 128, interpret=True)
    block_sparse_flash_attention(q, q, q, SegmentMask.causal(256, 256), 128, interpret=True)  # the cache's hit says nothing
    (said,) = op.take_engaged()
    assert said["s_q"] == said["padded_q"] == 256 and said["rep"] == 1 and said["backward"] == "split" and said["mask"] == "stored"
    assert [said[name] for name in op._TILE_NAMES] == [128] * 8 and said["tiles_nonempty_share"] == 0.75
    assert op.take_engaged() == []


def test_by_shape_the_op_takes_the_fused_backward_and_matches_the_dense_mask():
    """No ``block_size``: at 4,608 causal positions (the loop cell's minibatch) the op itself takes the fused
    backward under the computed mask, says so, and gives the dense-mask attention's three gradients."""
    n = 4608
    mask = SegmentMask.causal(n, n)
    q, k, v = (jax.random.normal(key, (1, n, 1, 16)) for key in jax.random.split(jax.random.PRNGKey(0), 3))
    op._splash_kernel.cache_clear()
    op.take_engaged()
    got = jax.jit(jax.grad(lambda *a: (block_sparse_flash_attention(*a, mask, interpret=True) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    (said,) = op.take_engaged()
    assert said["backward"] == "fused" and said["mask"] == "computed_causal" and said["block_q_dq"] is None
    assert (said["block_q"], said["block_kv_dkv"], said["padded_q"]) == (768, 1536, n) and said["tiles_nonempty_share"] == 0.5833
    dense = jnp.asarray(mask.dense())
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda *a: (_dense_attention(*a, dense) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4)


def test_the_share_of_tiles_left_is_per_head_where_heads_share_a_mask():
    layout = EpisodeLayout(256, 512, 4, 4)
    n = layout.length
    census = op.tile_census(layout.mask, n, n)
    op._splash_kernel.cache_clear()
    op.take_engaged()
    op._splash_kernel(op._as_bytes(layout.mask), 8, op._one_tile(128), False, True)
    (said,) = op.take_engaged()
    assert said["rep"] == 8 and said["tiles_nonempty_share"] == round(float((census > 0).mean()), 4)
