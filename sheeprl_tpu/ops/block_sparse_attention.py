"""Attention under a mask that is data, as the TPU's block-sparse flash kernel
(``jax.experimental.pallas.ops.tpu.splash_attention``): scores, online softmax
and the weighted sum stay in fast memory, tiles the mask empties are skipped,
forward and backward.  This is the blocked attention of the language-model
policies: grouped-query attention under the block-diffusion mask
(``models/sdar_moe.py``) and latent attention with keys wider than values,
causal (``models/mla_moe.py``).  ``ring_attention.blockwise_attention`` is the
plain-XLA causal op of ``MultiHeadSelfAttention`` and takes no mask.

The forward kernel's two results, its output and its log-sum-exp, carry the
checkpoint name ``KERNEL_RESIDUALS``: they are all the kernel's backward rule
wants of its forward.  A caller that rematerialises the block around the op
under ``jax.checkpoint_policies.save_only_these_names(KERNEL_RESIDUALS)`` keeps
them and does not run the forward kernel a second time in the backward pass
(the models' ``remat``); where no policy asks for the name it is the identity.

Off a TPU the kernel runs only through Pallas' interpreter
(``interpret=True``: the CPU tests); without it a CPU refuses the call.
"""

from __future__ import annotations

import math
import sys
from functools import lru_cache
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_LANES = 128  # the kernel's tiles come in multiples of this
KERNEL_RESIDUALS = "blocked_attention_out"  # the name on the forward kernel's output and log-sum-exp


def _head_width(d: int) -> int:
    """The width a head of ``d`` numbers is handed to the kernel at: the one place heads are padded.
    A multiple of half a lane tile goes as it is (MLA's 192-wide queries and keys: the compiler lays
    them over two lane tiles in fast memory, so the products that contract over them cost the MXU
    256 / 192 of their FLOPs, and memory holds 192); any other width is zero-padded to whole tiles,
    in memory too."""
    return d if d >= _LANES and d % (_LANES // 2) == 0 else -(-d // _LANES) * _LANES


class SegmentMask(NamedTuple):
    """A mask given as data: four integer arrays, one entry per position,
    concrete (numpy) when the op is traced, because the kernel's block tables
    are built from them.  Query ``i`` sees key ``t`` iff

        k_segment[t] == 0  and  k_index[t] <= q_limit[i]        (shared keys)
        k_segment[t] != 0  and  k_segment[t] == q_segment[i]    (a segment's own)

    ``causal`` is the case ``q_limit = k_index = arange`` with every segment 0;
    a block-diffusion episode (``sheeprl_tpu/models/sdar_moe.py``) gives the
    clean sequence segment 0 with ``k_index`` its block, and every noised copy
    a segment of its own."""

    q_limit: np.ndarray
    q_segment: np.ndarray
    k_index: np.ndarray
    k_segment: np.ndarray

    @classmethod
    def causal(cls, s_q: int, s_k: int) -> "SegmentMask":
        return cls(np.arange(s_q), np.zeros(s_q, np.int64), np.arange(s_k), np.zeros(s_k, np.int64))

    def dense(self) -> np.ndarray:
        """(Q, K) bool, by the rule above."""
        ql, qs, ki, ks = (np.asarray(a).astype(np.int64) for a in self)
        return np.where(ks[None, :] == 0, ki[None, :] <= ql[:, None], ks[None, :] == qs[:, None])


def _pad(x: np.ndarray, n: int, value: int) -> np.ndarray:
    return np.concatenate([x, np.full(n - len(x), value, x.dtype)])


# the library's eight tile names: the forward kernel's, the dK/dV kernel's and the dQ kernel's
_TILE_NAMES = ("block_q", "block_kv", "block_kv_compute", "block_q_dkv", "block_kv_dkv", "block_kv_dkv_compute",
               "block_q_dq", "block_kv_dq")
ENGAGED: List[dict] = []  # how each kernel built in this process engaged (``take_engaged``)


def _library():
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    return sk, sm


def _one_tile(block: int):
    """Every tile of every kernel ``block`` wide: what an integer ``block_size`` asks for."""
    return _library()[0].BlockSizes(**{name: block for name in _TILE_NAMES})


def _padded(s_q: int, s_k: int, sizes) -> Tuple[int, int]:
    """The shortest lengths that are multiples of every tile ``sizes`` lays over them."""
    across = lambda *tiles: math.lcm(*(t for t in tiles if t))  # noqa: E731
    m_q = across(sizes.block_q, sizes.block_q_dkv, sizes.block_q_dq)
    m_k = across(sizes.block_kv, sizes.block_kv_dkv, sizes.block_kv_dq)
    return -(-s_q // m_q) * m_q, -(-s_k // m_k) * m_k


def _as_bytes(mask: SegmentMask) -> Tuple[bytes, ...]:
    return tuple(np.asarray(a).astype(np.int64).tobytes() for a in mask)


def _is_causal(mask: SegmentMask) -> bool:
    s_q, s_k = len(mask.q_limit), len(mask.k_index)
    return s_q == s_k and all(np.array_equal(np.asarray(a), b) for a, b in zip(mask, SegmentMask.causal(s_q, s_k)))


@lru_cache(maxsize=1)
def _dense_padded(mask_bytes: Tuple[bytes, ...], p_q: int, p_k: int) -> np.ndarray:
    """(p_q, p_k) bool: the mask with padded queries that see nothing and padded keys no query sees
    (segment -1).  Kept from the chooser's census until the kernel's tables are built from the same array."""
    q_limit, q_segment, k_index, k_segment = (np.frombuffer(b, np.int64) for b in mask_bytes)
    return SegmentMask(_pad(q_limit, p_q, -1), _pad(q_segment, p_q, 0), _pad(k_index, p_k, 0), _pad(k_segment, p_k, -1)).dense()


def tile_census(mask: SegmentMask, p_q: int, p_k: int, tile: int = _LANES) -> np.ndarray:
    """(p_q / tile, p_k / tile) int8 over the padded mask: 0 where a tile is empty, 2 where every query
    of it sees every key, 1 where it is partial.  Coarser tiles are read from the table at 128
    (``coarser``), so a mask is walked once; an unpadded causal mask is not walked at all."""
    if (p_q, p_k) == (len(mask.q_limit), len(mask.k_index)) and _is_causal(mask):
        return (2 * np.tri(p_q // tile, p_k // tile, -1, dtype=np.int8) + np.eye(p_q // tile, p_k // tile, dtype=np.int8))
    tiles = _dense_padded(_as_bytes(mask), p_q, p_k).reshape(p_q // tile, tile, p_k // tile, tile)
    return tiles.any(axis=(1, 3)).astype(np.int8) + tiles.all(axis=(1, 3))


def coarser(census: np.ndarray, by_q: int, by_k: int) -> np.ndarray:
    """The census at tiles ``by_q`` x ``by_k`` times as large."""
    tiles = census.reshape(census.shape[0] // by_q, by_q, census.shape[1] // by_k, by_k)
    return (tiles.max(axis=(1, 3)) > 0).astype(np.int8) + (tiles.min(axis=(1, 3)) == 2)


# What ``benchmarks/attention_tile_readings.py`` read on a v5e (PERF.md §6 has its table) and what
# ``_tiles`` makes of it.  A 512 x 512 tile is bound by the softmax's f32 passes, not by the MXU or by
# re-reading keys, so a larger tile wins only what the grid's steps cost (a tenth of a causal kernel) and
# loses what the mask empties at 512 and no longer does: 1,024 wins under a causal mask of 2,048 and more
# and loses under the block-diffusion mask, whose own-copy diagonal fills any larger tile with work.
_SPLIT_TILE = 1024  # a split kernel's memory tiles: the largest divisor of the padded length up to here
_FUSED_KEY_TILE = 2048  # the fused backward's key tile, which decides how many parts ``dq`` is summed from
_TILE_AREA = 1024 * 1024  # query tile x key tile: every kernel compiles at it under 16 MiB of scoped VMEM ...
_LANES_READ = (256, 128)  # ... with heads up to this wide in lanes (q / k, v), the mask stored or computed
_SCORE_AREA = 512 * 512  # query tile x key COMPUTE tile: a larger f32 score tile spills (1,024 x 512 reads 4 % slower)
_AREA_GROWTH = 1.25  # a coarser tile may execute this much more of the score matrix than tiles of 512 do
_FUSED_PARTS = 4  # the fused backward where ``dq`` is summed from at most this many bf16 parts


def _divisor(length: int, most: int) -> int:
    """The largest multiple of 128 that divides ``length`` and is at most ``most`` (128 divides it)."""
    return max(t for t in range(_LANES, max(most, _LANES) + 1, _LANES) if length % t == 0)


def _tiles(s_q: int, s_k: int, d: int, d_v: int, rep: int, mask: SegmentMask):
    """``_tiles_of`` under the arguments the op has at hand (the lengths are the mask's)."""
    assert (s_q, s_k) == (len(mask.q_limit), len(mask.k_index))
    return _tiles_of(_as_bytes(mask), d, d_v, rep)


@lru_cache(maxsize=16)
def _tiles_of(mask_bytes: Tuple[bytes, ...], d: int, d_v: int, rep: int):
    """The ``BlockSizes`` of a call and whether its causal mask is computed in the kernel, from what the
    op can see: the lengths, the head widths as handed over, the query heads a key-value head, the mask
    (once a mask and shape: every block of a model asks again).
    The padded lengths are those of tiles of 512 (every tile chosen divides them).  Each kernel takes the
    largest tiles up to ``_SPLIT_TILE`` that execute at most ``_AREA_GROWTH`` times the score elements the
    mask leaves to tiles of 512 (``tile_census``), the wider key tile where only one side can grow, and a
    compute tile that keeps the f32 score tile at ``_SCORE_AREA``.  The backward is fused (no dQ kernel;
    dK/dV writes a bf16 part of ``dq`` per key tile, summed outside) where a key tile up to
    ``_FUSED_KEY_TILE`` passes the same test and leaves at most ``_FUSED_PARTS`` parts: that bounds the
    parts' memory at that many copies of q and the roundings of a ``dq`` at as many.  ``rep`` decides
    nothing: a grid step holds one query head whatever the heads share."""
    mask = SegmentMask(*(np.frombuffer(b, np.int64) for b in mask_bytes))
    s_q, s_k = len(mask.q_limit), len(mask.k_index)
    block = min(512, -(-max(s_q, s_k) // _LANES) * _LANES)
    p_q, p_k = -(-s_q // block) * block, -(-s_k // block) * block
    computed = (p_q, p_k) == (s_q, s_k) and _is_causal(mask)
    if max(p_q, p_k) <= block or d > _LANES_READ[0] or d_v > _LANES_READ[1]:
        return _one_tile(block), computed
    census = tile_census(mask, p_q, p_k)

    def executed(bq, bkv):
        return int((coarser(census, bq // _LANES, bkv // _LANES) > 0).sum()) * bq * bkv

    allowed = _AREA_GROWTH * executed(block, block)

    def compute_tile(bq, bkv, share=1):
        return _divisor(bkv, max(_SCORE_AREA // share // bq, _LANES))

    big_q, big_k = _divisor(p_q, _SPLIT_TILE), _divisor(p_k, _SPLIT_TILE)
    split = next(t for t in ((big_q, big_k), (block, big_k), (big_q, block), (block, block)) if executed(*t) <= allowed)
    sizes = dict(block_q=split[0], block_kv=split[1], block_kv_compute=compute_tile(*split))
    fused_k = _divisor(p_k, min(_FUSED_KEY_TILE, _TILE_AREA // block))
    fused_q = _divisor(p_q, max(_TILE_AREA // fused_k, block))
    if p_k // fused_k <= _FUSED_PARTS and executed(fused_q, fused_k) <= allowed:
        # (half the score tile: beside dK/dV's it holds ``dq``'s f32 scratch and part, and 512 / 2,048 / 512
        # compiles alone but not inside the causal update: 16.07 MiB of 16)
        sizes.update(block_q_dkv=fused_q, block_kv_dkv=fused_k, block_kv_dkv_compute=compute_tile(fused_q, fused_k, 2),
                     use_fused_bwd_kernel=True)
    else:
        sizes.update(block_q_dkv=split[0], block_kv_dkv=split[1], block_kv_dkv_compute=compute_tile(*split),
                     block_q_dq=split[0], block_kv_dq=split[1])
    return _library()[0].BlockSizes(**sizes), computed


@lru_cache(maxsize=16)
def _splash_kernel(mask_bytes: Tuple[bytes, ...], rep: int, sizes, computed_causal: bool, interpret: bool):
    """The kernel for one mask (built once: reading a 5,632 x 5,632 mask into
    its block tables takes about a second).  ``computed_causal``: the mask goes
    to the library as its ``CausalMask``, which yields the same block tables
    and has the kernel compute a partial tile's mask from the positions
    instead of reading a stored tile (only where nothing is padded)."""
    sk, sm = _library()
    mask = SegmentMask(*(np.frombuffer(b, np.int64) for b in mask_bytes))
    s_q, s_k = len(mask.q_limit), len(mask.k_index)
    p_q, p_k = _padded(s_q, s_k, sizes)
    if computed_causal:
        if (p_q, p_k) != (s_q, s_k) or not _is_causal(mask):
            raise ValueError("only an unpadded causal mask can be computed in the kernel")
        head_mask = sm.CausalMask((p_q, p_k))
    else:
        head_mask = sm.NumpyMask(_dense_padded(mask_bytes, p_q, p_k))
    # the query heads that share a key-value head share the mask.  The kernel object holds its block
    # tables as arrays: they must be concrete, not values of whichever trace first asked for the kernel
    with jax.ensure_compile_time_eval():
        kernel = sk.make_splash_mqa_single_device(
            sm.MultiHeadMask([head_mask] * rep), block_sizes=sizes,
            residual_checkpoint_name=KERNEL_RESIDUALS, interpret=interpret
        )
    visited = np.asarray(kernel.fwd_mask_info.block_mask) != 0
    engaged = {
        "s_q": s_q, "s_k": s_k, "padded_q": p_q, "padded_k": p_k, "rep": rep,
        # (heads that share a mask share one table)
        "tiles_nonempty_share": round(float(visited.sum()) * sizes.block_q * sizes.block_kv / (visited.shape[0] * p_q * p_k), 4),
        **{name: getattr(sizes, name) for name in _TILE_NAMES},
        "backward": "fused" if sizes.use_fused_bwd_kernel else "split",
        "mask": "computed_causal" if computed_causal else "stored",
    }
    _dense_padded.cache_clear()  # tens of MB, read for the last time
    ENGAGED.append(engaged)
    print(f"Blocked attention kernel: {engaged}", file=sys.stderr, flush=True)
    return kernel


def take_engaged() -> List[dict]:
    """How the kernels built since the last call engaged, one dict a kernel (lengths, ``rep``, the
    share of forward tiles the mask leaves non-empty, the eight tiles, fused or split backward): what a
    run's telemetry record carries once."""
    taken, ENGAGED[:] = list(ENGAGED), []
    return taken


def attention_under(q: jax.Array, k: jax.Array, v: jax.Array, mask: SegmentMask, sizes, computed_causal: bool = False,
                    interpret: bool = False) -> jax.Array:
    """``block_sparse_flash_attention`` under tiles given outright, a library ``BlockSizes``: the tile
    sweep's entry (``benchmarks/attention_tile_readings.py``) and the body of the op."""
    s_q, h_q, d = q.shape[-3:]
    s_k, h_kv, d_v = k.shape[-3], k.shape[-2], v.shape[-1]
    rep = h_q // h_kv
    p_q, p_k = _padded(s_q, s_k, sizes)
    kernel = _splash_kernel(_as_bytes(mask), rep, sizes, computed_causal, interpret)
    batch = q.shape[:-3]

    def padded(x, s):  # (..., S, H, D) -> (B, s, H, padded D)
        x = x.reshape(-1, *x.shape[-3:])
        return jnp.pad(x, ((0, 0), (0, s - x.shape[1]), (0, 0), (0, _head_width(x.shape[-1]) - x.shape[-1])))

    scale = 1.0 / np.sqrt(d)  # the kernel takes queries already scaled
    qg = padded((q.astype(jnp.float32) * scale).astype(q.dtype), p_q).reshape(-1, p_q, h_kv, rep, _head_width(d))
    qg = jnp.moveaxis(qg, 1, 3)  # (B, H_kv, rep, S, D)
    kg, vg = (jnp.moveaxis(padded(x, p_k), 1, 2) for x in (k, v))  # (B, H_kv, S, D)
    out = jax.vmap(jax.vmap(kernel))(qg, kg, vg)  # over episodes, over key-value heads
    return jnp.moveaxis(out, 3, 1).reshape(*batch, p_q, h_q, _head_width(d_v))[..., :s_q, :, :d_v]


def block_sparse_flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: SegmentMask, block_size: Optional[int] = None, interpret: bool = False
) -> jax.Array:
    """q: (..., Sq, Hq, D); k: (..., Sk, Hkv, D); v: (..., Sk, Hkv, Dv) with
    ``Hq`` a multiple of ``Hkv`` (each key-value head serves ``Hq / Hkv`` query
    heads; ``Hq == Hkv``: heads that share nothing) and ``Dv`` any width of its
    own.  Scores are scaled by ``1 / sqrt(D)``.  Returns (..., Sq, Hq, Dv).
    Without ``block_size`` each of the three kernels gets tiles of its own by
    the call's shapes and mask (``_tiles``); an integer (a multiple of 128)
    makes every tile of every kernel that wide, or the whole padded sequence
    where that is shorter.  Sequences that are no multiple of their tiles are
    padded with positions nothing sees, head widths with zeros by
    ``_head_width``.  Only reverse-mode differentiation is supported."""
    s_q, h_q, d = q.shape[-3:]
    s_k, h_kv, d_v = k.shape[-3], k.shape[-2], v.shape[-1]
    if k.shape[-1] != d:
        raise ValueError(f"queries are {d} wide and keys {k.shape[-1]}")
    if h_q % h_kv:
        raise ValueError(f"{h_q} query heads cannot share {h_kv} key-value heads")
    if block_size is None:
        sizes, computed_causal = _tiles(s_q, s_k, _head_width(d), _head_width(d_v), h_q // h_kv, mask)
    elif block_size % _LANES:
        raise ValueError(f"the kernel's tiles are multiples of {_LANES} wide, got {block_size}")
    else:
        sizes, computed_causal = _one_tile(min(block_size, -(-max(s_q, s_k) // _LANES) * _LANES)), False
    return attention_under(q, k, v, mask, sizes, computed_causal, interpret)
