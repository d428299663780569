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

from functools import lru_cache
from typing import NamedTuple, Tuple

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


@lru_cache(maxsize=16)
def _splash_kernel(mask_bytes: Tuple[bytes, ...], padded: Tuple[int, int], rep: int, block: int, interpret: bool):
    """The kernel for one mask (built once: reading a 5,632 x 5,632 mask into
    its block tables takes about a second).  Padded queries see nothing and no
    query sees a padded key (segment -1)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    q_limit, q_segment, k_index, k_segment = (np.frombuffer(b, np.int64) for b in mask_bytes)
    dense = SegmentMask(_pad(q_limit, padded[0], -1), _pad(q_segment, padded[0], 0), _pad(k_index, padded[1], 0),
                        _pad(k_segment, padded[1], -1)).dense()
    sizes = sk.BlockSizes(**{name: block for name in (
        "block_q", "block_kv", "block_kv_compute", "block_q_dkv", "block_kv_dkv", "block_kv_dkv_compute", "block_q_dq",
        "block_kv_dq")})
    # the query heads that share a key-value head share the mask.  The kernel object holds its block
    # tables as arrays: they must be concrete, not values of whichever trace first asked for the kernel
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa_single_device(
            sm.MultiHeadMask([sm.NumpyMask(dense)] * rep), block_sizes=sizes,
            residual_checkpoint_name=KERNEL_RESIDUALS, interpret=interpret
        )


def block_sparse_flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: SegmentMask, block_size: int = 512, interpret: bool = False
) -> jax.Array:
    """q: (..., Sq, Hq, D); k: (..., Sk, Hkv, D); v: (..., Sk, Hkv, Dv) with
    ``Hq`` a multiple of ``Hkv`` (each key-value head serves ``Hq / Hkv`` query
    heads; ``Hq == Hkv``: heads that share nothing) and ``Dv`` any width of its
    own.  Scores are scaled by ``1 / sqrt(D)``.  Returns (..., Sq, Hq, Dv).
    Tiles are ``block_size`` wide (a multiple of 128, or the whole padded
    sequence where that is shorter); sequences that are no multiple of the
    tile are padded with positions nothing sees, head widths with zeros by
    ``_head_width``.  Only reverse-mode differentiation is supported."""
    s_q, h_q, d = q.shape[-3:]
    s_k, h_kv, d_v = k.shape[-3], k.shape[-2], v.shape[-1]
    if k.shape[-1] != d:
        raise ValueError(f"queries are {d} wide and keys {k.shape[-1]}")
    if h_q % h_kv:
        raise ValueError(f"{h_q} query heads cannot share {h_kv} key-value heads")
    if block_size % _LANES:
        raise ValueError(f"the kernel's tiles are multiples of {_LANES} wide, got {block_size}")
    rep = h_q // h_kv
    block = min(block_size, -(-max(s_q, s_k) // _LANES) * _LANES)
    p_q, p_k = -(-s_q // block) * block, -(-s_k // block) * block
    kernel = _splash_kernel(tuple(np.asarray(a).astype(np.int64).tobytes() for a in mask), (p_q, p_k), rep, block,
                            interpret)
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
