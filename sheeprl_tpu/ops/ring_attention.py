"""Sequence/context parallelism primitives: blockwise + ring attention.

The reference framework has no attention anywhere (SURVEY.md §5.7) — its
long-sequence handling is truncated BPTT through the RSSM. These ops make
long-context sequence parallelism a first-class capability of the TPU
runtime for attention-based models: the sequence axis is sharded over a
mesh axis, every device computes attention for its query shard, and K/V
shards rotate around the ring over ICI (`jax.lax.ppermute`) while an
online-softmax accumulator folds in one block per hop — memory per device
stays O(seq/n_devices), and the K/V transfer overlaps with the block
matmuls (Ring Attention, arXiv:2310.01889; blockwise parallel transformers,
arXiv:2305.19370).

Layouts: `q, k, v` are `(..., S, H, D)` (sequence, heads, head_dim) —
batch dims lead. All math runs in float32 accumulators regardless of input
dtype (bf16-safe).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def _block_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """(…, Sq, H, D) x (…, Sk, H, D) -> (…, H, Sq, Sk) scaled scores."""
    d = q.shape[-1]
    return jnp.einsum("...qhd,...khd->...hqk", q, k).astype(jnp.float32) / jnp.sqrt(
        jnp.float32(d)
    )


def _online_update(carry, scores: jax.Array, v: jax.Array, mask: Optional[jax.Array]):
    """Fold one KV block into the online-softmax state.

    carry: (acc (…, H, Sq, D), row_sum (…, H, Sq, 1), row_max (…, H, Sq, 1))
    """
    acc, row_sum, row_max = carry
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    block_max = scores.max(-1, keepdims=True)
    new_max = jnp.maximum(row_max, block_max)
    # -inf rows (fully masked so far) must not produce NaNs
    safe_new_max = jnp.where(jnp.isneginf(new_max), 0.0, new_max)
    correction = jnp.exp(row_max - safe_new_max)
    p = jnp.exp(scores - safe_new_max)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    acc = acc * correction + jnp.einsum("...hqk,...khd->...hqd", p, v.astype(jnp.float32))
    row_sum = row_sum * correction + p.sum(-1, keepdims=True)
    return acc, row_sum, new_max


def _finalize(acc: jax.Array, row_sum: jax.Array, dtype) -> jax.Array:
    out = acc / jnp.maximum(row_sum, 1e-30)
    # (…, H, Sq, D) -> (…, Sq, H, D)
    return jnp.swapaxes(out, -3, -2).astype(dtype)


def _mark_varying(tree, axis_name: str):
    """Zeros-initialized accumulators are device-INvariant to shard_map's
    varying-axes typing while the scan body's outputs (mixed with sharded
    inputs) are device-varying — mark the carry varying up front so the
    scan types close."""
    return jax.lax.pcast(tree, axis_name, to="varying")


def _hop_block_mask(src, j, block: int, s_local: int, q_pos, scores_shape, causal: bool):
    """Padding + causal mask for inner block `j` of the K/V shard that
    originated on device `src` — SHARED by the forward fold and the custom
    backward so the recomputed softmax weights can never desynchronize
    from the forward's."""
    k_pos = src * s_local + j * block + jnp.arange(block)
    mask = k_pos[None, :] < (src * s_local + s_local)  # pad mask
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    return jnp.broadcast_to(mask, scores_shape)


def _pad_blocks(x, batch_shape, n_inner: int, block: int, pad: int):
    """(…, S/n, H, D) -> (n_inner, …, block, H, D) scan layout, padding the
    sequence axis up to a block multiple. Shared by fwd + bwd hops."""
    if pad:
        widths = [(0, 0)] * (x.ndim - 3) + [(0, pad), (0, 0), (0, 0)]
        x = jnp.pad(x, widths)
    h = x.shape[-2]
    x = x.reshape(*batch_shape, n_inner, block, h, x.shape[-1])
    return jnp.moveaxis(x, len(batch_shape), 0)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_size: int = 512,
    causal: bool = False,
) -> jax.Array:
    """Single-device flash-style attention: `lax.scan` over KV blocks with
    an online softmax — O(S * block) memory instead of O(S^2).

    q, k, v: (..., S, H, D). Returns (..., Sq, H, D)."""
    s_k = k.shape[-3]
    block_size = min(block_size, s_k)
    n_blocks = -(-s_k // block_size)
    pad = n_blocks * block_size - s_k

    s_q = q.shape[-3]
    h = q.shape[-2]
    batch_shape = q.shape[:-3]
    q_pos = jnp.arange(s_q)

    # single-device case == one ring hop with src=0 and the whole sequence
    # as the "local shard": reuse the shared blocking + mask helpers so the
    # logic cannot drift from the ring path
    kb = _pad_blocks(k, batch_shape, n_blocks, block_size, pad)
    vb = _pad_blocks(v, batch_shape, n_blocks, block_size, pad)

    acc = jnp.zeros((*batch_shape, h, s_q, q.shape[-1]), jnp.float32)
    row_sum = jnp.zeros((*batch_shape, h, s_q, 1), jnp.float32)
    row_max = jnp.full((*batch_shape, h, s_q, 1), -jnp.inf, jnp.float32)

    def step(carry, inp):
        i, (k_i, v_i) = inp
        scores = _block_scores(q, k_i)
        mask = _hop_block_mask(0, i, block_size, s_k, q_pos, scores.shape[-2:], causal)
        return _online_update(carry, scores, v_i, mask), None

    # remat the block fold: autodiff would otherwise SAVE every block's
    # (H, Sq, block) scores/probabilities for the backward pass, making the
    # "O(S * block)" claim quietly O(S^2) once gradients flow (caught by
    # benchmarks/bench_ring_attention.py's compiled-memory sweep).
    # Recomputing scores in the backward pass is the flash-attention trade.
    (acc, row_sum, _), _ = jax.lax.scan(
        jax.checkpoint(step), (acc, row_sum, row_max), (jnp.arange(n_blocks), (kb, vb))
    )
    return _finalize(acc, row_sum, q.dtype)


def _ring_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
):
    """Forward ring pass; returns `(out, lse)` where `lse` is the
    per-query log-sum-exp `(…, H, Sq, 1)` the custom backward needs to
    re-normalize recomputed score blocks."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[-3]
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_pos = idx * s_local + jnp.arange(s_local)

    acc = jnp.zeros((*q.shape[:-3], q.shape[-2], s_local, q.shape[-1]), jnp.float32)
    row_sum = jnp.zeros((*q.shape[:-3], q.shape[-2], s_local, 1), jnp.float32)
    row_max = jnp.full((*q.shape[:-3], q.shape[-2], s_local, 1), -jnp.inf, jnp.float32)

    # hop loop as lax.scan: an unrolled python loop left EVERY hop's
    # (H, S/n, S/n) score/probability buffers simultaneously live (XLA's
    # buffer assignment would not reuse them across the unrolled hops), so
    # both forward and backward peaked at O(S^2/n) per device — exactly the
    # blowup ring attention exists to avoid.  With a scan only one hop's
    # buffers exist at a time, and the rematted body keeps autodiff from
    # saving per-hop scores (the flash-attention trade: recompute in bwd).
    # Measured by benchmarks/bench_ring_attention.py's compiled-memory sweep.
    # inner blocking: even one hop's FULL (S/n, S/n) score block is the
    # dominant working set at long context; folding the hop's K/V shard in
    # (S/n, block) chunks keeps per-device temp memory ~linear in S/n
    batch_shape = q.shape[:-3]
    block = min(512, s_local)
    n_inner = -(-s_local // block)
    pad = n_inner * block - s_local

    def hop(carry, i):
        acc_state, k_i, v_i = carry
        src = (idx - i) % n  # K/V origin device after i hops

        def inner(carry2, inp):
            j, (k_j, v_j) = inp
            scores = _block_scores(q, k_j)
            mask = _hop_block_mask(src, j, block, s_local, q_pos, scores.shape[-2:], causal)
            return _online_update(carry2, scores, v_j, mask), None

        acc_state, _ = jax.lax.scan(
            jax.checkpoint(inner),
            acc_state,
            (
                jnp.arange(n_inner),
                (
                    _pad_blocks(k_i, batch_shape, n_inner, block, pad),
                    _pad_blocks(v_i, batch_shape, n_inner, block, pad),
                ),
            ),
        )
        # rotate K/V one step around the ring (the final rotation returns
        # them to their origin device — semantics-free)
        k_i = jax.lax.ppermute(k_i, axis_name, perm)
        v_i = jax.lax.ppermute(v_i, axis_name, perm)
        return (acc_state, k_i, v_i), None

    acc, row_sum, row_max = _mark_varying((acc, row_sum, row_max), axis_name)
    init = ((acc, row_sum, row_max), k, v)
    # no outer remat: the inner fold already remats the score blocks, and
    # under the custom VJP below autodiff never traces this scan at all.
    (acc_state, _, _), _ = jax.lax.scan(hop, init, jnp.arange(n))
    acc, row_sum, row_max = acc_state
    lse = jnp.where(
        row_sum > 0.0,
        jnp.where(jnp.isneginf(row_max), 0.0, row_max) + jnp.log(jnp.maximum(row_sum, 1e-30)),
        -jnp.inf,
    )
    return _finalize(acc, row_sum, q.dtype), lse


def _ring_backward(q, k, v, out, lse, g, axis_name: str, causal: bool):
    """Flash-style backward for the ring: rotate K/V (and their gradient
    accumulators) around the ring AGAIN, recomputing each hop's score
    blocks from the saved `lse` instead of storing them — so residuals are
    just the local q/k/v/out/lse shards, O(S/n) per device, not the
    O(S) per-device K/V carry chain a plain `lax.scan` VJP would save.

    Standard flash-attention gradients per block (scores already scaled):
      W  = exp(scores - lse)            (softmax weights, recomputed)
      dV = Wᵀ · dO
      dP = dO · Vᵀ
      dS = W ⊙ (dP - Δ) / sqrt(D),  Δ = rowsum(dO ⊙ O)
      dQ += dS · K,   dK += dSᵀ · Q
    Each device keeps its query-shard quantities (q, dO, Δ, lse, dQ)
    resident; (K, V, dK, dV) travel together — after n hops dK/dV have
    accumulated every device's contribution and are home again."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[-3]
    h = q.shape[-2]
    d = q.shape[-1]
    batch_shape = q.shape[:-3]
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_pos = idx * s_local + jnp.arange(s_local)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    # head-major f32 copies of the query-resident tensors
    qt = jnp.swapaxes(q, -3, -2).astype(jnp.float32)  # (…, H, Sq, D)
    gt = jnp.swapaxes(g, -3, -2).astype(jnp.float32)
    ot = jnp.swapaxes(out, -3, -2).astype(jnp.float32)
    delta = (gt * ot).sum(-1, keepdims=True)  # (…, H, Sq, 1)
    dead = jnp.isneginf(lse)  # fully-masked query rows contribute nothing
    safe_lse = jnp.where(dead, 0.0, lse)

    block = min(512, s_local)
    n_inner = -(-s_local // block)
    pad = n_inner * block - s_local

    def from_blocks(x):
        x = jnp.moveaxis(x, 0, len(batch_shape))
        x = x.reshape(*batch_shape, n_inner * block, h, x.shape[-1])
        return x[..., :s_local, :, :]

    def hop(carry, i):
        dq, k_i, v_i, dk_i, dv_i = carry
        src = (idx - i) % n  # K/V origin device after i hops (as in fwd)

        def inner(dq2, inp):
            j, (k_j, v_j) = inp
            scores = _block_scores(q, k_j)  # (…, H, Sq, block) f32
            mask = _hop_block_mask(src, j, block, s_local, q_pos, scores.shape[-2:], causal)
            w = jnp.where(mask & ~dead, jnp.exp(scores - safe_lse), 0.0)
            kt_j = jnp.swapaxes(k_j, -3, -2).astype(jnp.float32)  # (…, H, block, D)
            vt_j = jnp.swapaxes(v_j, -3, -2).astype(jnp.float32)
            dp = jnp.einsum("...hqd,...hkd->...hqk", gt, vt_j)
            ds = w * (dp - delta) * scale
            dq_c = jnp.einsum("...hqk,...hkd->...hqd", ds, kt_j)
            dk_j = jnp.einsum("...hqk,...hqd->...khd", ds, qt)
            dv_j = jnp.einsum("...hqk,...hqd->...khd", w, gt)
            return dq2 + dq_c, (dk_j, dv_j)

        dq, (dk_blocks, dv_blocks) = jax.lax.scan(
            inner,
            dq,
            (
                jnp.arange(n_inner),
                (
                    _pad_blocks(k_i, batch_shape, n_inner, block, pad),
                    _pad_blocks(v_i, batch_shape, n_inner, block, pad),
                ),
            ),
        )
        dk_i = dk_i + from_blocks(dk_blocks)
        dv_i = dv_i + from_blocks(dv_blocks)
        # rotate the shard AND its gradient accumulator together; after n
        # hops both are back on the shard's origin device
        k_i, v_i, dk_i, dv_i = (
            jax.lax.ppermute(x, axis_name, perm) for x in (k_i, v_i, dk_i, dv_i)
        )
        return (dq, k_i, v_i, dk_i, dv_i), None

    dq = jnp.zeros(qt.shape, jnp.float32)
    dk = jnp.zeros((*batch_shape, s_local, h, d), jnp.float32)
    dv = jnp.zeros(dk.shape, jnp.float32)
    dq, dk, dv = _mark_varying((dq, dk, dv), axis_name)
    (dq, _, _, dk, dv), _ = jax.lax.scan(hop, (dq, k, v, dk, dv), jnp.arange(n))
    dq = jnp.swapaxes(dq, -3, -2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@lru_cache(maxsize=None)
def _ring_attention_vjp(axis_name: str, causal: bool):
    @jax.custom_vjp
    def attn(q, k, v):
        return _ring_forward(q, k, v, axis_name, causal)[0]

    def fwd(q, k, v):
        out, lse = _ring_forward(q, k, v, axis_name, causal)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        return _ring_backward(*res, g, axis_name, causal)

    attn.defvjp(fwd, bwd)
    return attn


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
) -> jax.Array:
    """Ring attention body — call INSIDE `shard_map` with the sequence axis
    sharded over `axis_name`.

    Each device holds `(..., S/n, H, D)` shards. K/V rotate around the ring
    with `ppermute`; after n hops every query shard has attended to the
    full sequence. For `causal=True` global positions are reconstructed
    from the device index and the hop count.

    Differentiation goes through a custom VJP (`_ring_backward`) that
    re-rotates K/V around the ring instead of saving the forward scan's
    per-hop K/V carries — per-device memory stays O(S/n) under gradients
    (measured by benchmarks/bench_ring_attention.py). Trade-off of
    `jax.custom_vjp`: only reverse-mode differentiation is supported —
    `jax.jvp` / `jax.jacfwd` / `jax.linearize` through this op raise."""
    return _ring_attention_vjp(axis_name, bool(causal))(q, k, v)


def make_ring_attention(
    mesh: Mesh,
    axis_name: str = "data",
    causal: bool = False,
):
    """jitted ring attention over `mesh`: inputs `(..., S, H, D)` with the
    sequence axis sharded over `axis_name` (S divisible by the axis size).

    This is the public entry: it wraps `ring_attention` in `shard_map` with
    the sequence-sharded PartitionSpecs and jits the result. The spec is
    built per input rank so any number of leading batch dims works."""
    fns = {}

    def _build(ndim: int):
        # (..., S, H, D): shard the sequence axis, replicate the rest
        spec = P(*([None] * (ndim - 3)), axis_name, None, None)

        @jax.jit
        @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        def fn(q, k, v):
            return ring_attention(q, k, v, axis_name=axis_name, causal=causal)

        return fn, NamedSharding(mesh, spec)

    def apply(q, k, v):
        if q.ndim < 3:
            raise ValueError(f"ring attention inputs must be (..., S, H, D), got rank {q.ndim}")
        if q.ndim not in fns:
            fns[q.ndim] = _build(q.ndim)
        fn, sharding = fns[q.ndim]
        q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
        return fn(q, k, v)

    return apply
