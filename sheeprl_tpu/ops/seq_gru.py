"""Sequence-level fused LayerNorm-GRU: T steps in ONE Pallas kernel.

The per-step fused cell (``ops/pallas_gru.py``) removes the elementwise HBM
round trips inside one GRU step, but a ``lax.scan`` over it still pays, per
time step, a kernel launch plus a re-read of the (H+X, 3H) weight matrix.
For the latency-bound RSSM train scans that launch/stream overhead is most
of the remaining while-loop time (round-4 chip profile of the DV3-S step:
the three while loops were 17 % of 13.9 ms of device time).

This op runs the WHOLE T-step recurrence inside one ``pallas_call``:

* grid = (T,) — TPU grid steps execute sequentially, so the hidden state
  lives in a VMEM scratch carried across iterations;
* the weight matrix's BlockSpec index map is constant, so Mosaic keeps it
  resident in VMEM for the whole sequence (fetched from HBM once);
* the per-step math is the Hafner LayerNorm-GRU of
  ``models.LayerNormGRUCell`` with the Dreamer ``is_first`` reset gate
  folded in (state swaps to ``init_rec`` where ``is_first`` is set), i.e.
  exactly ``RSSM.gru_step_gated`` (reference sheeprl LayerNormGRUCell:331 +
  RSSM.dynamic:390 reset logic).

Training uses a custom VJP whose backward is the *efficient BPTT* form:
everything that can batch over time does — the pre-LN activations are
recomputed from the SAVED hidden states in one (T*B, H+X) @ (H+X, 3H)
matmul, and the weight/input/LN-parameter gradients are single batched
contractions — so the reverse ``lax.scan`` carries only ``dh`` (B, H) and
does one small (B, 3H) @ (3H, H) matmul per step. Compared with
autodiff-through-scan this removes the (H+X, 3H) weight-gradient
accumulator from the backward loop carry and all per-step residual stacking
except the hidden states themselves.

Weights must fit in VMEM (f32: (H+X)*3H*4 bytes; S/M Dreamer sizes do, L/XL
do not) — ``fits_vmem`` gates eligibility and callers fall back to the
per-step path. Lane alignment (H, X, B multiples of 128/8) is padded for.

Status: numerics (forward + gradients) pinned against the pure-scan
reference in ``tests/test_parallel/test_seq_gru.py`` (interpret mode);
wall-clock on a real chip is measured by ``benchmarks/bench_seq_gru.py``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gru_sequence", "gru_sequence_reference", "fits_vmem"]


def fits_vmem(hidden: int, in_dim: int, matmul_dtype=jnp.float32, budget_mb: float = 10.0) -> bool:
    """Can the (H+X, 3H) weight matrix stay VMEM-resident (plus working set)?

    The 10 MB bound is conservative for a v5e (128 MiB of VMEM): asked for a
    described v5e, Mosaic took everything under it (9.8 MB f32 at H=X=640,
    9.4 MB bf16 at H=1024/X=512) and gave mixed answers above it (11.8 MB
    f32 compiled, 20.4 MB f32 ran out of VMEM, 34.6 MB bf16 compiled, XL's
    126 MB did not).  tests/test_ops/test_tpu_compile.py holds the bound to
    what compiles."""
    itemsize = jnp.dtype(matmul_dtype).itemsize
    return (hidden + in_dim) * 3 * hidden * itemsize <= budget_mb * 2**20


def _gate_math(parts: jax.Array, hg: jax.Array, hidden: int) -> jax.Array:
    reset = jax.nn.sigmoid(parts[..., :hidden])
    cand = jnp.tanh(reset * parts[..., hidden : 2 * hidden])
    update = jax.nn.sigmoid(parts[..., 2 * hidden :] - 1.0)
    return update * cand + (1.0 - update) * hg


def _ln(z: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float) -> jax.Array:
    mu = z.mean(-1, keepdims=True)
    var = jnp.maximum((z * z).mean(-1, keepdims=True) - mu * mu, 0.0)
    return (z - mu) * jax.lax.rsqrt(var + eps) * gamma + beta


def gru_sequence_reference(h0, xs, w, gamma, beta, is_first=None, init_rec=None, *, eps=1e-6, matmul_dtype=jnp.float32):
    """Pure lax.scan reference with identical semantics (autodiff-friendly)."""
    hidden = h0.shape[-1]
    if is_first is None:
        is_first = jnp.zeros((*xs.shape[:2], 1), jnp.float32)
    if init_rec is None:
        init_rec = jnp.zeros_like(h0)

    def step(h, inp):
        x, first = inp
        hg = (1.0 - first) * h + first * init_rec.astype(jnp.float32)
        z = jnp.concatenate([hg.astype(matmul_dtype), x.astype(matmul_dtype)], -1) @ w.astype(matmul_dtype)
        parts = _ln(z.astype(jnp.float32), gamma, beta, eps)
        h_new = _gate_math(parts, hg, hidden)
        return h_new, h_new

    _, hs = jax.lax.scan(step, h0.astype(jnp.float32), (xs, is_first.astype(jnp.float32)))
    return hs


def _seq_kernel(x_ref, first_ref, init_ref, h0_ref, w_ref, gamma_ref, beta_ref, out_ref, h_ref, *, eps: float, hidden: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_ref[:] = h0_ref[:]

    first = first_ref[0]  # block (1, B, 1) -> (B, 1) f32
    hg = (1.0 - first) * h_ref[:] + first * init_ref[:]
    inp = jnp.concatenate([hg.astype(x_ref.dtype), x_ref[0]], -1)
    z = jnp.dot(inp, w_ref[:], preferred_element_type=jnp.float32)
    parts = _ln(z, gamma_ref[:], beta_ref[:], eps)
    h_new = _gate_math(parts, hg, hidden)
    h_ref[:] = h_new
    out_ref[0] = h_new


@functools.partial(jax.jit, static_argnames=("eps", "interpret", "matmul_dtype"))
def _gru_sequence_fwd_pallas(h0, xs, w, gamma, beta, is_first, init_rec, *, eps, interpret, matmul_dtype):
    T, b, xdim = xs.shape
    hidden = h0.shape[-1]
    kdim = hidden + xdim

    xs = xs.astype(matmul_dtype)
    w = w.astype(matmul_dtype)
    # pad batch to a sublane multiple; padded rows run harmless math on zeros
    pb = (-b) % 8
    if pb:
        h0 = jnp.pad(h0, ((0, pb), (0, 0)))
        xs = jnp.pad(xs, ((0, 0), (0, pb), (0, 0)))
        is_first = jnp.pad(is_first, ((0, 0), (0, pb), (0, 0)))
        init_rec = jnp.pad(init_rec, ((0, pb), (0, 0)))
    bp = b + pb

    hs = pl.pallas_call(
        functools.partial(_seq_kernel, eps=eps, hidden=hidden),
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, bp, xdim), lambda t: (t, 0, 0)),  # xs
            pl.BlockSpec((1, bp, 1), lambda t: (t, 0, 0)),  # is_first
            pl.BlockSpec((bp, hidden), lambda t: (0, 0)),  # init_rec (resident)
            pl.BlockSpec((bp, hidden), lambda t: (0, 0)),  # h0 (resident)
            pl.BlockSpec((kdim, 3 * hidden), lambda t: (0, 0)),  # w (resident)
            pl.BlockSpec((3 * hidden,), lambda t: (0,)),
            pl.BlockSpec((3 * hidden,), lambda t: (0,)),
        ],
        out_specs=pl.BlockSpec((1, bp, hidden), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, bp, hidden), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bp, hidden), jnp.float32)],
        interpret=interpret,
    )(
        xs.reshape(T, bp, xdim),
        is_first.astype(jnp.float32),
        init_rec.astype(jnp.float32),
        h0.astype(jnp.float32),
        w,
        jnp.asarray(gamma, jnp.float32),
        jnp.asarray(beta, jnp.float32),
    )
    return hs[:, :b]


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def gru_sequence(h0, xs, w, gamma, beta, is_first, init_rec, eps: float = 1e-6, interpret: bool = False, matmul_dtype=jnp.float32):
    """T-step LayerNorm-GRU with is_first reset gating, one Pallas kernel.

    h0: (B, H) f32 initial carry; xs: (T, B, X) projected inputs;
    w: (H+X, 3H); gamma/beta: (3H,); is_first: (T, B, 1);
    init_rec: (B, H) learned reset state. Returns hs (T, B, H) f32.
    """
    return _gru_sequence_fwd_pallas(
        h0, xs, w, gamma, beta, is_first, init_rec,
        eps=eps, interpret=interpret, matmul_dtype=matmul_dtype,
    )


def _fwd(h0, xs, w, gamma, beta, is_first, init_rec, eps, interpret, matmul_dtype):
    hs = _gru_sequence_fwd_pallas(
        h0, xs, w, gamma, beta, is_first, init_rec,
        eps=eps, interpret=interpret, matmul_dtype=matmul_dtype,
    )
    return hs, (h0, xs, w, gamma, beta, is_first, init_rec, hs)


def _bwd(eps, interpret, matmul_dtype, res, g):
    """Efficient BPTT: batched recompute from saved states; the reverse scan
    carries only dh and does one (B, 3H) @ (3H, H) matmul per step."""
    h0, xs, w, gamma, beta, is_first, init_rec, hs = res
    T, b, xdim = xs.shape
    hidden = h0.shape[-1]
    f32 = jnp.float32

    h_prev = jnp.concatenate([h0[None].astype(f32), hs[:-1]], 0)  # (T, B, H)
    hg = (1.0 - is_first) * h_prev + is_first * init_rec.astype(f32)

    # ---- batched recompute of every step's pre-LN activations and gates
    inp = jnp.concatenate([hg.astype(matmul_dtype), xs.astype(matmul_dtype)], -1)
    z = (inp @ w.astype(matmul_dtype)).astype(f32)  # (T, B, 3H)
    mu = z.mean(-1, keepdims=True)
    var = jnp.maximum((z * z).mean(-1, keepdims=True) - mu * mu, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    zhat = (z - mu) * inv
    parts = zhat * gamma + beta
    p1, p2, p3 = jnp.split(parts, 3, -1)
    reset = jax.nn.sigmoid(p1)
    cand = jnp.tanh(reset * p2)
    update = jax.nn.sigmoid(p3 - 1.0)

    n3 = 3 * hidden
    w_h = w[:hidden].astype(f32)  # (H, 3H)

    def back_step(dh, inp_t):
        g_t, hg_t, cand_t, update_t, reset_t, p2_t, zhat_t, inv_t, first_t = inp_t
        dh_tot = dh + g_t
        du = (cand_t - hg_t) * dh_tot
        dcand = update_t * dh_tot
        dhg = (1.0 - update_t) * dh_tot
        dp3 = du * update_t * (1.0 - update_t)
        dtanh = dcand * (1.0 - cand_t * cand_t)
        dp2 = dtanh * reset_t
        dreset = dtanh * p2_t
        dp1 = dreset * reset_t * (1.0 - reset_t)
        dparts = jnp.concatenate([dp1, dp2, dp3], -1)  # (B, 3H)
        # LayerNorm backward (per row over 3H; stats are saved, not carried)
        dzhat = dparts * gamma
        dz = inv_t * (
            dzhat
            - dzhat.mean(-1, keepdims=True)
            - zhat_t * (dzhat * zhat_t).mean(-1, keepdims=True)
        )
        # into the carry: through the matmul's h-side AND the convex update
        dhg = dhg + dz @ w_h.T
        dh_prev = (1.0 - first_t) * dhg
        return dh_prev, (dz, dparts, dhg)

    seq = (g.astype(f32), hg, cand, update, reset, p2, zhat, inv, is_first.astype(f32))
    dh0, (dzs, dpartss, dhgs) = jax.lax.scan(
        back_step, jnp.zeros_like(h0, f32), seq, reverse=True
    )

    # ---- everything else batches over (T*B): ONE contraction each
    inp2 = jnp.concatenate([hg, xs.astype(f32)], -1).reshape(T * b, hidden + xdim)
    dz2 = dzs.reshape(T * b, n3)
    dw = (inp2.T @ dz2).astype(w.dtype)  # (H+X, 3H)
    dxs = (dz2 @ w[hidden:].astype(f32).T).reshape(T, b, xdim).astype(xs.dtype)
    dgamma = (dpartss.reshape(T * b, n3) * zhat.reshape(T * b, n3)).sum(0)
    dbeta = dpartss.reshape(T * b, n3).sum(0)
    dinit = (is_first * dhgs).sum(0).astype(init_rec.dtype)  # (B, H)
    dfirst = ((init_rec.astype(f32) - h_prev) * dhgs).sum(-1, keepdims=True)
    return (
        dh0.astype(h0.dtype),
        dxs,
        dw,
        dgamma.astype(gamma.dtype),
        dbeta.astype(beta.dtype),
        dfirst.astype(is_first.dtype),
        dinit,
    )


gru_sequence.defvjp(_fwd, _bwd)
