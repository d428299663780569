"""The gated delta rule, the recurrence of Gated DeltaNet's linear attention
(Yang, Kautz and Hatamizadeh 2024, *Gated Delta Networks*, arXiv:2412.06464;
``models/qwen3_next.py``).  Per value head, with key head ``h // (Hv / Hk)``::

    S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T      S_0 = initial_state (or 0)
    o_t = S_t^T q_t

``S`` is ``d_k x d_v`` a value head; ``g_t <= 0`` is the log of the decay and
``beta_t`` in [0, 1] the write strength.  ``q`` and ``k`` come in already
L2-normed, ``q`` already scaled.

Two forms, chosen by shape:

- ``recurrent``: one token (the collectors' cached passes) is the step above.
- ``chunked``: a sequence is cut into chunks of ``CHUNK`` positions (the last
  padded with positions of no decay and no write, which leave the state as it
  is).  Inside a chunk the delta rule's writes are the WY / UT form: with
  ``Gamma_ij = exp(G_i - G_j)`` for ``j <= i`` (``G`` the decay's cumulative
  sum inside the chunk) and ``A = tril(diag(beta) K K^T * Gamma, -1)``, one
  unit lower-triangular solve gives ``T = (I + A)^-1``, and ``U = T diag(beta) V``
  every position's value, ``W = T diag(beta e^G) K`` its key's read of the
  chunk's starting state.  Then chunk by chunk, the state carried in a scan::

      V' = U - W S,   O = (Q * e^G) S + (Q K^T * Gamma) V',
      S  = e^{G_C} S + (K * e^{G_C - G})^T V'

  which is the recurrence exactly, in another order: no term is dropped.  The
  chunks' parallel parts (the products, the solve) are computed for all chunks
  at once; only the state's passage is sequential.  The gradient is
  differentiation through that scan.

Everything inside runs in float32 at ``highest`` matmul precision (the state
is carried across 128 chunks of an 8,192-position episode).  The key heads are
indexed, never repeated: value heads are laid ``(key head, the value heads
that share it)`` and every product of ``q`` or ``k`` reads the key head once.

Every shape the op is built for is recorded once in ``ENGAGED``
(``take_engaged``, ``telemetry.jsonl``'s ``gdn`` section) and printed to
stderr.
"""

from __future__ import annotations

import sys
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

CHUNK = 64
ENGAGED: List[dict] = []  # one record a shape the op was built for in this process (``take_engaged``)
_SEEN: set = set()


def take_engaged() -> List[dict]:
    """The records made since the last call, one a shape: ``form`` (``chunked`` or ``recurrent``),
    ``chunk``, ``tokens`` a sequence, ``key_heads``, ``value_heads``, ``d_k``, ``d_v`` and the inputs'
    ``dtype``."""
    taken, ENGAGED[:] = list(ENGAGED), []
    return taken


def _record(form: str, q: jax.Array, v: jax.Array) -> None:
    record = {"form": form, "chunk": CHUNK if form == "chunked" else 1, "tokens": q.shape[1], "key_heads": q.shape[2],
              "value_heads": v.shape[2], "d_k": q.shape[3], "d_v": v.shape[3], "dtype": jnp.dtype(q.dtype).name}
    key = tuple(record.values())
    if key not in _SEEN:
        _SEEN.add(key)
        ENGAGED.append(record)
        print(f"Gated delta rule: {record}", file=sys.stderr, flush=True)


def _grouped(v: jax.Array, hk: int) -> jax.Array:
    """(B, N, Hv, ...) -> (B, N, Hk, Hv / Hk, ...): value head ``h`` at (h // rep, h % rep)."""
    b, n, hv = v.shape[:3]
    return v.reshape(b, n, hk, hv // hk, *v.shape[3:])


def _recurrent(q, k, v, g, beta, state):
    """One token: q, k (B, Hk, dk); v (B, Hk, R, dv); g, beta (B, Hk, R); state (B, Hk, R, dk, dv)."""
    state = state * jnp.exp(g)[..., None, None]
    read = jnp.einsum("bhk,bhrkv->bhrv", k, state)
    state = state + jnp.einsum("bhk,bhrv->bhrkv", k, (v - read) * beta[..., None])
    return jnp.einsum("bhk,bhrkv->bhrv", q, state), state


def _chunked(q, k, v, g, beta, state):
    """Whole sequences: q, k (B, N, Hk, dk); v (B, N, Hk, R, dv); g, beta (B, N, Hk, R); state
    (B, Hk, R, dk, dv).  N is a multiple of ``CHUNK``.  Every array is laid chunk-major, the scan's
    axis first, once at the inputs: what the scan reads needs no copy of its own."""
    b, n, hk, dk = q.shape
    r, dv = v.shape[3:]
    c, nc = CHUNK, n // CHUNK
    q, k = (x.reshape(b, nc, c, hk, dk).swapaxes(0, 1) for x in (q, k))  # (nc, B, C, Hk, dk)
    v = v.reshape(b, nc, c, hk, r, dv).swapaxes(0, 1)
    # (nc, B, Hk, R, C): a head's numbers along the chunk's positions
    g, beta = (jnp.moveaxis(x.reshape(b, nc, c, hk, r).swapaxes(0, 1), 2, -1) for x in (g, beta))
    gc = jnp.cumsum(g, axis=-1)
    # log Gamma_ij = G_i - G_j = g_{j+1} + ... + g_i, summed as it stands (a segment sum) rather than as the difference of
    # two cumulative sums, which under strong decay reach -1e3 and would leave float32's noise on every decay and its
    # gradient; 0 on and above the diagonal, so that its exponential is finite everywhere
    lower = jnp.tril(jnp.ones((c, c), bool))
    seg = jnp.cumsum(jnp.where(jnp.tril(lower, -1), g[..., :, None], 0.0), axis=-2)  # (nc, B, Hk, R, C, C)
    gamma = jnp.where(lower, jnp.exp(seg), 0.0)  # 0 above the diagonal
    kk = jnp.einsum("nbihd,nbjhd->nbhij", k, k)
    qk = jnp.einsum("nbihd,nbjhd->nbhij", q, k)
    a = kk[:, :, :, None] * gamma * beta[..., :, None]  # its strict lower triangle is A; the solve reads no more
    # T = (I + A)^-1 by one unit lower-triangular solve; U = T diag(beta) V and W = T diag(beta e^G) K
    t = jax.scipy.linalg.solve_triangular(a, jnp.broadcast_to(jnp.eye(c), a.shape), lower=True, unit_diagonal=True)
    u = jnp.einsum("nbhrij,nbjhrv->nbhriv", t * beta[..., None, :], v)
    w = jnp.einsum("nbhrij,nbjhd->nbhrid", t * (beta * jnp.exp(gc))[..., None, :], k)
    attn = qk[:, :, :, None] * gamma  # (nc, B, Hk, R, C, C), the diagonal included
    g_end = gc[..., -1]  # (nc, B, Hk, R)
    to_end = jnp.exp(seg[..., -1, :])  # a position's decay to the chunk's end: the segment sum's last row

    def chunk(state, xs):
        q_c, k_c, u_c, w_c, attn_c, e_c, end_c, g_end_c = xs
        v_new = u_c - jnp.einsum("bhrck,bhrkv->bhrcv", w_c, state)
        o = (jnp.einsum("bchk,bhrkv->bhrcv", q_c, state) * e_c[..., None]
             + jnp.einsum("bhrij,bhrjv->bhriv", attn_c, v_new))
        state = (state * jnp.exp(g_end_c)[..., None, None]
                 + jnp.einsum("bchk,bhrcv->bhrkv", k_c, v_new * end_c[..., None]))
        return state, o

    state, o = jax.lax.scan(chunk, state, (q, k, u, w, attn, jnp.exp(gc), to_end, g_end))  # o: (nc, B, Hk, R, C, dv)
    return o.transpose(1, 0, 4, 2, 3, 5).reshape(b, n, hk, r, dv), state


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
                     initial_state: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """q, k: (B, N, Hk, dk), L2-normed, q scaled; v: (B, N, Hv, dv) with ``Hv`` a multiple of ``Hk``;
    g (the log of the decay, <= 0) and beta: (B, N, Hv); ``initial_state``: (B, Hv, dk, dv) or None
    (zeros).  Returns the outputs (B, N, Hv, dv) and the final state (B, Hv, dk, dv), float32.  One token
    takes the recurrent form, a longer sequence the chunked one."""
    b, n, hk, dk = q.shape
    hv, dv = v.shape[2:]
    if k.shape != q.shape or v.shape[:2] != (b, n) or g.shape != (b, n, hv) or beta.shape != (b, n, hv):
        raise ValueError(f"shapes q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, beta {beta.shape} do not agree")
    if hv % hk:
        raise ValueError(f"{hv} value heads cannot share {hk} key heads")
    _record("recurrent" if n == 1 else "chunked", q, v)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    q, k = f32(q), f32(k)
    v, g, beta = (_grouped(f32(x), hk) for x in (v, g, beta))
    state = (jnp.zeros((b, hv, dk, dv), jnp.float32) if initial_state is None else f32(initial_state))
    state = state.reshape(b, hk, hv // hk, dk, dv)
    with jax.default_matmul_precision("highest"):
        if n == 1:
            o, state = _recurrent(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
            o = o[:, None]
        else:
            pad = -n % CHUNK  # positions of no decay and no write: the state passes them unchanged
            padded = lambda x: jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))  # noqa: E731
            o, state = _chunked(*(padded(x) for x in (q, k, v, g, beta)), state)
            o = o[:, :n]
    return o.reshape(b, n, hv, dv), state.reshape(b, hv, dk, dv)
