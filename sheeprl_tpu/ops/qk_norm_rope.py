"""The per-head RMSNorm of queries or keys and their rotate-half rotary as one
op with a backward of its own: what grouped-query attention does to q and k
between their projections and the attention kernel (``models/sdar_moe.py``,
``models/afmoe.py``; its plain form is ``rope(rms_norm(x, g, eps), pos,
theta)``, which stays the reference).

The op reads the projection's output in the dtype the product returned and
writes its result in the model's dtype (SDAR hands it float32 products,
Trinity bf16 ones; both write bf16 under ``bf16-mixed``); the norm's
statistics, the gain's product and the rotation run in float32 inside the
kernel, so no float32 copy of its result or of the norm's intermediates lives
in HBM.  Forward: one read of ``x``, one write of the result.  Backward: one
read of ``x`` and of the cotangent, one write of ``dx`` (rounded to the
result's dtype), and the gain's gradient from partial sums a grid step; the
statistics are recomputed, not stored.  The
rotation reads a cos table and a sin table built once a call, (…, N, D) and
shared by every head, with rotate-half's sign folded into the sin table, so
that rotate-half is a lane roll by ``D / 2``.

The kernel lays a tile of ``_tile_rows`` positions of one sequence over every
head at once, each head a lane-aligned slice of the row.  A sequence shorter
than ``TOKEN_TILE`` positions (the collectors' cached passes: a block of 4, or
one token) or heads that do not fill whole lane tiles take the composed form,
the plain ``jnp`` chain, which XLA fuses into its neighbours: a kernel launch
per layer and pass would cost such a pass more than it saves.

Off a TPU the kernel runs only through Pallas' interpreter (``interpret=True``:
the CPU tests).  Every shape the op is built for is recorded once in
``ENGAGED`` (``take_engaged``) and printed to stderr.
"""

from __future__ import annotations

import functools
import math
import sys
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
TOKEN_TILE = 128  # the fewest positions a sequence needs for the fused form: one tile of rows
_TILE_BYTES = 1 << 20  # a tile's input block above the floor of 128 rows (double-buffered with the others in VMEM)
_MAX_ROWS = 1024
ENGAGED: List[dict] = []  # one record a shape the op was built for in this process (``take_engaged``)
_SEEN: set = set()


def take_engaged() -> List[dict]:
    """The records made since the last call, one a shape: ``form`` (``fused`` or ``composed``), the
    leading ``batch``, ``tokens`` a sequence, ``heads``, ``head_dim``, ``rotate``, ``dtype`` in and ``out``."""
    taken, ENGAGED[:] = list(ENGAGED), []
    return taken


def _record(form: str, x: jax.Array, rotate: bool, dtype) -> None:
    *lead, n, heads, d = x.shape
    record = {"form": form, "batch": math.prod(lead), "tokens": n, "heads": heads, "head_dim": d, "rotate": rotate,
              "dtype": jnp.dtype(x.dtype).name, "out": dtype.name}
    key = tuple(record.values())
    if key not in _SEEN:
        _SEEN.add(key)
        ENGAGED.append(record)
        print(f"Q/K norm and rotary: {record}", file=sys.stderr, flush=True)


def _tile_rows(x3: jax.Array) -> int:
    """Rows of a tile of ``x3`` (B, N, H x D): 128, doubled up to ``_MAX_ROWS`` while the input block stays
    within ``_TILE_BYTES``, and no more than a sequence holds."""
    n, row_bytes = x3.shape[1], x3.shape[2] * x3.dtype.itemsize
    rows = _LANES
    while rows * 2 <= _MAX_ROWS and rows * 2 * row_bytes <= _TILE_BYTES:
        rows *= 2
    return min(rows, n // _LANES * _LANES)


def tables(pos: jax.Array, d: int, theta: float):
    """cos and signed sin, (..., N, D) float32, for positions (..., N): ``x * cos + roll(x, D / 2) * sin``
    is rotate-half's ``x * cos + concat(-x2, x1) * sin``."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([cos, cos], -1), jnp.concatenate([-sin, sin], -1)


def _norm(x, eps):
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r, r


def _fwd_kernel(*refs, heads: int, d: int, eps: float, rotate: bool, **_):
    if rotate:
        x_ref, g_ref, cos_ref, sin_ref, y_ref = refs
        cos, sin = cos_ref[0], sin_ref[0]
    else:
        x_ref, g_ref, y_ref = refs
    g = g_ref[...]
    for h in range(heads):
        lanes = slice(h * d, (h + 1) * d)
        u = _norm(x_ref[0, :, lanes].astype(jnp.float32), eps)[0] * g
        if rotate:
            u = u * cos + pltpu.roll(u, d // 2, 1) * sin
        y_ref[0, :, lanes] = u.astype(y_ref.dtype)


def _bwd_kernel(*refs, heads: int, d: int, eps: float, rotate: bool, rows: int, n: int):
    if rotate:
        x_ref, g_ref, cos_ref, sin_ref, dy_ref, dx_ref, dg_ref = refs
        cos, sin = cos_ref[0], sin_ref[0]
    else:
        x_ref, g_ref, dy_ref, dx_ref, dg_ref = refs
    g = g_ref[...]
    dg = jnp.zeros((rows, d), jnp.float32)
    for h in range(heads):
        lanes = slice(h * d, (h + 1) * d)
        nx, r = _norm(x_ref[0, :, lanes].astype(jnp.float32), eps)
        du = dy_ref[0, :, lanes].astype(jnp.float32)
        if rotate:  # the rotation's transpose: roll(., D / 2) is its own inverse
            du = du * cos + pltpu.roll(du * sin, d // 2, 1)
        dg = dg + du * nx
        dn = du * g
        dx_ref[0, :, lanes] = (r * (dn - nx * jnp.mean(dn * nx, axis=-1, keepdims=True))).astype(dx_ref.dtype)
    if n % rows:  # the last tile runs past the sequence: its rows there hold whatever the block read
        at = pl.program_id(1) * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, d), 0)
        dg = jnp.where(at < n, dg, 0.0)
    dg_ref[0, 0] = jnp.sum(dg, axis=0, keepdims=True)


# jitted so that the calls of one shape share one lowered kernel (the SDAR update makes 24: q and k, forward,
# rematerialised forward and backward, four blocks); lowering each anew took 1.7 s of every process's set-up
@functools.partial(jax.jit, static_argnums=(0, 1, 6, 7), static_argnames=("eps",))
def _run(name: str, kernel, x3, g, cs, dy, dtype, interpret: bool, **params):
    """``kernel`` over the grid (sequence, tile) of ``x3`` (B, N, H x D): a tile of rows over every head, the
    gain whole, the tables' rows of the tile (of this sequence, or of the one table every sequence shares).
    Forward (``dy`` None): the result, laid as ``x3``, in ``dtype``.  Backward: ``dy`` read as ``x3``; ``dx``
    in ``dtype`` and the gain's partial gradients, one row a grid step."""
    b, n, width = x3.shape
    d, rows = g.shape[-1], _tile_rows(x3)
    row = pl.BlockSpec((1, rows, width), lambda i, j: (i, j, 0))
    ins, args = [row, pl.BlockSpec((1, d), lambda i, j: (0, 0))], [x3, g.reshape(1, d)]
    if cs is not None:
        at = (lambda i, j: (i, j, 0)) if cs[0].shape[0] > 1 else (lambda i, j: (0, j, 0))  # or one for every sequence
        ins, args = ins + [pl.BlockSpec((1, rows, d), at)] * 2, args + list(cs)
    outs, out_specs, aliases = [jax.ShapeDtypeStruct(x3.shape, dtype)], [row], {}
    if dy is not None:  # dx takes dy's buffer: a grid step reads its tile of dy before it writes the same tile of dx
        ins, args, aliases = ins + [row], args + [dy], {len(args): 0}
        outs.append(jax.ShapeDtypeStruct((b, pl.cdiv(n, rows), 1, d), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, 1, d), lambda i, j: (i, j, 0, 0)))
    return pl.pallas_call(
        functools.partial(kernel, heads=width // d, d=d, rotate=cs is not None, rows=rows, n=n, **params),
        name=name, grid=(b, pl.cdiv(n, rows)), in_specs=ins, out_specs=out_specs, out_shape=outs,
        input_output_aliases=aliases, interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused(x3, g, cs, eps: float, dtype, interpret: bool):
    return _run("qk_norm_rope_fwd", _fwd_kernel, x3, g, cs, None, dtype, interpret, eps=eps)[0]


def _fused_fwd(x3, g, cs, eps, dtype, interpret):
    return _fused(x3, g, cs, eps, dtype, interpret), (x3, g, cs)


def _fused_bwd(eps, dtype, interpret, res, dy):
    x3, g, cs = res
    dx, dg = _run("qk_norm_rope_bwd", _bwd_kernel, x3, g, cs, dy.astype(dtype), dtype, interpret, eps=eps)
    # dx is rounded to the result's dtype, as the plain form's cast rounds its cotangent
    return dx.astype(x3.dtype), dg.sum((0, 1, 2)).astype(g.dtype), None


_fused.defvjp(_fused_fwd, _fused_bwd)


def qk_norm_rope(x: jax.Array, g: jax.Array, eps: float, pos: Optional[jax.Array] = None, theta: float = 1e4,
                 dtype=None, interpret: bool = False) -> jax.Array:
    """``rope(rms_norm(x, g, eps), pos, theta)`` in ``dtype`` (default ``x``'s), or the norm alone where
    ``pos`` is None (a layer without positional embedding).  ``x``: (..., N, H, D), the projection as the
    product returned it; ``g``: (D,) float32; ``pos``: (..., N) a position a token, or (N,) shared by every
    sequence.  Fused where a sequence holds ``TOKEN_TILE`` positions or more and a head fills whole lane
    tiles; composed otherwise."""
    *lead, n, heads, d = x.shape
    rotate, dtype = pos is not None, jnp.dtype(dtype or x.dtype)
    if n < TOKEN_TILE or d % _LANES:
        from sheeprl_tpu.models.sdar_moe import rms_norm, rope  # the reference form (that module imports this one)

        _record("composed", x, rotate, dtype)
        y = rms_norm(x, g, eps)
        return (rope(y, pos, theta) if rotate else y).astype(dtype)
    _record("fused", x, rotate, dtype)
    x3 = x.reshape(-1, n, heads * d)
    cs = None
    if rotate:
        cs = tables(pos, d, theta)
        cs = tuple(t.reshape(-1, n, d) for t in cs)
        if cs[0].shape[0] not in (1, x3.shape[0]):
            raise ValueError(f"positions {pos.shape} are neither shared nor one a token of {x.shape[:-2]}")
    return _fused(x3, g, cs, eps, dtype, interpret).reshape(x.shape)
