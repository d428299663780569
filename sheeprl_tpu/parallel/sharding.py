"""Canonical mesh-axis layout for pod-scale sharded training.

The runtime used to build a one-axis ``("data",)`` mesh; DP and FSDP both
laid everything over that single axis, which works but cannot express the
layouts a pod actually wants (batch over ICI, params over a separate
ZeRO axis, and eventually tensor axes).  This module owns the 2-D
``Mesh(..., ("data", "fsdp"))`` vocabulary (SNIPPETS.md [2]'s
``SpecLayout`` idea, PAPER.md §5.8's ``jax.lax`` collectives as the
NCCL-equivalent):

- the **batch** (a rollout's env columns, a replay draw's rows) is always
  sharded over BOTH axes flattened — every device is a data-parallel
  worker regardless of how the pod is split;
- **params/opt-state** are replicated under ``dp`` and sharded over the
  ``fsdp`` axis (largest divisible dim, ZeRO-style) under
  ``strategy=fsdp``;
- ``fabric.mesh_shape`` picks the split: ``auto`` reproduces the pre-2-D
  behavior bit-exactly (all devices on ``data`` for dp, all on ``fsdp``
  for fsdp — either way every device holds a batch shard), an explicit
  ``[d, f]`` (or ``"dxf"`` string) lays a pod as d-way data x f-way
  param sharding.

Everything here is pure layout bookkeeping: no jax dispatches happen at
import or construction time, so the module is free on the hot import
path.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "DATA_AXIS",
    "FSDP_AXIS",
    "BATCH_AXES",
    "ShardingLayout",
    "parse_mesh_shape",
    "shard_dim_for",
    "shard_slice",
]

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
# the flattened batch axes: batch dims shard over data x fsdp together,
# so world_size (the number of batch shards) is always every device
BATCH_AXES = (DATA_AXIS, FSDP_AXIS)


def shard_dim_for(shape: Sequence[int], fsdp_size: int) -> Optional[int]:
    """The dim the fsdp axis shards for a leaf of ``shape``: its LARGEST
    dim divisible by ``fsdp_size`` (picking the first divisible dim can
    hit a small leading axis — e.g. a conv kernel's spatial dim —
    producing tiny shards and halo all-gathers); None when the leaf stays
    replicated (``fsdp_size`` 1, scalars, indivisible shapes).

    Pure and deterministic in (shape, fsdp_size) alone — the SAME rule
    drives :meth:`ShardingLayout.param_spec` (live placement) and the
    sharded checkpoint plane (resilience/sharded_ckpt.py), so a shard
    file written under one mesh maps onto any other mesh's layout
    without recording per-leaf placement decisions."""
    f = int(fsdp_size)
    shape = tuple(int(s) for s in shape)
    if f <= 1:
        return None
    return max(
        (d for d, s in enumerate(shape) if s >= f and s % f == 0),
        key=lambda d: shape[d],
        default=None,
    )


def shard_slice(shape: Sequence[int], dim: int, n_shards: int, rank: int) -> Tuple[slice, ...]:
    """Index tuple selecting shard ``rank`` of ``n_shards`` equal splits
    along ``dim`` of a leaf of ``shape`` (the slice a device on fsdp
    coordinate ``rank`` owns under :func:`shard_dim_for`'s layout)."""
    size = int(shape[dim])
    if size % int(n_shards):
        raise ValueError(f"dim {dim} of {tuple(shape)} does not split into {n_shards} shards")
    per = size // int(n_shards)
    idx = [slice(None)] * len(shape)
    idx[dim] = slice(int(rank) * per, (int(rank) + 1) * per)
    return tuple(idx)


def parse_mesh_shape(spec: Any, n_devices: int, strategy: str = "auto") -> Tuple[int, int]:
    """Resolve ``fabric.mesh_shape`` to ``(data, fsdp)`` axis sizes.

    ``auto`` (default) reproduces the pre-2-D-mesh layouts exactly:
    every device on ``data`` for dp/auto strategies, every device on
    ``fsdp`` for ``strategy=fsdp`` (the old code sharded params over the
    same axis the batch used — ZeRO — which in the 2-D vocabulary IS a
    ``(1, n)`` mesh).  Explicit shapes accept a 2-sequence ``[d, f]`` or
    a string ``"4x2"`` / ``"4,2"``; one entry may be ``-1`` (inferred).
    """
    n = int(n_devices)
    if spec is None or (isinstance(spec, str) and spec.strip().lower() in ("", "auto")):
        return (1, n) if strategy == "fsdp" else (n, 1)
    if isinstance(spec, str):
        parts = [p for p in spec.replace("x", ",").split(",") if p.strip()]
    else:
        try:
            parts = list(spec)
        except TypeError:
            raise ValueError(f"mesh_shape must be 'auto', 'DxF', or a [data, fsdp] pair; got {spec!r}")
    if len(parts) != 2:
        raise ValueError(f"mesh_shape needs exactly two entries (data, fsdp); got {spec!r}")
    d, f = (int(p) for p in parts)
    if d == -1 and f == -1:
        raise ValueError("mesh_shape may infer (-1) at most one axis")
    if d == -1:
        d = n // f if f > 0 else 0
    if f == -1:
        f = n // d if d > 0 else 0
    if d <= 0 or f <= 0 or d * f != n:
        raise ValueError(
            f"mesh_shape {spec!r} does not tile {n} device(s): data({d}) x fsdp({f}) != {n}"
        )
    return d, f


def build_mesh(devices: Sequence[Any], mesh_shape: Any, strategy: str = "auto") -> Mesh:
    """The 2-D device mesh every runtime owns (see :func:`parse_mesh_shape`)."""
    d, f = parse_mesh_shape(mesh_shape, len(devices), strategy)
    return Mesh(np.asarray(devices).reshape(d, f), axis_names=BATCH_AXES)


class ShardingLayout:
    """Canonical ``PartitionSpec``s for one mesh (SNIPPETS.md [2] style).

    One instance rides on :class:`~sheeprl_tpu.parallel.MeshRuntime` as
    ``runtime.layout`` — the single source of truth the train steps, the
    replay cache, and the telemetry all read, so the axis vocabulary
    cannot drift per subsystem.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    # ------------------------------------------------------------- sizes
    @property
    def data_size(self) -> int:
        return int(self.mesh.shape[DATA_AXIS])

    @property
    def fsdp_size(self) -> int:
        return int(self.mesh.shape[FSDP_AXIS])

    @property
    def n_shards(self) -> int:
        """Batch shard count — every device, regardless of the d x f split."""
        return self.data_size * self.fsdp_size

    # ------------------------------------------------------------- specs
    def batch_spec(self, axis: int = 0) -> P:
        """Batch dim ``axis`` sharded over the flattened (data, fsdp) axes."""
        return P(*([None] * axis + [BATCH_AXES]))

    def batch_sharding(self, axis: int = 0) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec(axis))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def param_spec(self, shape: Sequence[int]) -> P:
        """ZeRO layout for one leaf: :func:`shard_dim_for`'s pick sharded
        over ``fsdp``; scalars and indivisible leaves stay replicated.
        The dim rule lives in the module-level helper so the sharded
        checkpoint plane applies the identical rule without a mesh."""
        shape = tuple(shape)
        best = shard_dim_for(shape, self.fsdp_size)
        if best is None:
            return P()
        spec = [None] * len(shape)
        spec[best] = FSDP_AXIS
        return P(*spec)

    def param_sharding(self, leaf: Any) -> NamedSharding:
        return NamedSharding(self.mesh, self.param_spec(getattr(leaf, "shape", ())))

    # ------------------------------------------------- in-jit constraints
    def constrain_state(self, tree: Any, fsdp: bool) -> Any:
        """``with_sharding_constraint`` at the update boundary: pin every
        float/array leaf of a state tree (params, opt-state, moments) to
        its canonical layout — the fsdp ZeRO layout when ``fsdp``, else
        replicated.  This is what makes the mesh layout EXPLICIT in the
        lowered program (GSPMD otherwise may pick a different resolution
        per output, and the reduce-scatter/all-gather structure becomes an
        accident of propagation).  Only call inside jit."""
        import jax

        def leaf_constraint(x):
            if not hasattr(x, "shape"):
                return x
            s = self.param_sharding(x) if fsdp else self.replicated
            return jax.lax.with_sharding_constraint(x, s)

        return jax.tree_util.tree_map(leaf_constraint, tree)

    def constrain_batch(self, tree: Any, axis: int = 0) -> Any:
        """Pin a batch pytree to the flattened batch-axes layout (in-jit)."""
        import jax

        sharding = self.batch_sharding(axis)
        return jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, sharding) if hasattr(x, "shape") else x,
            tree,
        )

    def flat_rank(self):
        """Flattened device index inside a ``shard_map`` body: the batch
        shard this device owns, row-major over (data, fsdp) — matches the
        device order :meth:`batch_spec` splits a batch in."""
        import jax

        return jax.lax.axis_index(BATCH_AXES)

    # ------------------------------------------------------------- telemetry
    def param_shard_bytes(self, tree: Any) -> int:
        """Per-device bytes of the fsdp-sharded param tree (telemetry:
        the ZeRO memory win actually achieved, given indivisible leaves
        stay replicated)."""
        import jax

        f = self.fsdp_size
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            shape = getattr(leaf, "shape", None)
            if shape is None:
                continue
            n = int(np.prod(shape, dtype=np.int64) or 1)
            itemsize = np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
            sharded = self.param_spec(shape) != P()
            total += (n // f if sharded else n) * itemsize
        return int(total)

    def describe(self) -> Dict[str, Any]:
        """Telemetry stub: axis names/sizes for the ``mesh`` key."""
        return {
            "axes": {DATA_AXIS: self.data_size, FSDP_AXIS: self.fsdp_size},
            "devices": self.n_shards,
        }


def collective_bytes_estimate(compiled: Any) -> Optional[float]:
    """Best-effort per-update cross-device traffic estimate from XLA's
    ``Compiled.cost_analysis()`` (the ``bytes accessed`` breakdown carries
    operand traffic; collective-specific keys exist only on some
    backends).  Returns None when the backend exposes nothing usable —
    callers must treat this as advisory telemetry, never a gate."""
    try:
        costs = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(costs, (list, tuple)):
        costs = costs[0] if costs else None
    if not isinstance(costs, dict):
        return None
    # backend-dependent key spellings: TPU exposes dedicated cross-core /
    # network counters; CPU/GPU report only the aggregate operand traffic
    # ("bytes accessed"), which upper-bounds the collective term
    for key in (
        "bytes accessed cross-core",
        "network bytes accessed",
        "bytes accessed output",
        "bytes accessedout{}",
        "bytes accessed",
    ):
        if key in costs:
            try:
                return float(costs[key])
            except (TypeError, ValueError):
                continue
    return None
