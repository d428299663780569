"""Device-resident binary sum-tree for proportional prioritized replay.

Prioritized Experience Replay (Schaul et al., 2016) samples transition i
with probability p_i^α / Σ p^α and corrects the induced bias with
importance-sampling weights w_i = (N · P(i))^-β.  The classical host
implementation is a mutable array-backed segment tree; here the tree is a
single flat ``jax.Array`` living on the training device next to the
``DeviceReplayCache`` rings, so sampling stays inside the jitted sample
step — an O(log n) vectorized descent, no host round-trips — exactly the
property that keeps the replay feed off the host.

Layout: 1-based heap in a ``(2·P,)`` float32 array where ``P`` is the
leaf count padded to a power of two; index 0 is unused, the root (total
mass) sits at 1, leaves at ``[P, 2·P)``.  All kernels take the depth
``log2(P)`` statically, so the per-level loops unroll into a fixed
gather/scatter chain XLA fuses well.

Batched updates with duplicate leaf indices are safe: the leaf scatter
picks one writer per duplicate (callers that can produce duplicates —
``update_priorities`` with a batch that sampled the same transition
twice — pass equal values per duplicate within one call), and parents
are rebuilt bottom-up from the final child values, so the tree is always
internally consistent.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PriorityTree",
    "ShardedPriorityTree",
    "per_beta_schedule",
    "priority_from_td",
    "shard_proportional_draw",
]


def priority_from_td(td_abs, alpha: float, eps: float):
    """Schaul proportional priority: (|δ| + ε)^α (works on jnp or np)."""
    return (abs(td_abs) + eps) ** alpha


def per_beta_schedule(beta0: float, beta_end: float, total_steps: int):
    """Linear β annealing (Schaul §3.4: anneal the IS correction toward 1
    as training converges).  Returns ``step -> β`` on host floats."""
    beta0 = float(beta0)
    beta_end = float(beta_end)
    span = max(int(total_steps), 1)

    def beta(step: int) -> float:
        frac = min(max(float(step) / span, 0.0), 1.0)
        return beta0 + (beta_end - beta0) * frac

    return beta


def _write_impl(tree, leaf_idx, values, active, depth):
    """Set ``leaf_idx`` to ``values`` where ``active``, keep the rest, and
    rebuild the touched ancestor paths bottom-up.

    Inactive entries are REDIRECTED to heap slot 0 (unused by the 1-based
    layout) instead of writing their current value back: a masked-out
    duplicate of an active leaf would otherwise win the one-writer-per-
    duplicate scatter and silently drop the active write — exactly what
    the sharded tree's per-shard ownership masks produce (every global
    batch of leaves contains each local leaf once per shard, active on
    exactly one)."""
    p = 1 << depth
    node = jnp.where(active, leaf_idx.astype(jnp.int32) + p, 0)
    tree = tree.at[node].set(jnp.where(active, values.astype(tree.dtype), tree[0]))
    for _ in range(depth):
        node = node >> 1  # inactive chains stay parked at slot 0
        tree = tree.at[node].set(tree[2 * node] + tree[2 * node + 1])
    return tree


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("depth",))
def _tree_write(tree, leaf_idx, values, active, *, depth):
    return _write_impl(tree, leaf_idx, values, active, depth)


@functools.partial(jax.jit, static_argnames=("depth",))
def _tree_zeroed(tree, leaf_idx, active, *, depth):
    """Functional copy with ``leaf_idx`` zeroed where ``active`` — the
    sampling-time exclusion mask (write-head rows whose next-obs is stale,
    ring cells too close to the head to start a full sequence).  The
    stored tree is untouched."""
    return _write_impl(tree, leaf_idx, jnp.zeros(leaf_idx.shape, tree.dtype), active, depth)


def _descend(tree, u, depth):
    """Vectorized root-to-leaf descent shared by the single-device sampler
    and the per-shard bodies of the sharded one: ``u`` in [0, total mass)
    -> (leaf index, leaf mass)."""
    p = 1 << depth
    node = jnp.ones(u.shape, jnp.int32)
    for _ in range(depth):
        left = tree[2 * node]
        go_right = u >= left
        u = jnp.where(go_right, u - left, u)
        node = 2 * node + go_right.astype(jnp.int32)
    return node - p, tree[node]


def _tree_zeroed_local(tree, leaf_idx, depth):
    """Raw (un-jitted) functional zeroing for use INSIDE shard_map bodies:
    same semantics as :func:`_tree_zeroed` on a shard-local sub-tree."""
    leaf_idx = jnp.asarray(leaf_idx, jnp.int32)
    return _write_impl(
        tree, leaf_idx, jnp.zeros(leaf_idx.shape, tree.dtype), jnp.ones(leaf_idx.shape, bool), depth
    )


@functools.partial(jax.jit, static_argnames=("n", "depth"))
def _tree_sample(tree, key, beta, count, *, n, depth):
    """Draw ``n`` leaves proportional to priority + their IS weights.

    ``count`` is the number of live transitions N in the IS correction
    w_i = (N · P(i))^-β, normalized by the batch max (Schaul §3.4) so
    weights only ever scale losses DOWN.
    """
    total = tree[1]
    u = jax.random.uniform(key, (n,)) * total
    leaf, mass = _descend(tree, u, depth)
    # float-rounding guard: a draw can skid into a zero-mass leaf at a
    # subtree boundary; fold it onto the heaviest neighbor direction by
    # clamping the probability floor instead of resampling (probability
    # ~ulp, bias unmeasurable, and the kernel stays branch-free)
    probs = jnp.maximum(mass, jnp.finfo(tree.dtype).tiny) / jnp.maximum(total, jnp.finfo(tree.dtype).tiny)
    w = (jnp.maximum(count.astype(tree.dtype), 1.0) * probs) ** (-beta)
    w = w / jnp.max(w)
    return leaf, w


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("depth",))
def _tree_update(tree, max_p, leaf_idx, priorities, active, *, depth):
    new_max = jnp.maximum(max_p, jnp.max(jnp.where(active, priorities, 0.0)))
    tree = _write_impl(tree, leaf_idx, priorities, active, depth)
    return tree, new_max


class PriorityTree:
    """Handle owning the device sum-tree + the running max priority.

    ``n_leaves`` is the flat transition-cell count (the cache maps
    ``(row, env) -> row * n_envs + env``).  ``max_priority`` stays a
    device scalar: seeding appends and folding in TD updates never sync
    to the host.
    """

    def __init__(
        self,
        n_leaves: int,
        *,
        alpha: float = 0.6,
        eps: float = 1e-6,
        device=None,
        initial_priority: float = 1.0,
    ):
        if n_leaves <= 0:
            raise ValueError(f"n_leaves must be positive, got {n_leaves}")
        self.n_leaves = int(n_leaves)
        self.alpha = float(alpha)
        self.eps = float(eps)
        self.depth = max(int(self.n_leaves - 1).bit_length(), 1)
        self._device = device
        with jax.default_device(device) if device is not None else _null():
            self.tree = jnp.zeros(2 << self.depth, dtype=jnp.float32)
            self.max_priority = jnp.asarray(float(initial_priority), dtype=jnp.float32)

    # ------------------------------------------------------------- write
    def _write_tree(self, leaf_idx, values, active):
        return _tree_write(self.tree, leaf_idx, values, active, depth=self.depth)

    def seed_max(self, leaf_idx, active) -> None:
        """Priority-seeded insert: new cells enter at the running max
        priority so every transition is trained on at least once before
        its priority can decay (Schaul §3.3 'new transitions arrive at
        maximal priority')."""
        leaf_idx = jnp.asarray(leaf_idx, jnp.int32)
        vals = jnp.broadcast_to(self.max_priority, leaf_idx.shape)
        self.tree = self._write_tree(leaf_idx, vals, jnp.asarray(active))

    def update(self, leaf_idx, td_abs, active=None) -> None:
        """TD-error feedback from the train step: p = (|δ| + ε)^α."""
        leaf_idx = jnp.asarray(leaf_idx, jnp.int32)
        if active is None:
            active = jnp.ones(leaf_idx.shape, bool)
        pri = priority_from_td(jnp.asarray(td_abs, jnp.float32).reshape(leaf_idx.shape), self.alpha, self.eps)
        self.tree, self.max_priority = _tree_update(
            self.tree, self.max_priority, leaf_idx, pri, jnp.asarray(active), depth=self.depth
        )

    def scale(self, leaf_idx, factor: float) -> None:
        """Multiply the priorities at ``leaf_idx`` by ``factor`` (duplicate
        indices scale once — gather-then-write).  Used for decay-on-sample
        recency bias when no TD signal drives the priorities."""
        leaf_idx = jnp.asarray(leaf_idx, jnp.int32).reshape(-1)
        vals = self.priorities(leaf_idx) * jnp.float32(factor)
        self.tree = self._write_tree(leaf_idx, vals, jnp.ones(leaf_idx.shape, bool))

    def set_priorities(self, leaf_idx, priorities, active=None) -> None:
        """Raw priority write (restore path / tests)."""
        leaf_idx = jnp.asarray(leaf_idx, jnp.int32)
        if active is None:
            active = jnp.ones(leaf_idx.shape, bool)
        self.tree = self._write_tree(
            leaf_idx, jnp.asarray(priorities, jnp.float32), jnp.asarray(active)
        )

    # ------------------------------------------------------------- read
    def sample(
        self, key, n: int, *, beta: float, count, exclude_idx=None, exclude_active=None
    ) -> Tuple[jax.Array, jax.Array]:
        """Proportional draw of ``n`` leaves (+ β-corrected IS weights).

        ``exclude_idx``/``exclude_active`` zero those cells in a
        functional copy first — the stored priorities survive (used for
        the stale-next-obs head row and invalid sequence starts)."""
        tree = self.tree
        if exclude_idx is not None:
            ex = jnp.asarray(exclude_idx, jnp.int32)
            act = (
                jnp.asarray(exclude_active)
                if exclude_active is not None
                else jnp.ones(ex.shape, bool)
            )
            tree = _tree_zeroed(tree, ex, act, depth=self.depth)
        return _tree_sample(
            tree,
            jnp.asarray(key),
            jnp.asarray(beta, jnp.float32),
            jnp.asarray(count, jnp.float32),
            n=int(n),
            depth=self.depth,
        )

    def priorities(self, leaf_idx) -> jax.Array:
        leaf = jnp.asarray(leaf_idx, jnp.int32) + (1 << self.depth)
        return self.tree[leaf]

    @property
    def total(self) -> float:
        return float(self.tree[1])

    # ------------------------------------------------------- checkpoint
    def state_dict(self) -> dict:
        """Leaf priorities + running max as host numpy (rides the
        CheckpointManager snapshot; internal nodes are derived state)."""
        p = 1 << self.depth
        return {
            "leaves": np.asarray(self.tree[p : p + self.n_leaves]),
            "max_priority": np.asarray(self.max_priority),
            "alpha": self.alpha,
            "eps": self.eps,
        }

    def load_state_dict(self, state: dict) -> None:
        leaves = np.asarray(state["leaves"], np.float32)
        if leaves.shape[0] != self.n_leaves:
            raise ValueError(
                f"priority state has {leaves.shape[0]} leaves, tree expects {self.n_leaves}"
            )
        p = 1 << self.depth
        full = np.zeros(2 << self.depth, np.float32)
        full[p : p + self.n_leaves] = leaves
        # rebuild internal nodes host-side in one pass (resume cadence only)
        for node in range(p - 1, 0, -1):
            full[node] = full[2 * node] + full[2 * node + 1]
        with jax.default_device(self._device) if self._device is not None else _null():
            self.tree = jnp.asarray(full)
            self.max_priority = jnp.asarray(float(state["max_priority"]), jnp.float32)


def _null():
    import contextlib

    return contextlib.nullcontext()


# --------------------------------------------------------------------- sharded
def shard_proportional_draw(
    tree,
    key,
    rank,
    n_shards,
    axes,
    *,
    n,
    depth,
):
    """Globally-proportional draw from per-shard sub-trees, callable ONLY
    inside a ``shard_map`` body (it issues collectives over ``axes``).

    Conceptually the global mass space is the concatenation of every
    shard's sub-tree mass; the single cross-shard reduction is ONE
    ``psum`` assembling the per-shard total masses (the scalar vector all
    shards need to place their interval in the global CDF).  Every shard
    then draws the SAME ``n`` uniforms (the key is deliberately not
    rank-folded), descends its own sub-tree for all of them, and owns
    exactly the draws whose ``u`` falls inside its mass interval — so
    each global draw has exactly one owner and the aggregate marginals
    are IDENTICAL to a single global sum-tree's (the parity property the
    multi-device PER tests pin).

    Returns ``(local_leaf, mass, own, total)``: the shard-local leaf and
    its mass for ALL n draws (garbage where ``own`` is False — mask
    before any cross-shard assembly), the ownership mask, and the global
    total mass (replicated).  Sampling exclusions are the caller's: it
    pre-zeroes a functional sub-tree copy."""
    m_local = tree[1]
    masses = jax.lax.psum(
        jnp.zeros((n_shards,), tree.dtype).at[rank].set(m_local), axes
    )
    prefix = jnp.concatenate([jnp.zeros((1,), tree.dtype), jnp.cumsum(masses)])
    total = prefix[-1]
    # clamp the unit draws below 1: u == total would fall outside every
    # shard's half-open interval (float rounding can push r * total up to
    # total exactly); the 1e-7 relative clamp is ~1 ulp in f32
    r01 = jnp.minimum(jax.random.uniform(key, (n,)), jnp.float32(1.0 - 1e-7))
    u = r01 * total
    lo = prefix[rank]
    hi = prefix[rank + 1]
    own = (u >= lo) & (u < hi)
    # cumsum rounding can make (hi - lo) exceed this shard's own mass by
    # an ulp; keep the local descent strictly inside the sub-tree
    u_loc = jnp.clip(u - lo, 0.0, m_local * (1.0 - 1e-7))
    leaf, mass = _descend(tree, u_loc, depth)
    return leaf, mass, own, total


class ShardedPriorityTree:
    """Shard-aware counterpart of :class:`PriorityTree` for the env-sharded
    :class:`~sheeprl_tpu.data.device_buffer.ShardedDeviceReplayCache`.

    Each device owns an independent sub-tree over ITS env columns' cells
    (leaf = row * n_local_envs + env_local); the sub-trees ride stacked as
    one ``(n_shards, 2·P)`` array sharded over the mesh batch axes, so
    every write is a single shard_map dispatch where each device scatters
    only the leaves it owns and sampling needs exactly one psum'd
    total-mass reduction per draw (:func:`shard_proportional_draw`).

    The host-facing API mirrors :class:`PriorityTree` verbatim — GLOBAL
    cell indices in, checkpoint state in global leaf order — so the cache
    and the checkpoint schema cannot tell the two apart (a run may resume
    sharded from a single-device tree state and vice versa).
    """

    def __init__(
        self,
        capacity: int,
        n_envs: int,
        n_shards: int,
        mesh,
        *,
        alpha: float = 0.6,
        eps: float = 1e-6,
        initial_priority: float = 1.0,
    ):
        from sheeprl_tpu.parallel.sharding import BATCH_AXES
        from jax.sharding import NamedSharding, PartitionSpec as P

        if n_envs % n_shards:
            raise ValueError(f"n_envs ({n_envs}) must divide over {n_shards} shards")
        self.capacity = int(capacity)
        self.n_envs = int(n_envs)
        self.n_shards = int(n_shards)
        self.n_local_envs = self.n_envs // self.n_shards
        self.n_leaves = self.capacity * self.n_envs
        self.n_leaves_local = self.capacity * self.n_local_envs
        self.alpha = float(alpha)
        self.eps = float(eps)
        self.depth = max(int(self.n_leaves_local - 1).bit_length(), 1)
        self._mesh = mesh
        self._axes = BATCH_AXES
        self._tree_sharding = NamedSharding(mesh, P(BATCH_AXES, None))
        self._replicated = NamedSharding(mesh, P())
        # device-native zeros (NOT a numpy temp): the write kernels donate
        # ``trees``, and donating a buffer that zero-copy aliases host
        # memory is the PR-3 heap-corruption class
        self.trees = jax.device_put(
            jnp.zeros((self.n_shards, 2 << self.depth), jnp.float32), self._tree_sharding
        )
        self.max_priority = jax.device_put(jnp.float32(initial_priority), self._replicated)
        self._write_fn = self._build_write()

    # ------------------------------------------------------------- mapping
    def _map_leaves(self, leaf_idx):
        """Global cell id -> (owning shard, shard-local leaf).  Works on
        jnp or np arrays (pure arithmetic)."""
        row = leaf_idx // self.n_envs
        env = leaf_idx % self.n_envs
        return env // self.n_local_envs, row * self.n_local_envs + env % self.n_local_envs

    def _build_write(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        axes, n_shards, depth = self._axes, self.n_shards, self.depth
        fsdp = int(self._mesh.shape[self._axes[1]])

        def body(trees, max_p, shard_ids, local_leaf, values, active, track_max):
            r = jax.lax.axis_index(axes[0]) * fsdp + jax.lax.axis_index(axes[1])
            act = active & (shard_ids == r)
            t = _write_impl(trees[0], local_leaf, values, act, depth)
            # running max across every shard's accepted writes: pmax keeps
            # it replicated without a host sync (track_max=False for raw
            # set/scale writes, matching PriorityTree semantics)
            cand = jnp.max(jnp.where(act, values, 0.0))
            new_max = jnp.maximum(max_p, jax.lax.pmax(cand, axes))
            new_max = jnp.where(track_max, new_max, max_p)
            return t[None], new_max

        mapped = shard_map(
            body,
            mesh=self._mesh,
            in_specs=(P(axes, None), P(), P(), P(), P(), P(), P()),
            out_specs=(P(axes, None), P()),
            check_vma=False,
        )
        return jax.jit(mapped, donate_argnums=(0,))

    def _write(self, leaf_idx, values, active, track_max: bool) -> None:
        leaf_idx = jnp.asarray(leaf_idx, jnp.int32).reshape(-1)
        values = jnp.asarray(values, jnp.float32).reshape(leaf_idx.shape)
        active = jnp.asarray(active).reshape(leaf_idx.shape)
        shard_ids, local_leaf = self._map_leaves(leaf_idx)
        self.trees, self.max_priority = self._write_fn(
            self.trees,
            self.max_priority,
            shard_ids.astype(jnp.int32),
            local_leaf.astype(jnp.int32),
            values,
            active,
            jnp.asarray(track_max),
        )

    # ------------------------------------------------------------- write API
    def seed_max(self, leaf_idx, active) -> None:
        leaf_idx = jnp.asarray(leaf_idx, jnp.int32)
        vals = jnp.broadcast_to(self.max_priority, leaf_idx.shape)
        self._write(leaf_idx, vals, jnp.asarray(active), track_max=False)

    def update(self, leaf_idx, td_abs, active=None) -> None:
        leaf_idx = jnp.asarray(leaf_idx, jnp.int32)
        if active is None:
            active = jnp.ones(leaf_idx.shape, bool)
        pri = priority_from_td(
            jnp.asarray(td_abs, jnp.float32).reshape(leaf_idx.shape), self.alpha, self.eps
        )
        self._write(leaf_idx, pri, jnp.asarray(active), track_max=True)

    def scale(self, leaf_idx, factor: float) -> None:
        leaf_idx = jnp.asarray(leaf_idx, jnp.int32).reshape(-1)
        vals = self.priorities(leaf_idx) * jnp.float32(factor)
        self._write(leaf_idx, vals, jnp.ones(leaf_idx.shape, bool), track_max=False)

    def set_priorities(self, leaf_idx, priorities, active=None) -> None:
        leaf_idx = jnp.asarray(leaf_idx, jnp.int32)
        if active is None:
            active = jnp.ones(leaf_idx.shape, bool)
        self._write(leaf_idx, jnp.asarray(priorities, jnp.float32), jnp.asarray(active), track_max=False)

    # ------------------------------------------------------------- read
    def priorities(self, leaf_idx) -> jax.Array:
        """Per-cell priorities for GLOBAL cell ids (replicated result —
        each shard contributes its own leaves via one masked psum)."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        leaf_idx = jnp.asarray(leaf_idx, jnp.int32).reshape(-1)
        shard_ids, local_leaf = self._map_leaves(leaf_idx)
        axes, depth = self._axes, self.depth
        fsdp = int(self._mesh.shape[self._axes[1]])

        def body(trees, shard_ids, local_leaf):
            r = jax.lax.axis_index(axes[0]) * fsdp + jax.lax.axis_index(axes[1])
            vals = trees[0][local_leaf + (1 << depth)]
            return jax.lax.psum(jnp.where(shard_ids == r, vals, 0.0), axes)

        fn = shard_map(
            body,
            mesh=self._mesh,
            in_specs=(P(axes, None), P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        return jax.jit(fn)(self.trees, shard_ids.astype(jnp.int32), local_leaf.astype(jnp.int32))

    @property
    def total(self) -> float:
        return float(jnp.sum(self.trees[:, 1]))

    # ------------------------------------------------------- checkpoint
    def state_dict(self) -> dict:
        """Same schema as :class:`PriorityTree` — leaves in GLOBAL cell
        order, so sharded and single-device runs can resume each other."""
        p = 1 << self.depth
        trees_np = np.asarray(self.trees)  # gathers the shards
        local = trees_np[:, p : p + self.n_leaves_local]
        # (shard, row * n_local + e) -> global order (row, shard, e)
        leaves = (
            local.reshape(self.n_shards, self.capacity, self.n_local_envs)
            .transpose(1, 0, 2)
            .reshape(-1)
        )
        return {
            "leaves": leaves,
            "max_priority": np.asarray(self.max_priority),
            "alpha": self.alpha,
            "eps": self.eps,
        }

    def load_state_dict(self, state: dict) -> None:
        leaves = np.asarray(state["leaves"], np.float32)
        if leaves.shape[0] != self.n_leaves:
            raise ValueError(
                f"priority state has {leaves.shape[0]} leaves, tree expects {self.n_leaves}"
            )
        p = 1 << self.depth
        local = (
            leaves.reshape(self.capacity, self.n_shards, self.n_local_envs)
            .transpose(1, 0, 2)
            .reshape(self.n_shards, self.n_leaves_local)
        )
        full = np.zeros((self.n_shards, 2 << self.depth), np.float32)
        full[:, p : p + self.n_leaves_local] = local
        # rebuild internal nodes host-side per shard (resume cadence only)
        for node in range(p - 1, 0, -1):
            full[:, node] = full[:, 2 * node] + full[:, 2 * node + 1]
        # jnp.array (copy) before placement: the restored trees are donated
        # by the next write, which must never alias the host staging buffer
        self.trees = jax.device_put(jnp.array(full), self._tree_sharding)
        self.max_priority = jax.device_put(
            jnp.float32(float(state["max_priority"])), self._replicated
        )
