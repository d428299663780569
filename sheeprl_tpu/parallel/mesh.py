"""MeshRuntime — the TPU-native replacement for Lightning Fabric.

The reference wraps torch.distributed in Fabric (per-process DDP launcher,
NCCL/Gloo collectives, precision plugins — SURVEY.md §2.7/§5.8). On TPU the
idiomatic equivalent is single-controller SPMD:

- ``jax.distributed.initialize`` (multi-host) instead of TCPStore rendezvous;
- a ``jax.sharding.Mesh`` whose axes replace process groups: the ``data``
  axis is DDP, a ``model`` axis gives fsdp/tensor sharding;
- gradient all-reduce disappears: batches are sharded over ``data`` and XLA
  inserts the ``psum`` inside the jitted update (``NamedSharding`` + jit);
- precision plugins become a dtype policy (params fp32, compute bf16 on the
  MXU by default).

One MeshRuntime instance plays the roles of reference cli.py's
``hydra.utils.instantiate(cfg.fabric)`` object and utils/fabric.py:8's
single-device clone (``runtime.single_device()``).
"""

from __future__ import annotations

import os
import random
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sheeprl_tpu.parallel.sharding import BATCH_AXES, ShardingLayout, build_mesh
from sheeprl_tpu.utils.utils import take_refresh_copied_bytes


def _sanitize_enabled() -> bool:
    """Local alias kept import-lazy: the sanitizers module pulls in the
    analysis package, which mesh must not pay for on the hot import path."""
    return os.environ.get("SHEEPRL_SANITIZE", "").strip().lower() in ("1", "true", "yes", "on")


# the checkout root: sheeprl_tpu/parallel/mesh.py -> three levels up
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def configure_compilation_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no directory
    is set in code.  Unset: ``<checkout>/.jax_cache`` — a FIXED, git-ignored
    path (the path is part of how a cache is found again, so never ``~``, a
    temp name, a pid or a time).  Every entry point that compiles calls
    this before its first jit: ``MeshRuntime.launch`` (so ``cli.run``),
    ``serve_policy.build_server``, the bench children, ``chip_smoke.py``.

    The key of an entry covers the program's metadata (op names with their
    ``jax.named_scope`` path, source lines).  JAX leaves them out by default,
    and then serves an executable compiled before a scope was added or
    renamed: its profile carries the old names, and a reduction that splits
    device time by scope reads nothing.  The price is a compile whenever a
    traced line moves."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


_PRECISIONS = ("32-true", "bf16-mixed", "bf16-true")
_STRATEGIES = ("auto", "dp", "ddp", "fsdp")
_PLAYER_DEVICES = ("auto", "cpu", "accelerator")
# fabric.player_device=auto beside a chip: players whose weights reach this
# many bytes act on the training device, smaller ones on the host CPU.
# Set between the largest player the host won with and the smallest the chip
# won with, by the wall per policy step of ``cli.run`` on one v5e
# (benchmarks/player_device_readings.py, PR 27; host | chip, ms):
#   exp=ppo MLP, 4 envs (a refresh per 128 steps)   0.10 MB  0.35 | 0.45
#                                                   1.00 MB  0.36 | 0.45
#                                                   3.57 MB  0.38 | 0.45   <- host wins up to here
#                                                  13.42 MB  0.46 | 0.45
#   DreamerV3, 1 env (a refresh per 2 steps)        4.29 MB  22.1 | 11.2   <- chip wins from here
#                                                   9.63 MB  23.9 | 11.0
#                                             XS   31.50 MB  29.7 | 12.3
#                                             S    67.01 MB  73.4 | 14.4
#                                             M   222.37 MB 160.7 | 22.2
#                                             XL  764.14 MB 565.8 | 56.4
# On the chip a step costs the same whatever the weights weigh (1.8-2.7 ms of
# Time/player_step); on the host the step and the refresh both grow with them,
# and a refresh costs ~20 ms in per-leaf work before its first byte.
PLAYER_ON_CHIP_BYTES = 4 * 1024 * 1024


class MeshRuntime:
    """Owns device selection, the device mesh, dtype policy and RNG keys."""

    def __init__(
        self,
        devices: int = 1,
        num_nodes: int = 1,
        strategy: str = "auto",
        accelerator: str = "auto",
        precision: str = "32-true",
        player_device: str = "auto",
        mesh_shape: Any = "auto",
        **kwargs: Any,
    ):
        if precision not in _PRECISIONS:
            raise ValueError(f"precision must be one of {_PRECISIONS}, got '{precision}'")
        if strategy not in _STRATEGIES:
            raise ValueError(f"strategy must be one of {_STRATEGIES}, got '{strategy}'")
        if player_device not in _PLAYER_DEVICES:
            raise ValueError(
                f"player_device must be one of {_PLAYER_DEVICES}, got '{player_device}'"
            )
        self._requested_devices = devices
        self._num_nodes = num_nodes
        self._strategy = strategy
        self._accelerator = accelerator
        self._precision = precision
        self._player_device = player_device
        self._mesh_shape = mesh_shape
        self._player_choice_logged = False
        self._player_placement: Optional[Dict[str, Any]] = None
        self._launched = False
        self._mesh: Optional[Mesh] = None
        self._layout: Optional[ShardingLayout] = None
        self._key: Optional[jax.Array] = None

    # ------------------------------------------------------------------ #
    # device / mesh setup
    # ------------------------------------------------------------------ #
    def _resolve_backend(self) -> str:
        if self._accelerator in ("auto", None):
            return jax.default_backend()
        if self._accelerator in ("tpu", "cpu", "gpu"):
            return self._accelerator
        raise ValueError(f"Unknown accelerator '{self._accelerator}'")

    def launch(self) -> "MeshRuntime":
        """Initialize (multi-host if configured) runtime and build the mesh.

        Unlike Fabric there is no process spawning: SPMD means one python
        process per host drives all local devices.
        """
        if self._launched:
            return self
        configure_compilation_cache()
        # NOTE: the guard must not call jax.process_count() — that would
        # initialize the XLA backend, after which distributed.initialize()
        # refuses to run
        if self._num_nodes > 1 and not jax.distributed.is_initialized():
            # multi-host rendezvous. Cloud TPU / SLURM / MPI environments
            # auto-detect coordinator settings; plain CPU/GPU clusters (and
            # the 2-process test in tests/test_parallel) pass them
            # explicitly via SHEEPRL_COORDINATOR_ADDRESS / _NUM_PROCESSES /
            # _PROCESS_ID.  Counterpart of the reference's
            # TorchCollective.setup + env:// init (SURVEY.md §5.8).
            init_kwargs = {}
            addr = os.environ.get("SHEEPRL_COORDINATOR_ADDRESS")
            if addr:
                missing = [
                    k
                    for k in ("SHEEPRL_NUM_PROCESSES", "SHEEPRL_PROCESS_ID")
                    if k not in os.environ
                ]
                if missing:
                    raise RuntimeError(
                        "SHEEPRL_COORDINATOR_ADDRESS is set but "
                        + " and ".join(missing)
                        + " is not; the three variables must be set together "
                        "for an explicit multi-host rendezvous."
                    )
                init_kwargs = dict(
                    coordinator_address=addr,
                    num_processes=int(os.environ["SHEEPRL_NUM_PROCESSES"]),
                    process_id=int(os.environ["SHEEPRL_PROCESS_ID"]),
                )
            jax.distributed.initialize(**init_kwargs)
        # raises when the requested backend is absent: a run that asked for
        # accelerator=tpu must not carry on on the CPU
        devices = jax.devices(self._resolve_backend())
        n = self._requested_devices
        if n in (-1, "auto", None):
            n = len(devices)
        n = int(n)
        if n > len(devices):
            raise RuntimeError(f"Requested {n} devices but only {len(devices)} are available")
        devices = devices[:n]

        # Two mesh axes (parallel/sharding.py): batches shard over the
        # flattened ("data", "fsdp") axes — every device is a DP worker —
        # while params/opt-state replicate under dp and shard ZeRO-style
        # over "fsdp" under ``strategy=fsdp``.  ``mesh_shape=auto``
        # reproduces the pre-2-D layouts bit-exactly (all devices on one
        # axis); explicit ``[d, f]`` shapes lay a pod as d-way data x
        # f-way param sharding, with jit lowering the cross-shard
        # reductions to ``jax.lax`` collectives over ICI/DCN.
        self._mesh = build_mesh(devices, self._mesh_shape, self._strategy)
        self._layout = ShardingLayout(self._mesh)
        self._launched = True
        return self

    @property
    def mesh(self) -> Mesh:
        if not self._launched:
            self.launch()
        return self._mesh

    @property
    def world_size(self) -> int:
        """Number of data-parallel workers (batch shards) — the flattened
        (data x fsdp) device count: the batch sharding always covers both
        axes, so every device owns a batch shard."""
        return self.layout.n_shards

    @property
    def layout(self) -> ShardingLayout:
        """Canonical PartitionSpec vocabulary for this mesh."""
        if not self._launched:
            self.launch()
        return self._layout

    @property
    def data_size(self) -> int:
        return self.layout.data_size

    @property
    def fsdp_size(self) -> int:
        return self.layout.fsdp_size

    @property
    def device_count(self) -> int:
        return len(self.mesh.devices.ravel())

    @property
    def global_rank(self) -> int:
        return jax.process_index()

    @property
    def node_rank(self) -> int:
        return jax.process_index()

    @property
    def is_global_zero(self) -> bool:
        return jax.process_index() == 0

    @property
    def strategy(self) -> str:
        return self._strategy

    @property
    def precision(self) -> str:
        return self._precision

    @property
    def device(self):
        return self.mesh.devices.ravel()[0]

    # ------------------------------------------------------------------ #
    # dtype policy
    # ------------------------------------------------------------------ #
    @property
    def compute_dtype(self):
        return jnp.float32 if self._precision == "32-true" else jnp.bfloat16

    @property
    def param_dtype(self):
        return jnp.bfloat16 if self._precision == "bf16-true" else jnp.float32

    def to_param_dtype(self, tree: Any, exclude: Tuple[str, ...] = ()) -> Any:
        """Cast float32 leaves to the parameter STORAGE dtype.

        Under ``bf16-true`` parameters live in bfloat16 — half the HBM
        footprint and half the weight traffic on bandwidth-bound paths
        (e.g. the RSSM scan's per-step matmuls) — while flax modules
        promote them to each module's compute dtype on use, and the
        optimizer keeps an f32 master copy
        (``sheeprl_tpu.optim.master_weights``).  Dict keys in ``exclude``
        match at ANY nesting depth (e.g. an EMA ``target_critic`` at the
        top level, or each ensemble member's ``target_module`` inside
        p2e's ``critics_exploration``): the whole subtree under a matched
        key keeps f32 storage — EMA targets' small per-step updates would
        drown in bf16 rounding.  No-op for other precisions, so call
        sites are unconditional."""
        if self.param_dtype == jnp.float32:
            return tree
        cast = lambda t: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if getattr(x, "dtype", None) == jnp.float32
            else x,
            t,
        )
        if not exclude:
            return cast(tree)
        ex = frozenset(exclude)

        def rec(node):
            if isinstance(node, dict):
                return {k: (v if k in ex else rec(v)) for k, v in node.items()}
            return cast(node)

        return rec(tree)

    # ------------------------------------------------------------------ #
    # RNG
    # ------------------------------------------------------------------ #
    def seed_everything(self, seed: int) -> jax.Array:
        """Seed python/numpy and derive the root PRNG key (replaces Fabric's
        seed_everything + torch cudnn flags).

        ``next_key`` draws raw uint32 key DATA from a seeded host-side
        numpy stream: generating keys costs microseconds, while any eager
        jax op in the env hot loop pays a per-dispatch toll."""
        random.seed(seed)
        np.random.seed(seed)
        os.environ["PYTHONHASHSEED"] = str(seed)
        self._seed = int(seed)
        self._key = jax.random.PRNGKey(seed)
        self._np_key_rng = np.random.Generator(np.random.PCG64(seed))
        return self._key

    def reseed_key_stream(self, salt: int) -> None:
        """Re-derive the host key stream deterministically from the run
        seed and ``salt`` (the sentinel's rollback ordinal): after a
        rollback-to-last-good, replaying the exact keys would re-draw the
        same sample indices/noise that fed the anomaly."""
        base = int(getattr(self, "_seed", 0) or 0)
        self._np_key_rng = np.random.Generator(np.random.PCG64([base, 0x5E47, int(salt)]))

    def next_key(self, num: int = 1):
        """Fresh independent PRNG keys for the host-side loop (jitted code
        threads keys explicitly). Raw uint32[2] key data drawn from a seeded
        host RNG — no device computation per call."""
        if self._key is None:
            self.seed_everything(0)
        data = self._np_key_rng.integers(0, 2**32, size=(num, 2), dtype=np.uint32)
        # retain the buffer until the NEXT draw: keys are usually passed as
        # call-expression temporaries, and CPU device_put may zero-copy
        # alias the numpy memory — freeing it before the async consumer
        # executes lets the allocator recycle it mid-computation
        self._live_key = data
        # returned as UNCOMMITTED numpy key data: jit places it with the
        # computation (replicated over the mesh for train steps, pinned by
        # the player's device_put for the env hot loop)
        return data[0] if num == 1 else [row for row in data]

    # ------------------------------------------------------------------ #
    # shardings
    # ------------------------------------------------------------------ #
    def sharding(self, *axes: Optional[str]) -> NamedSharding:
        """NamedSharding with the given axis names over array dims."""
        return NamedSharding(self.mesh, P(*axes))

    def ddp_gate(self, batch_axis_size: int, algo: str = "") -> bool:
        """Whether a rank-local DDP ``shard_map`` core applies: multi-device,
        evenly divisible batch axis, and replicated (non-fsdp) params — the
        shard_map cores declare params/opt-state replicated, which would
        all-gather and destroy a ZeRO (fsdp) layout.  When it returns False
        on a multi-device mesh, warns that the update runs on the
        replicated GSPMD fallback (correct, but every device computes the
        FULL update) — except under fsdp, where the GSPMD path with the
        layout constraints IS the intended ZeRO program, not a fallback.
        One gate shared by ppo/a2c/ppo_recurrent/sac/droq so the fsdp
        guard and the warning cannot drift per algo."""
        if self.world_size == 1:
            return False
        if self._strategy == "fsdp":
            # not a fallback: the jit path with guard_update's boundary
            # constraints lowers to the ZeRO all-gather/reduce-scatter
            # program — silence here, the layout is by design
            return False
        if batch_axis_size % self.world_size == 0:
            return True
        import warnings

        warnings.warn(
            f"multi-device {algo or 'train'} update falling back to the replicated GSPMD "
            f"path (correct, but every device computes the FULL update — no DP speedup): "
            f"batch axis {batch_axis_size} is not divisible by world_size={self.world_size}."
        )
        return False

    def batch_sharding(self, axis: int = 0) -> NamedSharding:
        """Sharding that splits ``axis`` over the flattened batch axes
        (data x fsdp — one shard per device; pass to device_put /
        DevicePrefetcher so batches land already distributed)."""
        return self.layout.batch_sharding(axis)

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_batch(self, batch: Any, axis: int = 0) -> Any:
        """device_put a host pytree, splitting ``axis`` over the data axis.

        Every leaf's ``axis`` dim must be divisible by world_size.
        """
        if _sanitize_enabled():
            from sheeprl_tpu.analysis.sanitizers import check_host_sources

            check_host_sources(batch, "shard_batch")
        return jax.device_put(batch, self.batch_sharding(axis))

    def replicate(self, tree: Any) -> Any:
        """Place params/opt-state on the mesh.

        Default strategies replicate every leaf. Under ``strategy="fsdp"``
        each leaf is sharded over the **fsdp** axis on its LARGEST
        dimension divisible by the axis size (scalars and indivisible
        leaves stay replicated): the ZeRO-3 layout, with XLA inserting the
        weight all-gathers and gradient reduce-scatters during jit.  The
        per-leaf rule lives in :meth:`ShardingLayout.param_spec` so the
        in-jit boundary constraints agree with this placement by
        construction."""
        if _sanitize_enabled():
            from sheeprl_tpu.analysis.sanitizers import check_host_sources

            check_host_sources(tree, "replicate")
        if self._strategy != "fsdp" or self.fsdp_size == 1:
            if self._strategy == "fsdp" and self.world_size > 1:
                import warnings

                warnings.warn(
                    "strategy=fsdp with a size-1 'fsdp' mesh axis keeps params "
                    "replicated (plain DP); set fabric.mesh_shape to give the "
                    "fsdp axis a real size (auto puts every device on it)."
                )
            return jax.device_put(tree, self.replicated)
        layout = self.layout
        return jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, layout.param_sharding(leaf)), tree
        )

    def mesh_telemetry(self, params: Any = None, compiled: Any = None) -> Dict[str, Any]:
        """The telemetry record's ``mesh`` key (howto/observability.md):
        axis names/sizes, the achieved per-device FSDP param-shard bytes
        (when ``params`` is passed), and a best-effort per-update
        cross-device traffic estimate from ``Compiled.cost_analysis()``
        (when a compiled update is passed)."""
        out: Dict[str, Any] = dict(self.layout.describe())
        out["strategy"] = self._strategy
        # extras stashed by the first guarded-update dispatch (sentinel.py):
        # param bytes, FSDP shard bytes, opt-in collective-bytes estimate
        out.update(getattr(self, "_mesh_extra", None) or {})
        if params is not None:
            total = self._player_params_nbytes(params)
            out["param_bytes_total"] = int(total)
            if self._strategy == "fsdp" and self.fsdp_size > 1:
                out["param_bytes_per_device"] = self.layout.param_shard_bytes(params)
        if compiled is not None:
            from sheeprl_tpu.parallel.sharding import collective_bytes_estimate

            est = collective_bytes_estimate(compiled)
            if est is not None:
                out["collective_bytes_estimate"] = est
        return out

    def setup_step(
        self,
        fn: Callable,
        donate_argnums: Tuple[int, ...] = (),
        static_argnums: Tuple[int, ...] = (),
    ) -> Callable:
        """jit-compile a step function under this mesh.

        With inputs placed via ``shard_batch``/``replicate``, XLA lays out
        the computation SPMD over the mesh and inserts the cross-device
        collectives (the DDP grad all-reduce equivalent) automatically.
        """
        jitted = jax.jit(fn, donate_argnums=donate_argnums, static_argnums=static_argnums)

        def wrapped(*args, **kw):
            with jax.set_mesh(self.mesh):
                return jitted(*args, **kw)

        wrapped._jitted = jitted
        if donate_argnums and _sanitize_enabled():
            # donation sanitizer (SHEEPRL_SANITIZE=1): deletes/poisons the
            # donated inputs after each dispatch so a use-after-donate
            # fails deterministically at the offending line on EVERY
            # backend — on CPU/GPU unhonored donation otherwise turns the
            # same bug into timing-dependent memory recycling.  Off path:
            # this branch is never entered, the returned callable is the
            # exact pre-sanitizer object (zero overhead).
            from sheeprl_tpu.analysis.sanitizers import guard_donation

            return guard_donation(wrapped, donate_argnums, where=getattr(fn, "__name__", "step"))
        return wrapped

    # ------------------------------------------------------------------ #
    # single-device view (players / target critics)
    # ------------------------------------------------------------------ #
    def single_device(self) -> "MeshRuntime":
        """A 1-device runtime on the same backend (reference
        utils/fabric.py:8-35): used for env-interaction players so inference
        never pays mesh collectives."""
        rt = MeshRuntime(
            devices=1,
            num_nodes=1,
            strategy="auto",
            accelerator=self._accelerator,
            precision=self._precision,
        )
        rt.launch()
        rt._key = self._key
        return rt

    def player_device(self, params: Any = None):
        """Device for env-interaction policies; None = the training device.

        ``params`` is the tree the loop hands to its player: under
        ``auto`` the player acts where its weights are cheapest to read.
        Small weights go to the host CPU backend beside a chip (the env hot
        loop then dispatches tiny policy steps without queueing behind
        train steps: CPU-actor/TPU-learner split); from
        ``PLAYER_ON_CHIP_BYTES`` up the player stays on the training device
        and shares the learner's arrays, so no step streams them through
        the host's memory and no update ships them there.  "cpu" and
        "accelerator" force either side.  Configured via
        ``fabric.player_device``; the SHEEPRL_PLAYER_DEVICE env var
        overrides the config."""
        choice = os.environ.get("SHEEPRL_PLAYER_DEVICE", self._player_device)
        if choice not in _PLAYER_DEVICES:
            raise ValueError(
                f"player_device must be one of {_PLAYER_DEVICES}, got '{choice}'"
            )
        nbytes = self._player_params_nbytes(params)
        device, why = self._player_device_decision(choice, nbytes)
        placed = device if device is not None else self.device
        self._player_placement = {"device": f"{placed.platform}:{placed.id}", "param_bytes": nbytes}
        take_refresh_copied_bytes()  # the count is this process's: a run's first record starts from its own player
        if not self._player_choice_logged:
            self._player_choice_logged = True
            self.print(f"Player device: {device if device is not None else 'training device'} ({why})")
        return device

    def player_telemetry(self) -> Optional[Dict[str, Any]]:
        """The telemetry record's ``player`` key (howto/observability.md):
        where the player acts, the bytes of its weights, and the bytes its
        refreshes copied across backends since the last record (0 while it
        shares the learner's arrays).  None before a loop placed a player."""
        if self._player_placement is None:
            return None
        return {**self._player_placement, "refresh_copied_bytes": take_refresh_copied_bytes()}

    def _player_params_nbytes(self, params: Any) -> int:
        return sum(
            int(np.prod(np.shape(leaf))) * np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
            for leaf in jax.tree_util.tree_leaves(params)
        )

    def _player_device_decision(self, choice: str, nbytes: int):
        """(device-or-None, reason); None = stay on the training device.
        The cases, pinned by tests/test_parallel/test_mesh.py: an explicit
        ``accelerator``, training already on the CPU, no CPU backend, and
        beside a local chip ``cpu`` (always the host) and ``auto`` (the
        host below ``PLAYER_ON_CHIP_BYTES`` of weights, the chip from it
        up)."""
        if choice == "accelerator":
            return None, "player_device=accelerator"
        if self.device.platform == "cpu":
            return None, "training backend is already the host CPU"
        try:
            cpu = jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            return None, "no host CPU backend in this process (JAX_PLATFORMS excludes cpu)"
        sizes = f"{nbytes} B of player weights, bound {PLAYER_ON_CHIP_BYTES} B"
        if choice == "auto" and nbytes >= PLAYER_ON_CHIP_BYTES:
            return None, f"player_device=auto: {sizes}: the player shares the learner's arrays on the chip"
        return cpu, f"player_device={choice}: {sizes}: host CPU player beside the local chip"

    # ------------------------------------------------------------------ #
    # host-side collectives (metrics, small objects)
    # ------------------------------------------------------------------ #
    def all_gather_object(self, obj: Any) -> list:
        """Gather a picklable object from every process (multi-host); on a
        single process returns [obj]. Replacement for TorchCollective
        broadcast/gather of config/metric dicts."""
        if jax.process_count() == 1:
            return [obj]
        import pickle

        from jax.experimental import multihost_utils

        # process_allgather only moves numeric arrays, so arbitrary objects
        # ride as pickled uint8 payloads padded to the global max length
        # (same trick as torch.distributed.all_gather_object)
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        sizes = np.asarray(multihost_utils.process_allgather(np.asarray([payload.size]))).reshape(-1)
        padded = np.zeros((int(sizes.max()),), np.uint8)
        padded[: payload.size] = payload
        gathered = np.asarray(multihost_utils.process_allgather(padded))
        return [pickle.loads(gathered[i, : int(sizes[i])].tobytes()) for i in range(len(sizes))]

    def barrier(self) -> None:
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("sheeprl_tpu_barrier")

    def print(self, *args: Any, **kwargs: Any) -> None:
        if self.is_global_zero:
            print(*args, **kwargs)
