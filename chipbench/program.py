"""The benchmark's only door into the program.

Everything here reaches the system under test through the entry points its
users call — ``sheeprl_tpu.cli.run``, and the builders ``cli.run`` itself
reaches for DreamerV3 (``compose``, the ``fabric`` node's ``MeshRuntime``,
``build_agent``, ``make_train_fn``, ``maybe_create_for``) — and reads only
what the program already exposes.  No other file under ``chipbench/``
imports ``sheeprl_tpu``.

``Spy`` is the pattern ``chip_smoke.py`` proved on the chip (copied, the
original stays where it is): it wraps names that ``dreamer_v3.main`` looks
up at call time, so the program gains no hook.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional


def compose_cfg(overrides: List[str]):
    from sheeprl_tpu.config import compose

    return compose(config_name="config", overrides=list(overrides))


def recompile_monitor(name: str):
    from sheeprl_tpu.obs import RecompileMonitor

    return RecompileMonitor(name=name, warn=False).install()


def read_telemetry(path: str) -> List[dict]:
    from sheeprl_tpu.obs import read_records

    return list(read_records(path))


class Update:
    """The DreamerV3 update and its replay ring, built as ``dreamer_v3.main``
    builds them (same calls, same order), without envs or a player."""

    def __init__(self, cfg):
        import jax
        import numpy as np

        import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3
        from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
        from sheeprl_tpu.config import instantiate
        from sheeprl_tpu.utils.env import make_env

        self.cfg = cfg
        self.runtime = runtime = instantiate(dict(cfg.fabric))
        runtime.launch()
        runtime.seed_everything(cfg.seed)
        cfg.env.frame_stack = -1

        env = make_env(cfg, cfg.seed, 0, None, "train")()
        self.observation_space = env.observation_space
        action_space = env.action_space
        env.close()
        self.is_continuous = hasattr(action_space, "high")
        self.actions_dim = tuple(
            action_space.shape
            if self.is_continuous
            else (action_space.nvec.tolist() if hasattr(action_space, "nvec") else [action_space.n])
        )
        self.world_size = runtime.world_size
        self.total_envs = int(cfg.env.num_envs) * self.world_size

        self.modules = dv3.build_agent(runtime, self.actions_dim, self.is_continuous, cfg, self.observation_space)
        world_model, actor, critic, params = self.modules
        params = runtime.replicate(runtime.to_param_dtype(params, exclude=("target_critic",)))
        precision = runtime.precision
        self.txs = tuple(
            dv3._make_optimizer(cfg.algo[name].optimizer, cfg.algo[name].clip_gradients, precision)
            for name in ("world_model", "actor", "critic")
        )
        opt_states = runtime.replicate(
            {name: tx.init(params[name]) for name, tx in zip(("world_model", "actor", "critic"), self.txs)}
        )
        moments = runtime.replicate(init_moments())
        self.state = (params, opt_states, moments)
        self.n_params = sum(
            int(np.prod(x.shape)) for k, v in params.items() if k != "target_critic" for x in jax.tree_util.tree_leaves(v)
        )

        buffer_size = max(int(cfg.buffer.size) // self.total_envs, 2)
        self.rb = dv3.EnvIndependentReplayBuffer(
            buffer_size,
            n_envs=self.total_envs,
            memmap=False,
            buffer_cls=dv3.SequentialReplayBuffer,
        )
        self.cache = dv3.maybe_create_for(cfg, runtime, self.rb)
        self.train_fn = dv3.make_train_fn(
            runtime, world_model, actor, critic, self.txs, cfg, self.is_continuous, self.actions_dim
        )

    @property
    def batch_size(self) -> int:
        return int(self.cfg.algo.per_rank_batch_size) * self.world_size

    @property
    def seq_len(self) -> int:
        return int(self.cfg.algo.per_rank_sequence_length)

    def first_row(self) -> Dict[str, Any]:
        """One ring row with the keys, shapes and dtypes of the loop's own
        ``step_data`` (``dreamer_v3.main``), which is what sizes the ring."""
        import numpy as np

        n = self.total_envs
        row = {
            k: np.zeros((1, n) + tuple(self.observation_space[k].shape), self.observation_space[k].dtype)
            for k in list(self.cfg.algo.cnn_keys.encoder) + list(self.cfg.algo.mlp_keys.encoder)
        }
        for k in ("rewards", "truncated", "terminated"):
            row[k] = np.zeros((1, n, 1))
        row["is_first"] = np.ones((1, n, 1))
        row["actions"] = np.zeros((1, n, int(sum(self.actions_dim))), np.float32)
        return row

    def compiled_text(self, state, batch, key) -> str:
        """The compiled update's text, for the shapes and shardings given
        (served from the compile cache: the same program already ran)."""
        import jax

        avals = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), (*state, batch)
        )
        with jax.set_mesh(self.runtime.mesh):
            return self.train_fn._jitted.lower(*avals, key).compile().as_text()


class SecondUpdate:
    """The same update on another number of devices, sharing modules and
    optimizers with ``first`` (what the four-chip cell is compared with)."""

    def __init__(self, first: Update, devices: int):
        import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3
        from sheeprl_tpu.config import instantiate

        fabric = dict(first.cfg.fabric)
        fabric["devices"] = devices
        fabric["strategy"] = "auto"
        self.runtime = instantiate(fabric)
        self.runtime.launch()
        world_model, actor, critic, _ = first.modules
        self.train_fn = dv3.make_train_fn(
            self.runtime, world_model, actor, critic, first.txs, first.cfg, first.is_continuous, first.actions_dim
        )


class Spy:
    """Records what ``cli.run`` builds and where its iterations begin, by
    wrapping four names ``dreamer_v3.main`` looks up at call time: the
    train-step builder, the replay-cache factory, the observability set-up
    (for ``on_iteration(policy_step)``) and the loss fetch."""

    def __init__(self, on_boundary: Optional[Callable[["Spy"], None]] = None):
        self.runtime = self.train_fn = self.cache = None
        self.metrics: List[dict] = []  # per gradient step, device scalars
        self.grad_steps = 0
        self.fetches = 0
        # one entry per loop iteration, taken where the program calls
        # on_iteration: (host time, policy steps before it, gradient steps
        # dispatched, whether the iteration before ended in a loss fetch)
        self.boundaries: List[tuple] = []
        self._fetches_seen = 0
        self._on_boundary = on_boundary

    def __enter__(self):
        import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3

        self._dv3 = dv3
        self._orig = (dv3.make_train_fn, dv3.maybe_create_for, dv3.setup_observability, dv3.device_get_metrics)
        dv3.make_train_fn = self._make_train_fn
        dv3.maybe_create_for = self._maybe_create_for
        dv3.setup_observability = self._setup_observability
        dv3.device_get_metrics = self._device_get_metrics
        return self

    def __exit__(self, *exc):
        dv3 = self._dv3
        dv3.make_train_fn, dv3.maybe_create_for, dv3.setup_observability, dv3.device_get_metrics = self._orig

    def _maybe_create_for(self, *args, **kwargs):
        self.cache = self._orig[1](*args, **kwargs)
        return self.cache

    def _make_train_fn(self, runtime, *args, **kwargs):
        fn = self._orig[0](runtime, *args, **kwargs)
        self.runtime, self.train_fn = runtime, fn

        def stepped(params, opt_states, moments, batch, key):
            out = fn(params, opt_states, moments, batch, key)
            self.metrics.append(out[3])
            self.grad_steps += 1
            return out

        stepped.health = fn.health
        return stepped

    def _setup_observability(self, *args, **kwargs):
        obs = self._orig[2](*args, **kwargs)
        inner = obs.on_iteration

        def on_iteration(policy_step: int = 0):
            after_fetch = self.fetches != self._fetches_seen
            self._fetches_seen = self.fetches
            self.boundaries.append((time.perf_counter(), int(policy_step), self.grad_steps, after_fetch))
            if self._on_boundary is not None:
                self._on_boundary(self)
            return inner(policy_step)

        obs.on_iteration = on_iteration
        return obs

    def _device_get_metrics(self, metrics):
        out = self._orig[3](metrics)
        self.fetches += 1
        return out


def run_cli(overrides: List[str], spy: Spy) -> None:
    """``sheeprl_tpu.cli.run`` — the function ``sheeprl.py`` calls — under
    the spy, on the calling (main) thread."""
    from sheeprl_tpu.cli import run

    with spy:
        run(list(overrides))
