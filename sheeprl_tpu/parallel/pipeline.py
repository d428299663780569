"""Pipelined collect/train: overlap host env stepping with device training.

The coupled on-policy loops (ppo/a2c/ppo_recurrent) serialize the two
halves of every iteration: the host steps the vectorized envs for
``rollout_steps``, then the jitted update consumes the rollout, then the
host steps again.  Under JAX async dispatch the update is ALREADY a
future the moment it is dispatched — the host just never uses that slack.
Podracer-style architectures (Hessel et al., 2021) and EnvPool (Weng et
al., 2022) get their integer-factor speedups from exactly this overlap:
a collector runs iteration t+1's env steps while the device trains on
iteration t.

:class:`PipelinedCollector` implements that overlap as a background
thread with

- **double-buffered rollout storage**: the collector converts + uploads
  (``pack_fn``) its finished rollout into fresh device buffers before the
  next rollout overwrites the host-side ring, and at most ONE packed
  rollout waits in the handoff queue;
- **a params-publish handoff with bounded staleness**: the trainer
  publishes the params produced by iteration t; the collector adopts, at
  each rollout boundary, EXACTLY the params of iteration
  k-1-``max_staleness`` (fixed lag; waits for them if unpublished, keeps
  the initial weights during warmup).  Default ``max_staleness=1`` — a
  rollout acts on weights exactly one update behind the fully-serial
  schedule.  A "newest published wins" adoption would honor the same
  bound but make the adopted version a thread-timing race; the fixed lag
  keeps overlapped runs reproducible given their seed;
- **a sync fallback** (``overlap=False``, config
  ``algo.overlap_collect=false``): the same collect/pack/train code runs
  inline on the caller's thread in the exact pre-pipeline order, so
  runs stay bit-exact with the serial loop for determinism checks.

RNG: the serial path draws per-step policy keys from ``runtime.next_key``
(bit-exact with the pre-pipeline loops).  The overlapped path draws them
from an independent, deterministically-seeded stream
(:class:`KeyStream`): thread interleaving cannot change which keys the
collector sees, and the fixed-lag params handoff (below) pins WHICH
weights each rollout acts on.  Exact float reproducibility across
overlapped runs additionally depends on the backend (concurrent host
uploads/saves on a shared CPU client can reorder allocator/runtime work);
``algo.overlap_collect=false`` is the documented bit-exactness switch.

Thread rules: the collector thread may touch the envs, the player and
the rollout buffer (it is their only user while active); the aggregator,
logger, timer registry and checkpoint manager stay on the caller's
thread — per-step episode events are deferred through the payload and
applied by the caller (:meth:`RolloutPayload.apply_events`).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from sheeprl_tpu.obs import flight

__all__ = [
    "KeyStream",
    "PipelinedCollector",
    "RolloutPayload",
    "credit_timer",
    "detach_copy",
    "resolve_overlap_setting",
]


def resolve_overlap_setting(cfg) -> bool:
    """Resolve ``algo.overlap_collect`` (``true``/``false``/``auto``).

    ``auto`` enables the pipeline only where it can win: the collector
    thread needs a host core of its own, and on a single-core host the
    overlap degenerates to time-slicing plus handoff overhead (measured
    0.67-0.81x in BENCH_r05) — those hosts stay on the bit-exact serial
    path.

    With ``algo.env_backend=jax`` the overlap resolves to OFF regardless:
    the fused collect IS the device program — there is no host env work
    left to overlap, and the pipeline thread would only add handoff
    latency.  A one-line notice is emitted when the setting would
    otherwise have enabled it."""
    import os
    import sys

    val = cfg.algo.get("overlap_collect", False)
    is_auto = isinstance(val, str) and val.strip().lower() == "auto"
    resolved = (os.cpu_count() or 1) > 1 if is_auto else bool(val)
    if str(cfg.algo.get("env_backend", "host") or "host").lower() == "jax":
        if resolved:
            print(
                "overlap_collect resolved to off: env_backend=jax runs the fused "
                "device collect — no host env stepping left to overlap.",
                file=sys.stderr,
            )
        return False
    return resolved


class KeyStream:
    """Independent PRNG-key stream for the collector thread.

    Mirrors ``MeshRuntime.next_key`` (raw uint32[2] key data from a host
    PCG64) but over its own generator, so the collector and trainer can
    draw keys concurrently without racing the runtime's shared stream —
    and an overlapped run draws the same keys every time given its seed.
    """

    def __init__(self, seed: int, tag: int = 0xC011EC7):
        self._rng = np.random.Generator(np.random.PCG64([int(seed) & 0xFFFFFFFF, int(tag)]))
        self._live = None

    def __call__(self, num: int = 1):
        data = self._rng.integers(0, 2**32, size=(num, 2), dtype=np.uint32)
        # retain the buffer until the NEXT draw: the key is usually passed
        # as a call-expression temporary, and CPU device_put may zero-copy
        # alias it — freeing it before the async consumer runs lets the
        # allocator recycle the memory mid-computation.  By the next draw
        # the previous step's computation has been forced by its caller.
        self._live = data
        return data[0] if num == 1 else [row for row in data]


def credit_timer(name: str, seconds: float, metric_cls=None, **metric_kwargs: Any) -> None:
    """Account ``seconds`` to a named timer without entering its context.

    The overlapped collector cannot use ``with timer(...)`` — the caller
    thread's ``timer.reset()`` at a log boundary races the collector's
    ``__exit__`` — so it accumulates wall-clock into the payload and the
    caller credits it here, on the thread that owns the timer registry.
    """
    from sheeprl_tpu.utils.metric import SumMetric
    from sheeprl_tpu.utils.timer import timer

    if timer.disabled:
        return
    timer(name, metric_cls or SumMetric, **metric_kwargs)  # registers if missing
    timer.timers[name].update(seconds)
    buf = timer.samples.get(name)
    if buf is None:
        from collections import deque

        buf = timer.samples[name] = deque(maxlen=timer.max_samples)
    buf.append(seconds)


class RolloutPayload:
    """One collected iteration, as handed from the collector to the trainer.

    ``data``/``next_obs`` are whatever ``pack_fn`` produced (device-placed
    arrays on both the sync and overlapped paths).  ``events`` holds
    deferred per-step episode records ``(policy_step, env_idx, reward,
    length)`` on the overlapped path (empty on the sync path, where the
    collector applies them inline exactly like the pre-pipeline loops).
    """

    __slots__ = (
        "iter_num",
        "data",
        "next_obs",
        "extras",
        "events",
        "env_seconds",
        "policy_step_end",
        "params_version",
        "host_refs",
    )

    def __init__(self, iter_num: int, data: Any = None, next_obs: Any = None):
        self.iter_num = iter_num
        self.data = data
        self.next_obs = next_obs
        self.extras: Dict[str, Any] = {}
        self.events: List[Tuple[int, int, float, float]] = []
        self.env_seconds: float = 0.0
        self.policy_step_end: int = 0
        self.params_version: int = -1
        # pack_fn parks its host-side upload sources here: CPU device_put
        # zero-copy aliases aligned numpy buffers WITHOUT keeping them
        # alive, so the arrays must outlive the update that reads them —
        # the payload does (see :meth:`PipelinedCollector.publish`)
        self.host_refs: List[Any] = []

    def apply_events(self, aggregator, runtime, log_level: int) -> None:
        """Apply deferred episode events on the caller's thread (overlap
        path); the sync path recorded nothing here."""
        if not self.events:
            return
        for policy_step, env_idx, ep_rew, ep_len in self.events:
            if log_level > 0:
                if aggregator and "Rewards/rew_avg" in aggregator:
                    aggregator.update("Rewards/rew_avg", ep_rew)
                if aggregator and "Game/ep_len_avg" in aggregator:
                    aggregator.update("Game/ep_len_avg", ep_len)
                runtime.print(f"Rank-0: policy_step={policy_step}, reward_env_{env_idx}={ep_rew}")
        if self.env_seconds > 0.0:
            from sheeprl_tpu.utils.metric import SumMetric

            credit_timer("Time/env_interaction_time", self.env_seconds, SumMetric, sync_on_compute=False)
            self.env_seconds = 0.0


class _ParamsBus:
    """Versioned params mailbox between the trainer and the collector.

    Keeps the last few published versions so the collector can adopt an
    EXACT version (the overlap path's fixed lag — see
    :meth:`PipelinedCollector._worker`): adopting "whatever is newest"
    would make which-params-collected-rollout-k a thread-timing race and
    overlapped runs irreproducible.
    """

    def __init__(self, initial_version: int, keep: int = 3):
        self._cond = threading.Condition()
        self._version = initial_version
        self._keep = int(keep)
        self._store: Dict[int, Any] = {}

    def publish(self, version: int, params: Any) -> None:
        with self._cond:
            if version > self._version:
                self._version = version
                self._store[version] = params
                for v in [v for v in self._store if v <= version - self._keep]:
                    del self._store[v]
                self._cond.notify_all()

    def latest(self) -> Tuple[int, Any]:
        with self._cond:
            return self._version, self._store.get(self._version)

    def take_exact(self, version: int, stop: threading.Event, poll_s: float = 0.05) -> Tuple[bool, Any]:
        """Block until ``version`` is published, return ``(True, params)``
        and prune strictly older versions; ``(False, None)`` on ``stop``
        or when ``version`` predates every publish (warmup: the player
        keeps its initial weights)."""
        with self._cond:
            while version not in self._store:
                if self._version >= version or stop.is_set():
                    # warmup (nothing that old was ever stored) or shutdown
                    return False, None
                self._cond.wait(timeout=poll_s)
            params = self._store[version]
            for v in [v for v in self._store if v < version]:
                del self._store[v]
            return True, params


def detach_copy(tree: Any) -> Any:
    """Fresh, materialized (blocked-on) copies of every leaf.

    Use to break buffer aliasing with a tree that is about to enter the
    donated update chain: the coupled loops hand the player a detached
    copy of the INITIAL params before the collector thread starts —
    ``PPOPlayer.__init__``'s ``device_put`` is a no-op on a same-device
    tree, so without the copy the player's warmup rollouts read the very
    buffers update 1 donates, and a fast trainer overwrites them
    mid-rollout at a timing-dependent step."""
    import jax
    import jax.numpy as jnp

    return jax.block_until_ready(jax.tree_util.tree_map(jnp.copy, tree))


def _copy_tree_for_publish(params: Any) -> Any:
    """Fresh, MATERIALIZED device buffers for the published params.

    The train steps donate their params/opt-state inputs
    (``donate_argnums``), so the arrays the trainer publishes for
    iteration t become donated inputs when iteration t+1's update
    dispatches.  An async ``jnp.copy`` is not enough: the copy and the
    donating update are both runnable once update t finishes, and the XLA
    client may execute them concurrently — the copy then reads buffers
    the donated update is overwriting (observed as run-to-run weight
    divergence on the CPU backend).  ``block_until_ready`` pins the copy
    before ``publish`` returns; the wait equals update t's completion,
    which the serial loop paid anyway — env collection still overlaps on
    the collector thread.
    """
    return detach_copy(params)


class PipelinedCollector:
    """Iterator of (iter_num, :class:`RolloutPayload`) over training iterations.

    Parameters
    ----------
    collect_fn:
        ``collect_fn(iter_num, inline, key_fn) -> RolloutPayload`` — steps
        the envs for one iteration and returns the HOST-side rollout
        (``payload.data``/``next_obs`` as produced by the rollout buffer).
        ``inline`` is True on the sync path (apply episode events / timers
        directly, exactly like the pre-pipeline loops); ``key_fn`` is the
        per-step policy key source to use.
    pack_fn:
        ``pack_fn(payload) -> None`` — converts ``payload.data`` /
        ``payload.next_obs`` (and any extras) to device-placed arrays.
        Runs inline on the sync path and on the collector thread on the
        overlapped path, where the host->device upload of rollout t+1
        overlaps the training dispatch of rollout t.
    adopt_params_fn:
        Called by the collector (rollout boundaries only) with the newest
        published params; typically ``player.params = p``.
    overlap:
        False = sync fallback: everything runs inline on the caller's
        thread in the exact serial order (bit-exact with the pre-pipeline
        loops).  True = background collector thread.
    max_staleness:
        Fixed lag (in updates behind the serial schedule) of the params a
        rollout acts on; >= 1.  Also the staleness upper bound — the
        collector waits for the lagged version rather than racing ahead.
    """

    def __init__(
        self,
        runtime,
        collect_fn: Callable[[int, bool, Callable], RolloutPayload],
        pack_fn: Callable[[RolloutPayload], None],
        *,
        start_iter: int,
        total_iters: int,
        overlap: bool,
        seed: int = 0,
        adopt_params_fn: Optional[Callable[[Any], None]] = None,
        max_staleness: int = 1,
    ):
        if max_staleness < 1:
            raise ValueError(f"max_staleness must be >= 1, got {max_staleness}")
        self._runtime = runtime
        self._collect_fn = collect_fn
        self._pack_fn = pack_fn
        self._start_iter = int(start_iter)
        self._total_iters = int(total_iters)
        self.overlap = bool(overlap)
        self._adopt = adopt_params_fn
        self._max_staleness = int(max_staleness)
        self._bus = _ParamsBus(initial_version=self._start_iter - 1, keep=self._max_staleness + 2)
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._queue: "queue.Queue[RolloutPayload]" = queue.Queue(maxsize=1)
        self._keys = KeyStream(seed)
        self._iter = self._start_iter
        self.staleness_log: List[Tuple[int, int]] = []  # (iter_num, staleness)
        self._thread: Optional[threading.Thread] = None
        if self.overlap and self._total_iters >= self._start_iter:
            self._thread = threading.Thread(
                target=self._worker, name="sheeprl-collector", daemon=True
            )
            from sheeprl_tpu.analysis.sanitizers import leak_registry

            self._leak_token = leak_registry.register(
                "thread", "sheeprl-collector", self._thread, where="PipelinedCollector"
            )
            self._thread.start()

    # ------------------------------------------------------------- worker
    def _worker(self) -> None:
        try:
            for k in range(self._start_iter, self._total_iters + 1):
                # fixed-lag adoption: rollout k acts on EXACTLY the params
                # of iteration k - 1 - max_staleness (warmup: the initial
                # weights).  A "newest published" adoption would satisfy
                # the staleness bound too, but which version wins would be
                # a thread-timing race — fixed lag keeps overlapped runs
                # reproducible given their seed.
                target = k - 1 - self._max_staleness
                ok, params = self._bus.take_exact(target, self._stop)
                if self._stop.is_set():
                    return
                version = target if ok else self._start_iter - 1
                if ok and self._adopt is not None:
                    self._adopt(params)
                self.staleness_log.append((k, max(0, (k - 1) - version)))
                with flight.span("collect", round=k):
                    payload = self._collect_fn(k, False, self._keys)
                payload.params_version = version
                self._pack_fn(payload)
                while not self._stop.is_set():
                    try:
                        self._queue.put(payload, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
        except BaseException as e:  # surfaced on the caller's next __next__
            self._error = e
            self._stop.set()

    # ------------------------------------------------------------ iterator
    def __iter__(self):
        return self

    def __next__(self) -> Tuple[int, RolloutPayload]:
        if self._iter > self._total_iters:
            raise StopIteration
        k = self._iter
        if not self.overlap:
            version, params = self._bus.latest()
            if params is not None and self._adopt is not None:
                self._adopt(params)
            self.staleness_log.append((k, max(0, (k - 1) - version)))
            with flight.span("collect", round=k):
                payload = self._collect_fn(k, True, self._runtime.next_key)
            payload.params_version = version
            self._pack_fn(payload)
            self._iter += 1
            return k, payload
        while True:
            if self._error is not None:
                err, self._error = self._error, None
                raise err
            try:
                payload = self._queue.get(timeout=0.5)
                break
            except queue.Empty:
                if self._stop.is_set():
                    raise StopIteration from None
                continue
        assert payload.iter_num == k, f"pipeline out of order: got {payload.iter_num}, expected {k}"
        self._iter += 1
        return k, payload

    # ------------------------------------------------------------- trainer
    def publish(self, version: int, params: Any) -> None:
        """Publish iteration ``version``'s freshly-trained params for the
        collector to adopt at its next rollout boundary.  On the sync path
        this feeds the same adopt-at-boundary handoff (keeping the serial
        order: adopt happens at the top of the next __next__).

        INVARIANT: publish returns only after update ``version`` has
        COMPLETED on device (the overlap path blocks on the params copy,
        the sync path blocks on the params themselves).  The algo loops'
        ``pack_fn``s rely on this: host buffers that CPU ``device_put``
        zero-copy aliased (``payload.host_refs``) may be released once the
        payload that published ``version`` is dropped — without the
        barrier, freeing them mid-update lets the allocator hand their
        memory to the next rollout's pack, scribbling the tensors the
        in-flight update is reading.

        ``ppo.main`` waits for the update before it calls this, under a span
        that says so (``Time/update_wait``): the barrier here then returns at
        once and ``Time/publish`` holds the hand-over alone."""
        if self.overlap:
            params = _copy_tree_for_publish(params)
        else:
            import jax

            jax.block_until_ready(params)
        self._bus.publish(version, params)

    # ------------------------------------------------------------- teardown
    def close(self, timeout: float = 30.0) -> None:
        """Stop and join the collector thread (no-op on the sync path).
        Call before closing the envs — the thread may be mid-``env.step``."""
        self._stop.set()
        if self._thread is not None:
            # unblock a collector stuck on a full handoff queue
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():  # pragma: no cover - pathological env hang
                import warnings

                warnings.warn("PipelinedCollector: collector thread did not join within timeout")
            self._thread = None
            from sheeprl_tpu.analysis.sanitizers import leak_registry

            leak_registry.unregister(getattr(self, "_leak_token", None))
            self._leak_token = None

    @property
    def closed(self) -> bool:
        return self._thread is None or not self._thread.is_alive()

    def __enter__(self) -> "PipelinedCollector":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


class OnPolicyCollector:
    """Shared PPO/A2C rollout stepper (the bodies were copy-identical).

    Owns the carried env state (``next_obs``) and writes into ``rb``; one
    ``collect`` call steps ``cfg.algo.rollout_steps`` env steps and
    returns the host-side rollout payload.  On the sync path
    (``inline=True``) episode metrics/prints and the env-interaction timer
    run inline — the exact pre-pipeline behavior; on the overlapped path
    they are deferred through the payload (see module docstring).
    """

    def __init__(
        self,
        *,
        envs,
        player,
        rb,
        cfg,
        runtime,
        obs_keys,
        total_envs: int,
        world_size: int,
        aggregator=None,
        clip_rewards_fn: Optional[Callable] = None,
        policy_step: int = 0,
    ):
        self.envs = envs
        self.player = player
        self.rb = rb
        self.cfg = cfg
        self.runtime = runtime
        self.obs_keys = list(obs_keys)
        self.total_envs = int(total_envs)
        self.world_size = int(world_size)
        self.aggregator = aggregator
        self.clip_rewards_fn = clip_rewards_fn or (lambda r: r)
        self.policy_step = int(policy_step)
        self.next_obs = envs.reset(seed=cfg.seed)[0]
        self._step_data: Dict[str, np.ndarray] = {}

    def collect(self, iter_num: int, inline: bool, key_fn) -> RolloutPayload:
        from sheeprl_tpu.utils.metric import SumMetric
        from sheeprl_tpu.utils.timer import timer
        from sheeprl_tpu.utils.utils import start_async_host_copy

        cfg = self.cfg
        payload = RolloutPayload(iter_num)
        step_data = self._step_data
        next_obs_np = self.next_obs
        for _ in range(cfg.algo.rollout_steps):
            self.policy_step += cfg.env.num_envs * self.world_size
            t0 = None
            cm = (
                timer("Time/env_interaction_time", SumMetric, sync_on_compute=False)
                if inline
                else None
            )
            if cm is not None:
                cm.__enter__()
            else:
                t0 = time.perf_counter()
            try:
                flat_actions, real_actions, logprobs, values = self.player.get_actions(
                    next_obs_np, key_fn()
                )
                # overlap the three host fetches the buffer write needs with
                # the env step: only the action array is awaited here
                start_async_host_copy(flat_actions, logprobs, values)
                real_actions_np = np.asarray(real_actions)
                obs, rewards, terminated, truncated, info = self.envs.step(
                    real_actions_np.reshape(self.envs.action_space.shape)
                )
                truncated_envs = np.nonzero(truncated)[0]
                if len(truncated_envs) > 0:
                    # fixed-shape bootstrap: substitute final obs rows, value
                    # the full env batch, pick the truncated entries
                    real_next_obs = {k: np.array(v) for k, v in obs.items()}
                    for env_idx in truncated_envs:
                        final = info["final_obs"][env_idx]
                        for k in self.obs_keys:
                            real_next_obs[k][env_idx] = final[k]
                    vals = np.asarray(self.player.get_values(real_next_obs))
                    rewards[truncated_envs] += cfg.algo.gamma * vals[truncated_envs].reshape(
                        rewards[truncated_envs].shape
                    )
                dones = (
                    np.logical_or(terminated, truncated)
                    .reshape(self.total_envs, 1)
                    .astype(np.uint8)
                )
                rewards = self.clip_rewards_fn(rewards).reshape(self.total_envs, 1).astype(np.float32)
            finally:
                if cm is not None:
                    cm.__exit__(None, None, None)
                else:
                    payload.env_seconds += time.perf_counter() - t0

            for k in self.obs_keys:
                step_data[k] = next_obs_np[k][np.newaxis]
            step_data["dones"] = dones[np.newaxis]
            step_data["values"] = np.asarray(values)[np.newaxis]
            step_data["actions"] = np.asarray(flat_actions)[np.newaxis]
            step_data["logprobs"] = np.asarray(logprobs)[np.newaxis]
            step_data["rewards"] = rewards[np.newaxis]
            self.rb.add(step_data, validate_args=cfg.buffer.validate_args)

            next_obs_np = obs

            if cfg.metric.log_level > 0 and "final_info" in info:
                ep = info["final_info"].get("episode")
                if ep is not None:
                    mask = info["final_info"]["_episode"]
                    for i in np.nonzero(mask)[0]:
                        ep_rew = float(ep["r"][i])
                        ep_len = float(ep["l"][i])
                        if inline:
                            if self.aggregator and "Rewards/rew_avg" in self.aggregator:
                                self.aggregator.update("Rewards/rew_avg", ep_rew)
                            if self.aggregator and "Game/ep_len_avg" in self.aggregator:
                                self.aggregator.update("Game/ep_len_avg", ep_len)
                            self.runtime.print(
                                f"Rank-0: policy_step={self.policy_step}, reward_env_{i}={ep_rew}"
                            )
                        else:
                            payload.events.append((self.policy_step, int(i), ep_rew, ep_len))

        self.next_obs = next_obs_np
        payload.data = self.rb.to_arrays()
        payload.next_obs = next_obs_np
        payload.policy_step_end = self.policy_step
        return payload
