"""PPO decoupled — N CPU players fanning rollouts into one TPU learner.

Counterpart of reference sheeprl/algos/ppo/ppo_decoupled.py (player:32,
trainer:368, main:623), generalized from the reference's 1 player x N DDP
trainers into the IMPALA/SEED-RL shape a TPU pod wants (Espeholt et al.,
2018; 2020): ``algo.num_players`` actor processes stream rollout shards
into ONE centralized learner over a pluggable transport
(``algo.decoupled_transport = queue | shm | tcp``, see
``sheeprl_tpu/parallel/transport.py``).

Topology:

- the TRAINER is the main process: it owns the accelerator mesh and runs
  the same single-jit PPO update as the coupled path; each round it
  assembles the global batch from per-player env shards in PLAYER-ID
  order (deterministic, arrival-order independent) and broadcasts the
  refreshed weights on a seq-numbered params channel;
- each PLAYER is a spawned subprocess pinned to the host CPU backend
  owning ``num_envs / num_players`` of the vectorized envs.  Player 0 is
  the LEAD: it owns the logger, the telemetry sink and the checkpoint
  files (the others are pure env-stepping workers);
- params staleness is a FIXED LAG (``algo.decoupled_params_lag``,
  PR 3's schedule across processes): rollout k acts on exactly the
  weights of update ``k - 1 - lag``, so players overlap their env
  stepping with the trainer's update without ever racing on "newest
  params win";
- resilience: a crashed player SHRINKS the fan-in — the trainer logs the
  shrink (it also rides telemetry under ``transport``), reassembles from
  the survivors (one XLA recompile for the smaller batch) and keeps
  training; only losing the LAST player aborts the run with the
  emergency dump the 1x1 topology always had.
"""

from __future__ import annotations

import copy
import multiprocessing as mp
import os
import queue as queue_mod
import time
import warnings
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.ppo.agent import PPOPlayer, build_agent
from sheeprl_tpu.algos.ppo.ppo import build_ppo_optimizer, make_update_fn
from sheeprl_tpu.algos.ppo.utils import prepare_obs, test
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.obs import fleet as obs_fleet
from sheeprl_tpu.obs import flight, setup_observability, trace_scope
from sheeprl_tpu.obs import ledger as obs_ledger
from sheeprl_tpu.parallel.transport import (
    FanIn,
    HeartbeatSender,
    JOIN_TAG,
    ParamsFollower,
    assemble_shards_padded,
    make_transport,
    split_envs,
    transport_setting,
)
from sheeprl_tpu.parallel.wire import OverlappedSender, wire_setting
from sheeprl_tpu.resilience.integrity import params_digest_fn
from sheeprl_tpu.resilience import (
    CheckpointManager,
    PeerDiedError,
    PreemptionHandler,
    child_alive,
    hard_exit_point,
    parent_alive,
    restore_like,
)
from sheeprl_tpu.utils.callback import load_checkpoint
from sheeprl_tpu.utils.env import make_env, resolve_env_backend
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.optim import restore_opt_states
from sheeprl_tpu.utils.utils import (
    device_get_metrics,
    polynomial_decay,
    save_configs,
    start_async_host_copy,
)

# generous IPC timeout: the first trainer reply waits on a fresh XLA
# compile of the full update (~20-40s on TPU)
_QUEUE_TIMEOUT_S = 600.0


def _np_tree(tree: Any) -> Any:
    """Pytree -> host numpy (the transport format)."""
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


def _flat_leaves(tree: Any):
    """Ordered ``(name, ndarray)`` pairs for transport shipping; the
    receiver rebuilds with its OWN treedef (both processes build the same
    agent)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return [(str(i), np.asarray(leaf)) for i, leaf in enumerate(leaves)]


def _unflat_leaves(treedef, payload: Dict[str, np.ndarray]) -> Any:
    """Inverse of :func:`_flat_leaves` (payload preserves pack order)."""
    return jax.tree_util.tree_unflatten(treedef, list(payload.values()))


def decoupled_knobs(cfg) -> Dict[str, Any]:
    """The fan-in configuration surface, resolved with defaults (shared
    with sac_decoupled)."""
    from sheeprl_tpu.resilience.supervisor import supervisor_knobs

    from sheeprl_tpu.resilience.integrity import integrity_setting

    lag = int(cfg.algo.get("decoupled_params_lag", 1))
    vt = cfg.algo.get("vtrace", None) or {}
    vtrace_on = bool(vt.get("enabled", False))
    supervisor = supervisor_knobs(cfg)
    # soft-lag mode: players adopt the NEWEST available params instead of
    # blocking for the exact fixed-lag target.  Implied by V-trace (the
    # learner corrects variable staleness) and by supervision (a rejoined
    # player resyncs its round clock off the broadcasts); max_lag is the
    # soft bound past which a player still blocks.
    soft_lag = vtrace_on or supervisor["enabled"]
    max_lag = int(vt.get("max_lag", 4)) if vtrace_on else lag
    wire_format = wire_setting(cfg)
    # params_digest_device=null follows the wire format: v2 broadcasts
    # compute the digest once on device (the PR-14 path) so the frame
    # ships without re-staging; v1 keeps the host walk default
    pdd = cfg.algo.get("params_digest_device", None)
    if pdd is None:
        pdd = wire_format == "v2"
    return {
        "backend": transport_setting(cfg),
        "num_players": int(cfg.algo.get("num_players", 1)),
        "lag": lag,
        "vtrace": vtrace_on,
        "soft_lag": soft_lag,
        "max_lag": max_lag,
        "supervisor": supervisor,
        # peer-death polling cadence + protocol-wait ceiling (PR-2's
        # hard-coded constants, now configurable)
        "liveness_interval": float(cfg.algo.get("liveness_interval", 0.5)),
        "liveness_timeout": float(cfg.algo.get("liveness_timeout", _QUEUE_TIMEOUT_S)),
        # a player may have up to lag+1 unacked shards in flight (soft
        # mode: up to max_lag+1)
        "window": max(2, int(cfg.algo.get("transport_window", 0)) or max(lag, max_lag) + 1),
        "host": str(cfg.algo.get("tcp_host", "127.0.0.1")),
        "port": int(cfg.algo.get("tcp_port", 0)),
        "compress_min": 65536 if bool(cfg.algo.get("tcp_compress", False)) else 0,
        # end-to-end data-integrity guard (resilience/integrity.py):
        # off = undecorated pre-integrity transport, crc = checksummed
        # frames on every backend, digest = crc + content-digest-verified
        # params adoption
        "integrity": integrity_setting(cfg),
        # batched device digest for params broadcasts (integrity.py
        # stream_digest_batched): one cached jit dispatch per message
        # instead of the per-leaf host CRC walk — pays when the leaves
        # are device-resident or numerous; both ends gate on this knob
        "params_digest_device": bool(pdd),
        # tcp length-prefix sanity cap (a corrupted prefix must not turn
        # into a multi-GB allocation)
        "max_frame_bytes": int(cfg.algo.get("tcp_max_frame_mb", 1024)) << 20,
        # fleet flight recorder (obs/flight.py): off constructs the
        # undecorated channel classes, sampled/full the traced variants
        "tracing": flight.tracing_setting(cfg),
        # transport wire format (parallel/wire.py): v1 = the bit-exact
        # pickled path, v2 = cached-table scatter-gather frames with
        # coalescing and the players' overlapped send pipeline
        "wire_format": wire_format,
        "coalesce_ms": float(cfg.algo.get("wire_coalesce_ms", 2.0)),
    }


def _player_loop(
    cfg,
    spec,
    state_counters,
    world_size: int,
    env_offset: int,
    n_local_envs: int,
    join: bool = False,
    infer_spec=None,
) -> None:
    """Player process body (reference ppo_decoupled.py:32-365).

    Runs on the host CPU backend (the parent exports JAX_PLATFORMS=cpu
    around the spawn): owns its SHARD of the envs; player 0 (the lead)
    additionally owns the logger, telemetry and checkpoint files.

    ``join=True`` is the supervised-restart path: instead of the startup
    ``init`` round the player announces itself with a ``join`` frame and
    syncs its round clock + weights off the trainer's ``assign`` reply,
    then keeps itself synced off the params broadcasts (a joiner that
    boots slowly fast-forwards instead of falling behind forever).

    ``infer_spec`` (``algo.inference=remote``) is a SECOND channel to the
    trainer-side InferenceServer: actions come from the centralized
    policy through the client failure envelope (deadline/retry/hedge/
    breaker), with THIS player's policy — still following the params
    broadcast exactly as in local mode — as the breaker's warm fallback.
    """
    import gymnasium as gym
    from gymnasium.vector import AsyncVectorEnv, AutoresetMode, SyncVectorEnv

    from sheeprl_tpu.cli import install_stack_dumper
    from sheeprl_tpu.parallel.mesh import MeshRuntime

    player_id = spec.player_id
    lead = player_id == 0
    knobs = decoupled_knobs(cfg)
    install_stack_dumper(suffix=f".player{player_id}")

    if cfg.metric.log_level == 0 or not lead:
        MetricAggregator.disabled = True
        timer.disabled = True
    if cfg.metric.get("disable_timer", False):
        timer.disabled = True
    # per-process flight recorder: EVERY player records its own stream
    # (obs.report merges them); must precede setup_observability so the
    # lead's recorder carries the player role, not "main"
    flight.configure_from_cfg(cfg, role=f"player{player_id}")
    # live metrics plane (ISSUE 15): every player serves its own
    # /metrics + /status and piggybacks a compact summary on the data
    # frames it already ships (the lead's /status shows the whole fleet)
    live = obs_fleet.configure_from_cfg(cfg, role=f"player{player_id}")
    # time ledger (ISSUE 16): this player's wall-clock decomposition,
    # fed by the same span call sites the flight recorder uses
    obs_ledger.configure_from_cfg(cfg, role=f"player{player_id}")

    runtime = MeshRuntime(devices=1, accelerator="cpu", precision=cfg.fabric.precision)
    runtime.launch()
    # player 0 keeps the exact 1x1 stream; siblings fork theirs by id
    runtime.seed_everything(cfg.seed + player_id)

    logger = get_logger(runtime, cfg) if lead else None
    if lead:
        log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name)
        runtime.print(f"Log dir: {log_dir}")
    else:
        # non-lead players own no run dir; memmap buffers (if any) land in
        # a per-player scratch dir next to the run root
        log_dir = os.path.join(str(cfg.root_dir), str(cfg.run_name), f"player_{player_id}")
    observability = setup_observability(runtime, cfg, log_dir if lead else None, logger=logger)
    if logger:
        logger.log_hyperparams(cfg)

    total_envs = int(cfg.env.num_envs)
    if resolve_env_backend(cfg) == "jax":
        # device-resident envs behind the same gymnasium vector API: the
        # composed-fleet topology (ISSUE 16 superbench) — jax players ×
        # fan-in × sharded trainer.  Each player owns its env shard.
        from sheeprl_tpu.envs.jax import JaxVectorEnv
        from sheeprl_tpu.utils.env import make_jax_env_from_cfg

        max_steps = cfg.env.max_episode_steps if cfg.env.get("max_episode_steps") else None
        envs = JaxVectorEnv(
            make_jax_env_from_cfg(cfg),
            n_local_envs,
            seed=cfg.seed + env_offset,
            max_episode_steps=max_steps,
        )
    else:
        thunks = [
            make_env(cfg, cfg.seed + env_offset + i, 0, log_dir, "train", vector_env_idx=env_offset + i)
            for i in range(n_local_envs)
        ]
        envs = (
            SyncVectorEnv(thunks, autoreset_mode=AutoresetMode.SAME_STEP)
            if cfg.env.sync_env
            else AsyncVectorEnv(thunks, context="spawn", autoreset_mode=AutoresetMode.SAME_STEP)
        )
    observation_space = envs.single_observation_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    cnn_keys = cfg.algo.cnn_keys.encoder
    mlp_keys = cfg.algo.mlp_keys.encoder
    obs_keys = cnn_keys + mlp_keys
    if obs_keys == []:
        raise RuntimeError("Specify at least one of `cnn_keys.encoder` or `mlp_keys.encoder`")

    is_continuous = isinstance(envs.single_action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(envs.single_action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        envs.single_action_space.shape
        if is_continuous
        else (envs.single_action_space.nvec.tolist() if is_multidiscrete else [envs.single_action_space.n])
    )
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)

    # one duplex channel to the trainer over the configured backend
    channel = spec.player_channel(peer_alive=parent_alive, who="trainer")
    timeout_s = knobs["liveness_timeout"]
    # supervised pools get a liveness beacon so the trainer can tell
    # "slow" from "silent" even without a process handle
    heartbeat = (
        HeartbeatSender(channel, interval=max(2 * knobs["liveness_interval"], 1.0))
        if knobs["supervisor"]["enabled"]
        else None
    )
    # wire-format v2: the data shard goes through the overlapped
    # device→wire pipeline — submit() snapshots inline, the sampled-CRC
    # digest and the socket write run on the pipeline thread while this
    # process is already collecting the next rollout.  Anything that must
    # order after the shard (checkpoint barrier, stop frame, direct sends
    # on this channel) flushes first.
    ov_sender = OverlappedSender(channel) if knobs["wire_format"] == "v2" else None

    # hand the agent blueprint to the trainer (reference broadcasts
    # agent_args from the player, :117); every player sends one so the
    # trainer can proceed from whichever subset survives startup.  A
    # supervised RESTART announces itself with a join frame instead and
    # syncs its round clock off the trainer's assign reply below.
    channel.send(JOIN_TAG if join else "init", extra=(observation_space, actions_dim, is_continuous))

    # inference-only agent; weights arrive on the params broadcast
    module, params = build_agent(runtime, actions_dim, is_continuous, cfg, observation_space)
    params_treedef = jax.tree_util.tree_structure(params)

    start_iter, policy_step, last_log, last_checkpoint = state_counters
    policy_steps_per_iter = int(cfg.env.num_envs * cfg.algo.rollout_steps)
    params_floor = start_iter - 1  # seq of the initial broadcast to wait for
    if join:
        # the assign reply carries (resume round, seq of the params frame
        # the trainer ships this channel right after); counters are global
        # functions of the round clock, so everything local re-derives
        deadline = time.monotonic() + timeout_s
        while True:
            frame = channel.recv(timeout=max(deadline - time.monotonic(), 0.01))
            if frame.tag == "assign":
                break
            frame.release()
        resume_iter, params_floor = int(frame.extra[0]), int(frame.extra[1])
        frame.release()
        start_iter = max(start_iter, resume_iter)
        policy_step = (start_iter - 1) * policy_steps_per_iter
        last_log = policy_step  # a rejoined lead restarts its cadences
        last_checkpoint = policy_step

    train_step = 0
    last_train = 0
    train_time_window = 0.0  # trainer-side seconds accumulated since last log
    trainer_compiles = None  # trainer-side XLA compile count (rides the params frames)
    latest_info_scalars: Dict[str, Any] = {}
    latest_transport_stats = None
    latest_train_metrics: Dict[str, Any] = {}
    latest_opt_np = None
    lead_health = None  # lead-side checkpoint health tagger (bound below)
    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(dict(cfg.metric.aggregator))

    def _apply_params_extra(frame) -> None:
        """Account a params frame's piggybacked trainer state (lead only:
        metrics, opt-state for checkpoints, info scalars, transport
        stats).  Safe pre-release — values are scalars/small trees."""
        nonlocal train_step, train_time_window, trainer_compiles
        nonlocal latest_info_scalars, latest_transport_stats, latest_train_metrics, latest_opt_np
        train_step += 1
        if not lead or not frame.extra:
            return
        # slot 4 (when present) is the params content digest — consumed
        # by the follower's verification, not by the accounting here
        train_metrics, opt_np, info_scalars, transport_stats = frame.extra[:4]
        latest_train_metrics = train_metrics or {}
        if opt_np is not None:
            latest_opt_np = opt_np
        latest_info_scalars = dict(info_scalars or {})
        if transport_stats is not None:
            latest_transport_stats = transport_stats
            if lead_health is not None:
                # the trainer's sentinel verdicts ride the broadcast; fold
                # them into the lead's good/quarantine checkpoint tagging
                lead_health.apply_remote(transport_stats.get("health"))
        train_time_window += latest_info_scalars.pop("train_time", 0.0)
        trainer_compiles = latest_info_scalars.pop("trainer_compiles", trainer_compiles)
        if aggregator and not aggregator.disabled:
            for k, v in latest_train_metrics.items():
                aggregator.update(k, v)

    follower = ParamsFollower(
        channel,
        lag=knobs["lag"],
        initial_seq=params_floor - 1,
        timeout=timeout_s,
        on_stale=_apply_params_extra,
        digest_slot=4 if knobs["integrity"] == "digest" else None,
        digest_fn=params_digest_fn(
            knobs["integrity"] == "digest", knobs["params_digest_device"]
        ),
    )

    def _adopt(frame) -> Any:
        """Copy a params frame out of the transport buffers and hand the
        numpy tree straight to the setter: jnp.asarray here would place
        the fresh params on the DEFAULT backend first and the setter's
        transfer to the host-CPU player would then copy every leaf
        twice."""
        new_params = _unflat_leaves(params_treedef, frame.arrays_copy())
        _apply_params_extra(frame)
        frame.release()
        player.params = new_params
        return new_params

    def _die_with_dump(e: PeerDiedError, policy_step_now: int, iter_now: int):
        """A dead trainer surfaces in ~a second as a final emergency
        checkpoint + a clear error instead of the full timeout hang."""
        path = None
        if lead and ckpt_mgr is not None:
            path = ckpt_mgr.emergency_dump(
                policy_step_now,
                {
                    "agent": player.params,
                    "iter_num": iter_now * world_size,
                    "policy_step": policy_step_now,
                },
            )
        raise RuntimeError(
            f"decoupled trainer process died at policy_step={policy_step_now}; "
            f"the player's last-known weights were dumped to {path} "
            "(partial state: resume from the last regular ckpt_*.ckpt instead)"
        ) from e

    # initial weights (the trainer broadcasts seq = start_iter - 1; a
    # joiner waits for AT LEAST the seq its assign reply named — a net
    # drop mid-handshake can replace the directed frame with the replay
    # of a newer broadcast); nothing to dump yet if the trainer dies here
    try:
        init_frame = (
            follower.advance_to_at_least(params_floor) if join else follower.advance_to(params_floor)
        )
    except PeerDiedError as e:
        raise RuntimeError(
            f"decoupled trainer process died before the initial params broadcast "
            f"reached player {player_id}"
        ) from e
    assert init_frame is not None
    train_step = 0  # the initial broadcast is not an update
    # the acting policy is pinned to the host CPU device explicitly, on
    # top of the JAX_PLATFORMS=cpu the parent exports around the spawn
    host_cpu = jax.local_devices(backend="cpu")[0]
    player = PPOPlayer(
        module,
        _unflat_leaves(params_treedef, init_frame.arrays_copy()),
        lambda o: prepare_obs(o, cnn_keys=cnn_keys, num_envs=n_local_envs),
        device=host_cpu,
    )
    init_frame.release()

    # centralized inference (algo.inference=remote): actions come from the
    # trainer-side server through the client envelope; `acting` keeps the
    # local path LITERALLY the pre-serve call (bit-exactness contract)
    infer_client = None
    acting = player
    if infer_spec is not None:
        from sheeprl_tpu.serve import PPO_OUT_KEYS, InferenceClient, RemoteActor, inference_knobs

        ik = inference_knobs(cfg)
        infer_client = InferenceClient(
            infer_spec.player_channel(peer_alive=parent_alive, who="inference server"),
            player_id,
            request_timeout_s=ik["request_timeout_s"],
            max_retries=ik["max_retries"],
            backoff_base_s=ik["backoff_base_s"],
            hedge_s=ik["hedge_s"],
            breaker_threshold=ik["breaker_threshold"],
            breaker_cooldown_s=ik["breaker_cooldown_s"],
        )
        acting = RemoteActor(infer_client, player, obs_keys, PPO_OUT_KEYS)
        if lead:
            observability.serve_stats = infer_client.stats

    if lead:
        save_configs(cfg, log_dir)

    if cfg.buffer.size < cfg.algo.rollout_steps:
        raise ValueError(
            f"The size of the buffer ({cfg.buffer.size}) cannot be lower "
            f"than the rollout steps ({cfg.algo.rollout_steps})"
        )
    rb = ReplayBuffer(
        cfg.buffer.size,
        n_local_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{player_id}"),
        obs_keys=obs_keys,
    )

    # the lead owns the checkpoint files AND its own preemption handler
    # (the trainer forwards SIGTERM to every player; non-leads just stop)
    ckpt_mgr = (
        CheckpointManager(runtime, cfg, log_dir, observability=observability, last_checkpoint=last_checkpoint)
        if lead
        else None
    )
    if lead:
        from sheeprl_tpu.resilience.sentinel import TrainHealth, sentinel_setting

        lead_health = TrainHealth(runtime, sentinel_setting(cfg)).bind(ckpt_mgr=ckpt_mgr)
        if lead_health.enabled:
            observability.health_stats = lead_health.stats
        else:
            lead_health = None
    preemption = None if lead else PreemptionHandler().install()
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    if lead and cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_iter != 0:
        warnings.warn(
            f"metric.log_every ({cfg.metric.log_every}) is not a multiple of "
            f"policy_steps_per_iter ({policy_steps_per_iter}); metrics log at the next multiple."
        )

    step_data: Dict[str, np.ndarray] = {}
    next_obs_np = envs.reset(seed=cfg.seed + env_offset)[0]

    iter_num = start_iter - 1
    while iter_num < total_iters:
        iter_num += 1
        if knobs["soft_lag"] and follower.current_seq + 1 > iter_num:
            # resync: the broadcasts show the pool is rounds ahead of this
            # player (a joiner that booted slowly, or a player that lost
            # rounds to a reconnect) — fast-forward the clock instead of
            # shipping shards for rounds the trainer already closed
            iter_num = follower.current_seq + 1
            policy_step = (iter_num - 1) * policy_steps_per_iter
            if iter_num > total_iters:
                break
        observability.on_iteration(policy_step)
        hard_exit_point("player_exit", index=player_id)  # fault site: a player crash
        # params adoption: the strict path acts on EXACTLY the weights of
        # update k - 1 - lag (warmup: the initial broadcast); the soft
        # path (V-trace / supervised pools) adopts the newest available
        # and only blocks past the max_lag soft bound — the learner's
        # importance correction absorbs the variable staleness
        try:
            if knobs["soft_lag"]:
                frame = follower.adopt_newest(iter_num, knobs["max_lag"])
            else:
                frame = follower.params_for_round(iter_num)
        except PeerDiedError as e:
            _die_with_dump(e, policy_step, iter_num)
        new_params = _adopt(frame) if frame is not None else player.params

        collect_span = flight.span("collect", round=iter_num)
        collect_span.__enter__()
        for _ in range(cfg.algo.rollout_steps):
            # policy steps are GLOBAL (all players advance in lockstep
            # modulo the lag), so counters keep the 1x1 meaning
            policy_step += cfg.env.num_envs

            with timer("Time/env_interaction_time", SumMetric, sync_on_compute=False):
                flat_actions, real_actions, logprobs, values = acting.get_actions(
                    next_obs_np, runtime.next_key()
                )
                # only the action array is awaited before the env step; the
                # other fetches ride under the env's wall-clock
                start_async_host_copy(flat_actions, logprobs, values)
                real_actions_np = np.asarray(real_actions)
                obs, rewards, terminated, truncated, info = envs.step(
                    real_actions_np.reshape(envs.action_space.shape)
                )
                truncated_envs = np.nonzero(truncated)[0]
                if len(truncated_envs) > 0:
                    real_next_obs = {k: np.array(v) for k, v in obs.items()}
                    for env_idx in truncated_envs:
                        final = info["final_obs"][env_idx]
                        for k in obs_keys:
                            real_next_obs[k][env_idx] = final[k]
                    vals = np.asarray(player.get_values(real_next_obs))
                    rewards[truncated_envs] += cfg.algo.gamma * vals[truncated_envs].reshape(
                        rewards[truncated_envs].shape
                    )
                dones = np.logical_or(terminated, truncated).reshape(n_local_envs, 1).astype(np.uint8)
                rewards = clip_rewards_fn(rewards).reshape(n_local_envs, 1).astype(np.float32)

            for k in obs_keys:
                step_data[k] = next_obs_np[k][np.newaxis]
            step_data["dones"] = dones[np.newaxis]
            step_data["values"] = np.asarray(values)[np.newaxis]
            step_data["actions"] = np.asarray(flat_actions)[np.newaxis]
            step_data["logprobs"] = np.asarray(logprobs)[np.newaxis]
            step_data["rewards"] = rewards[np.newaxis]
            rb.add(step_data, validate_args=cfg.buffer.validate_args)

            next_obs_np = obs

            if lead and cfg.metric.log_level > 0 and "final_info" in info:
                ep = info["final_info"].get("episode")
                if ep is not None:
                    for i in np.nonzero(info["final_info"]["_episode"])[0]:
                        ep_rew = float(ep["r"][i])
                        ep_len = float(ep["l"][i])
                        if aggregator and "Rewards/rew_avg" in aggregator:
                            aggregator.update("Rewards/rew_avg", ep_rew)
                        if aggregator and "Game/ep_len_avg" in aggregator:
                            aggregator.update("Game/ep_len_avg", ep_len)
                        runtime.print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep_rew}")

        collect_span.__exit__(None, None, None)
        # --------------------------------------------- ship the shard
        # preemption rides the cadence: a pending SIGTERM makes
        # should_checkpoint True, so this shard also requests the trainer
        # state needed for a full (resumable) emergency checkpoint
        need_ckpt = (
            ckpt_mgr.should_checkpoint(policy_step, is_last=iter_num == total_iters) if lead else False
        )
        local_data = {k: np.asarray(v) for k, v in rb.to_arrays().items()}
        arrays = [(f"d/{k}", v) for k, v in local_data.items()] + [
            (f"o/{k}", np.asarray(next_obs_np[k])) for k in obs_keys
        ]
        try:
            with trace_scope("ipc_send_shard"), flight.span("data_send", round=iter_num):
                # extra carries the BEHAVIOR-policy version this shard
                # acted with (the trainer's V-trace correction + lag
                # telemetry key off it) and, when the live plane is on,
                # this player's compact metrics summary (ISSUE 15).
                # data_send feeds the ledger's transport bucket — credit
                # stalls on a slow trainer surface here.
                send_extra = (
                    need_ckpt,
                    follower.current_seq,
                    live.beat(policy_step) if live is not None else None,
                )
                if ov_sender is not None:
                    # stage 1 (snapshot) runs here; stages 2-3 (digest +
                    # socket write) overlap the next collect.  A failed
                    # prior send re-raises from this submit.
                    ov_sender.submit("data", arrays, extra=send_extra, seq=iter_num, timeout=timeout_s)
                else:
                    channel.send("data", arrays=arrays, extra=send_extra, seq=iter_num, timeout=timeout_s)
        except PeerDiedError as e:
            _die_with_dump(e, policy_step, iter_num)

        # --------------------------------------------- checkpoint barrier
        # (lead only): the save needs the params + opt-state OF THIS ROUND,
        # so the fixed lag collapses for one round — named span: in a
        # profiler trace this wait IS the decoupled topology's comms/train
        # stall as seen from the player
        if need_ckpt:
            try:
                with trace_scope("ipc_wait_update"), flight.span("params_wait", round=iter_num):
                    if ov_sender is not None:
                        # the barrier orders after the shard: drain the
                        # pipeline so the trainer sees this round's data
                        ov_sender.flush(timeout=timeout_s)
                    frame = follower.advance_to(iter_num)
            except PeerDiedError as e:
                _die_with_dump(e, policy_step, iter_num)
            if frame is not None:
                new_params = _adopt(frame)
            # iter_num/batch_size stored in coupled units (scaled by the
            # trainer mesh size) so checkpoints swap between variants
            ckpt_mgr.checkpoint_now(
                policy_step=policy_step,
                state_fn=lambda: {
                    "agent": new_params,
                    "optimizer": latest_opt_np,
                    "iter_num": iter_num * world_size,
                    "batch_size": cfg.algo.per_rank_batch_size * world_size,
                    "last_log": last_log * world_size,
                    "last_checkpoint": ckpt_mgr.last_checkpoint * world_size,
                },
            )
            if ckpt_mgr.preempted:
                # the full emergency checkpoint is on disk (need_ckpt was
                # forced by the pending signal) — stop cleanly
                runtime.print(
                    f"Preemption signal: emergency checkpoint written, stopping at iter {iter_num}"
                )
                break
        if preemption is not None and preemption.preempted:
            # non-lead worker: nothing to save — drain out so the fan-in
            # shrinks cleanly instead of the trainer timing out on us
            break
        if not lead:
            # autoscaler shrink: the trainer retires this player by a
            # control frame on the params channel; drain out exactly like
            # a preempted non-lead (ship already done, stop frame below)
            retire_frame = follower.poll_control("retire")
            if retire_frame is not None:
                retire_frame.release()
                flight.fleet_event("player_retired", player=player_id, round=iter_num)
                break

        # --------------------------------------------- logging (lead-side)
        if lead and cfg.metric.log_level > 0 and logger:
            if latest_info_scalars:
                logger.log_metrics(latest_info_scalars, policy_step)
                latest_info_scalars = {}
            if policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters:
                extra = {"trainer_compiles": trainer_compiles}
                if latest_transport_stats is not None:
                    extra["transport"] = latest_transport_stats
                if knobs["integrity"] != "off":
                    # this process's boundary counters (params digest
                    # checks, frame verifications on the player side);
                    # the trainer's ride extra["transport"]["integrity"]
                    from sheeprl_tpu.resilience.integrity import integrity_stats

                    extra["integrity"] = integrity_stats().as_dict()
                    extra["integrity"]["params_digest_skips"] = follower.digest_skips
                observability.on_log(
                    policy_step,
                    train_step,
                    train_time_s=train_time_window,
                    extra=extra,
                )
                if aggregator and not aggregator.disabled:
                    logger.log_metrics(aggregator.compute(), policy_step)
                    aggregator.reset()
                if not timer.disabled:
                    timer_metrics = timer.compute()
                    if train_time_window > 0:
                        logger.log_metrics(
                            {"Time/sps_train": (train_step - last_train) / train_time_window},
                            policy_step,
                        )
                        train_time_window = 0.0
                    if timer_metrics.get("Time/env_interaction_time", 0) > 0:
                        logger.log_metrics(
                            {
                                "Time/sps_env_interaction": (
                                    (policy_step - last_log) * cfg.env.action_repeat
                                )
                                / timer_metrics["Time/env_interaction_time"]
                            },
                            policy_step,
                        )
                    timer.reset()
                last_log = policy_step
                last_train = train_step

    # drain the in-flight params broadcast before closing: the trainer
    # answers the final shard too, and a socket closed with UNREAD data
    # resets the connection — destroying the broadcast mid-send on the
    # trainer and the stop sentinel below with it
    if ov_sender is not None:
        try:
            ov_sender.flush(timeout=30.0)  # final shard out before the drain/stop
        except Exception:
            pass
    try:
        frame = follower.advance_to(iter_num, timeout=60.0)
        if frame is not None:
            _adopt(frame)
    except Exception:
        pass  # a dead/strangled trainer: nothing left to drain
    # shutdown sentinel (reference scatters -1, :344)
    try:
        channel.send("stop")
    except Exception:
        pass  # a dead trainer cannot receive it; exit anyway
    if heartbeat is not None:
        heartbeat.close()
    if infer_client is not None:
        infer_client.close()
    if ckpt_mgr is not None:
        ckpt_mgr.close()
    if preemption is not None:
        preemption.uninstall()
    envs.close()
    observability.close()
    if lead and cfg.algo.run_test:
        test_rew = test(player, runtime, cfg, log_dir)
        if logger:
            logger.log_metrics({"Test/cumulative_reward": test_rew}, policy_step)
    if logger:
        logger.finalize()
    if ov_sender is not None:
        ov_sender.close()
    channel.close()
    flight.close_recorder()
    obs_fleet.close_live()


def spawn_players(
    cfg, runtime, ctx, target, extra_args=(), knobs=None, with_inference=False, start_players=None
):
    """Create the transport + spawn ``num_players`` player processes
    pinned to the host CPU backend (shared with sac_decoupled).

    ``with_inference=True`` (``algo.inference=remote``) additionally
    builds a SECOND transport of the same backend for the inference
    service and hands each player its spec (trailing ``(join=False,
    infer_spec)`` positionals on the player-loop signature).

    ``start_players`` (autoscaler: ``algo.autoscaler.min_players``)
    starts the pool BELOW its configured size: the transport, env
    shards and specs are built for all ``num_players`` slots, but only
    the first ``start_players`` processes launch — the vacant slots are
    grown into later via :meth:`PlayerSupervisor.spawn_player` (the
    fixed-width padded batch assembly means a vacant slot is just a
    masked column, never a retrace).  The lead (pid 0) always starts.

    Returns ``(hub, fanin_channels, procs, env_shards, infer_hub)``
    (``infer_hub`` is None without inference).
    """
    knobs = knobs or decoupled_knobs(cfg)
    num_players = knobs["num_players"]
    start = num_players if start_players is None else max(1, min(int(start_players), num_players))
    total_envs = int(cfg.env.num_envs)
    env_shards = split_envs(total_envs, num_players)
    hub, specs = make_transport(
        ctx,
        knobs["backend"],
        num_players,
        window=knobs["window"],
        compress_min=knobs["compress_min"],
        host=knobs["host"],
        port=knobs["port"],
        poll_s=knobs["liveness_interval"],
        integrity=knobs["integrity"],
        max_frame_bytes=knobs["max_frame_bytes"],
        tracing=knobs["tracing"],
        wire_format=knobs["wire_format"],
        coalesce_ms=knobs["coalesce_ms"],
    )
    infer_hub = infer_specs = None
    if with_inference:
        # a deeper window than the rollout fan-in: retries + hedges can put
        # several small frames in flight per player (port 0: the inference
        # listener never collides with the configured rollout port)
        infer_hub, infer_specs = make_transport(
            ctx,
            knobs["backend"],
            num_players,
            window=max(4, knobs["window"]),
            compress_min=knobs["compress_min"],
            host=knobs["host"],
            port=0,
            poll_s=knobs["liveness_interval"],
            integrity=knobs["integrity"],
            max_frame_bytes=knobs["max_frame_bytes"],
            tracing=knobs["tracing"],
            wire_format=knobs["wire_format"],
            coalesce_ms=knobs["coalesce_ms"],
        )
    procs = []
    # the env copies the parent's environ at start, so the override only
    # affects the children
    saved_platform = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        for pid, (offset, count) in enumerate(env_shards):
            if pid >= start:
                break  # vacant slot: the autoscaler grows into it later
            args = (cfg, specs[pid]) + tuple(extra_args) + (offset, count)
            if infer_specs is not None:
                args += (False, infer_specs[pid])
            proc = ctx.Process(target=target, args=args, daemon=False)
            proc.start()
            procs.append(proc)
    finally:
        if saved_platform is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = saved_platform

    channels = {}
    for pid, proc in enumerate(procs):
        ch = hub.channel(pid, timeout=_QUEUE_TIMEOUT_S, peer_alive=proc.is_alive)
        ch.set_peer(
            child_alive(proc),
            f"player[{pid}]",
            detail_fn=lambda proc=proc: f"exitcode={proc.exitcode}",
        )
        channels[pid] = ch
    return hub, channels, procs, env_shards, infer_hub


@register_algorithm(decoupled=True)
def main(runtime, cfg: Dict[str, Any]):
    """Trainer process body + player spawn (reference ppo_decoupled.py:368-621).

    The trainer never touches an env: it assembles each round's global
    batch from the per-player shards, runs the coupled PPO single-jit
    update over the mesh, and broadcasts the refreshed weights."""
    if "minedojo" in str(cfg.env.wrapper.get("_target_", "")).lower():
        raise ValueError(
            "MineDojo is not currently supported by the PPO agent (no action-mask handling); "
            "use one of the Dreamer agents."
        )

    initial_ent_coef = copy.deepcopy(cfg.algo.ent_coef)
    initial_clip_coef = copy.deepcopy(cfg.algo.clip_coef)

    runtime.seed_everything(cfg.seed)
    knobs = decoupled_knobs(cfg)
    flight.configure_from_cfg(cfg, role="trainer")
    live = obs_fleet.configure_from_cfg(cfg, role="trainer")
    trainer_ledger = obs_ledger.configure_from_cfg(cfg, role="trainer")

    state = None
    if cfg.checkpoint.resume_from:
        state = load_checkpoint(cfg.checkpoint.resume_from)
        cfg.algo.per_rank_batch_size = state["batch_size"] // runtime.world_size

    start_iter = (state["iter_num"] // runtime.world_size) + 1 if state else 1
    policy_step = (
        (state["iter_num"] // runtime.world_size) * cfg.env.num_envs * cfg.algo.rollout_steps
        if state
        else 0
    )
    counters = (
        start_iter,
        policy_step,
        state["last_log"] // runtime.world_size if state else 0,
        state["last_checkpoint"] // runtime.world_size if state else 0,
    )

    from sheeprl_tpu.serve import inference_setting

    inference = inference_setting(cfg, knobs["num_players"])

    # elastic player pool (ROADMAP: serving/scale plane): the autoscaler
    # needs the supervisor's join machinery to actuate, and only makes
    # sense with a fan-out to flex
    from sheeprl_tpu.scale import Autoscaler, autoscaler_knobs

    ak = autoscaler_knobs(cfg)
    autoscale_on = (
        ak["enabled"] and knobs["supervisor"]["enabled"] and knobs["num_players"] > 1
    )

    ctx = mp.get_context("spawn")
    hub, channels, proc_list, env_shards, infer_hub = spawn_players(
        cfg,
        runtime,
        ctx,
        _player_loop,
        extra_args=(counters, runtime.world_size),
        knobs=knobs,
        with_inference=inference == "remote",
        start_players=ak["min_players"] if autoscale_on else None,
    )
    procs: Dict[int, Any] = dict(enumerate(proc_list))
    rollout_steps = int(cfg.algo.rollout_steps)
    steps_per_frame = {pid: count * rollout_steps for pid, (_, count) in enumerate(env_shards)}
    fanin = FanIn(channels, env_steps_per_frame=steps_per_frame)

    # a SIGTERM delivered to the trainer only (per-process preemption) is
    # forwarded to every player; the lead owns the checkpoint files and
    # runs the emergency-save path, the others drain out cleanly
    preemption = PreemptionHandler(forward_to=list(procs.values())).install()

    # elastic pool: the supervisor restarts dead players (with backoff,
    # under a restart budget) as JOIN-mode processes that re-man their
    # deterministic env shard at the current round
    supervisor = None
    serve_box: Dict[str, Any] = {"server": None}  # filled once the agent exists

    if knobs["supervisor"]["enabled"]:
        from sheeprl_tpu.resilience import PlayerSupervisor

        def _respawn_args(pid, spec):
            offset, count = env_shards[pid]
            args = (cfg, spec, counters, runtime.world_size, offset, count, True)
            if infer_hub is not None:
                # fresh inference endpoints for the replacement process; the
                # server re-attaches the rebuilt trainer-side channel
                ispec = infer_hub.respawn_spec(pid)
                if serve_box["server"] is not None:
                    serve_box["server"].attach(pid, infer_hub.channel(pid))
                args += (ispec,)
            return args

        supervisor = PlayerSupervisor(
            ctx,
            hub,
            fanin,
            _player_loop,
            _respawn_args,
            procs,
            restart_budget=knobs["supervisor"]["restart_budget"],
            backoff_base=knobs["supervisor"]["backoff_base"],
            backoff_max=knobs["supervisor"]["backoff_max"],
            heartbeat_timeout=knobs["supervisor"]["heartbeat_timeout"],
            steps_per_frame=steps_per_frame,
            preemption=preemption,
            join_timeout=knobs["liveness_timeout"],
        )

    def _dump_and_raise(e: PeerDiedError, what: str):
        """Every player died: final trainer dump + a clear error (the
        trainer owns no run dir, so the dump lands next to the run root)."""
        path = None
        try:
            from sheeprl_tpu.utils.ckpt_format import save_state

            dump_dir = os.path.join(str(cfg.root_dir), str(cfg.run_name))
            os.makedirs(dump_dir, exist_ok=True)
            path = save_state(
                os.path.join(dump_dir, "emergency_trainer_0.ckpt"),
                _np_tree({"agent": params, "optimizer": opt_state}),
            )
        except Exception:
            pass
        raise RuntimeError(
            f"decoupled player process died (all {knobs['num_players']} players gone: {e}) while "
            f"the trainer waited for a {what} message; trainer params/optimizer dumped to {path} "
            "(partial state: resume from the last regular ckpt_*.ckpt instead)"
        ) from e

    try:
        # agent blueprint: every live player greets; any one of them works
        try:
            _, init_frames = fanin.gather(timeout=_QUEUE_TIMEOUT_S, data_tag="init")
        except PeerDiedError as e:
            params = opt_state = None
            _dump_and_raise(e, "init")
        first = next(iter(init_frames.values()))
        observation_space, actions_dim, is_continuous = first.extra
        for f in init_frames.values():
            f.release()
        obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder

        module, params = build_agent(
            runtime,
            actions_dim,
            is_continuous,
            cfg,
            observation_space,
            state["agent"] if state else None,
        )
        params = runtime.replicate(runtime.to_param_dtype(params))
        tx = build_ppo_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm, runtime.precision)
        opt_state = (
            runtime.replicate(tx.init(params))
            if state is None
            else restore_opt_states(state["optimizer"], params, runtime.precision)
        )
        update_fn = make_update_fn(runtime, module, tx, cfg, obs_keys)
        # training health: the trainer owns the verdicts; the checkpoint
        # FILES live with the lead player, so rollback scans the run root
        # for the last good-tagged checkpoint (sidecar written by the lead)
        health = update_fn.health.bind(
            scan_root=str(cfg.root_dir), select=("agent", "optimizer")
        )

        # trainer-side recompile watch: the jitted update lives in THIS
        # process, so its retraces are invisible to the lead's telemetry
        # unless the count rides the params frames
        from sheeprl_tpu.obs import RecompileMonitor

        trainer_mon = RecompileMonitor(name="ppo_decoupled_trainer").install()

        # centralized inference: the server thread shares this process's
        # params (swap_params per round is a reference swap — the bucketed
        # traces never retrace) and serves the players' obs frames over
        # the second transport; a dead serving loop is respawned by the
        # ServeSupervisor in drain-recover mode under a restart budget
        serve_server = serve_sup = None
        ik = None
        if infer_hub is not None:
            from sheeprl_tpu.resilience import ServeSupervisor
            from sheeprl_tpu.serve import (
                build_server,
                inference_knobs,
                make_ppo_policy_fn,
                session_knobs,
            )

            ik = inference_knobs(cfg)
            # feedforward PPO has no recurrent state, so even with the
            # session knobs on this constructs the undecorated PR-8
            # server (build_server requires the session adapters) —
            # bit-exactness with the pre-session tree is structural
            serve_server = build_server(
                make_ppo_policy_fn(module, cfg.algo.cnn_keys.encoder),
                params,
                session=session_knobs(cfg),
                deadline_ms=ik["deadline_ms"],
                max_batch=ik["max_batch"],
                seed=cfg.seed + 1,
                name="ppo",
            )
            for pid, proc in procs.items():
                ch = infer_hub.channel(pid, timeout=_QUEUE_TIMEOUT_S, peer_alive=proc.is_alive)
                ch.set_peer(child_alive(proc), f"player[{pid}]")
                serve_server.attach(pid, ch)
            serve_server.start()
            serve_box["server"] = serve_server
            serve_sup = ServeSupervisor(
                serve_server,
                restart_budget=ik["restart_budget"],
                backoff_base=ik["restart_backoff_s"],
            )

        # player-pool autoscaler (the in-process serve flavor is
        # scale.pool.ServePool): measured gather-wait pressure + firing
        # alert NAMES in, supervisor spawn / retire orders + serve
        # batching capacity out — every decision is a typed flight event
        autoscaler = None
        if autoscale_on and supervisor is not None:
            autoscaler = Autoscaler(
                min_size=ak["min_players"],
                max_size=ak["max_players"] or knobs["num_players"],
                up_window_s=ak["up_window_s"],
                down_window_s=ak["down_window_s"],
                up_cooldown_s=ak["up_cooldown_s"],
                down_cooldown_s=ak["down_cooldown_s"],
                event_budget=ak["event_budget"],
                name="player_pool",
            )

        # params digest (algo.transport_integrity=digest): one content
        # digest per broadcast, computed from the SOURCE arrays on the
        # trainer and verified at every player's adoption — catches
        # corruption anywhere on the path, including copies the frame
        # checksum no longer covers
        digest_mode = knobs["integrity"] == "digest"
        _params_digest = params_digest_fn(digest_mode, knobs["params_digest_device"])

        # initial weights to every player (reference broadcast, :126)
        init_arrays = _flat_leaves(_np_tree(params))
        init_digest = _params_digest(init_arrays)
        fanin.broadcast(
            "params",
            arrays=init_arrays,
            seq=start_iter - 1,
            extra_fn=(lambda pid: (None, None, None, None, init_digest)) if digest_mode else None,
        )

        policy_steps_per_iter = int(cfg.env.num_envs * cfg.algo.rollout_steps)
        total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1

        lr0 = float(cfg.algo.optimizer.get("learning_rate", cfg.algo.optimizer.get("lr", 1e-3)))
        current_lr = lr0
        current_clip = float(cfg.algo.clip_coef)
        current_ent = float(cfg.algo.ent_coef)

        known_live = len(fanin.live)
        last_completed_seq = start_iter - 1

        def _on_control(pid, frame):
            """Join handshake: a supervised restart announces itself with
            a join frame; the reply is its round clock (skip the in-flight
            round) + the current weights (a joiner missed every earlier
            broadcast).  The env-shard assignment is implied by the pid —
            the same deterministic ``split_envs`` slot it held before."""
            if frame.tag == JOIN_TAG:
                frame.release()
                fanin.send_to(pid, "assign", extra=(last_completed_seq + 2, last_completed_seq))
                join_arrays = _flat_leaves(_np_tree(params))
                join_digest = _params_digest(join_arrays)
                fanin.send_to(
                    pid,
                    "params",
                    arrays=join_arrays,
                    seq=last_completed_seq,
                    extra=(None, None, None, None, join_digest) if digest_mode else (),
                )
            else:
                frame.release()

        while True:
            if supervisor is not None:
                supervisor.poll()
            if serve_sup is not None:
                serve_sup.poll()
            # named span: the trainer idling for the next fan-in round (the
            # inverse of the players' ipc_wait_update stall); its duration
            # is ALSO the autoscaler's pressure signal — a long wait means
            # the pool is too small for the learner, a near-zero wait
            # means shards are always ready (slack)
            t_gather = time.monotonic()
            try:
                with trace_scope("ipc_wait_rollout"), flight.span("fanin_wait"):
                    seq, frames = fanin.gather(timeout=_QUEUE_TIMEOUT_S, on_control=_on_control)
            except PeerDiedError as e:
                if supervisor is not None and supervisor.recoverable():
                    # the whole pool died at once but restarts are pending:
                    # stay alive, the joiners' frames will form a round
                    time.sleep(0.2)
                    continue
                _dump_and_raise(e, "rollout")
            except queue_mod.Empty:
                if supervisor is not None and (fanin.joining or supervisor.recoverable()):
                    continue
                raise
            gather_wait_s = time.monotonic() - t_gather
            if not frames:
                break  # every player stopped
            if len(fanin.live) != known_live:
                known_live = len(fanin.live)
                runtime.print(
                    f"elastic fan-in now {known_live} player(s) "
                    f"(dead: {sorted(fanin.dead)}, joining: {sorted(fanin.joining)}): "
                    "mask-padded batch keeps its shape, no retrace"
                )
            iter_num = seq
            need_ckpt = False
            for pid, frame in frames.items():
                extra = frame.extra or ()
                if pid == 0 and extra:
                    need_ckpt = bool(extra[0])
                if len(extra) > 1:
                    # behavior-policy version this shard acted with: the
                    # lag histogram is the V-trace soft-bound telemetry
                    fanin.note_lag(pid, (seq - 1) - int(extra[1]))
                if len(extra) > 2:
                    # the player's piggybacked live-metrics summary
                    fanin.note_summary(pid, extra[2])

            assembly_span = flight.span("batch_assembly", round=iter_num, shards=len(frames))
            assembly_span.__enter__()
            # per-player shard -> materialized arrays (the astype/copy
            # below frees the transport buffers right after)
            data_shards: Dict[int, Dict[str, np.ndarray]] = {}
            obs_shards: Dict[int, Dict[str, np.ndarray]] = {}
            for pid, frame in frames.items():
                data_shards[pid] = {
                    k[2:]: (v.astype(np.float32) if v.dtype not in (np.uint8,) else np.array(v))
                    for k, v in frame.arrays.items()
                    if k.startswith("d/")
                }
                obs_shards[pid] = {
                    k[2:]: np.array(v) for k, v in frame.arrays.items() if k.startswith("o/")
                }
                frame.release()
            # deterministic FIXED-WIDTH layout: each player's env columns
            # land at its split_envs offset, missing players' columns are
            # zero-filled and masked out of the losses — a pool shrink or
            # grow changes only the mask, never the shape, so the jitted
            # update is traced once and never recompiles on churn
            local_data, env_mask = assemble_shards_padded(data_shards, env_shards, axis=1)
            final_obs, _ = assemble_shards_padded(obs_shards, env_shards, axis=0)
            local_data["mask"] = np.ascontiguousarray(
                np.broadcast_to(env_mask[None, :, None], local_data["rewards"].shape).astype(
                    np.float32
                )
            )

            # env-axis sharding feeds each mesh device only its columns
            # (the shard_map update path consumes this layout); an
            # indivisible count stays unsharded (replicated fallback)
            if next(iter(local_data.values())).shape[1] % runtime.world_size == 0:
                local_data = runtime.shard_batch(local_data, axis=1)
                device_next_obs = runtime.shard_batch(dict(final_obs), axis=0)
            else:
                device_next_obs = {k: jnp.asarray(v) for k, v in final_obs.items()}

            assembly_span.__exit__(None, None, None)
            with timer("Time/train_time", SumMetric, sync_on_compute=cfg.metric.sync_on_compute), \
                    flight.span("train_dispatch", round=iter_num):
                params, opt_state, train_metrics = update_fn(
                    params,
                    opt_state,
                    local_data,
                    device_next_obs,
                    runtime.next_key(),
                    jnp.float32(current_clip),
                    jnp.float32(current_ent),
                    jnp.float32(current_lr),
                )
                train_metrics = device_get_metrics(train_metrics)

            rolled = health.tick()
            if rolled is not None:
                # rollback-to-last-good: restore, then the normal params
                # broadcast below ships the restored weights — every
                # player re-adopts through its ParamsFollower with no
                # special protocol round
                params = restore_like(params, rolled["agent"])
                opt_state = restore_like(opt_state, rolled["optimizer"])
                fanin.note_rollback(iter_num)

            info_scalars = {
                "Info/learning_rate": current_lr,
                "Info/clip_coef": current_clip,
                "Info/ent_coef": current_ent,
            }
            info_scalars["trainer_compiles"] = trainer_mon.compiles
            trainer_mon.mark_warmup_complete()  # first update done: further compiles are retraces
            if not timer.disabled:
                info_scalars["train_time"] = float(timer.compute().get("Time/train_time", 0.0))
                timer.reset()

            # annealing lives on the trainer (reference :528-540)
            if cfg.algo.anneal_lr:
                current_lr = polynomial_decay(
                    iter_num, initial=lr0, final=0.0, max_decay_steps=total_iters, power=1.0
                )
            if cfg.algo.anneal_clip_coef:
                current_clip = polynomial_decay(
                    iter_num, initial=initial_clip_coef, final=0.0,
                    max_decay_steps=total_iters, power=1.0,
                )
            if cfg.algo.anneal_ent_coef:
                current_ent = polynomial_decay(
                    iter_num, initial=initial_ent_coef, final=0.0,
                    max_decay_steps=total_iters, power=1.0,
                )

            if serve_server is not None:
                # the fresh weights serve the NEXT requests (between-batch
                # swap: zero dropped requests, zero retraces)
                serve_server.swap_params(params)

            if autoscaler is not None:
                # one control tick per round: classify this round's
                # measured gather wait (plus any firing pressure alerts)
                # and actuate through the SAME join machinery the
                # supervisor uses for failure recovery
                sig = supervisor.autoscale_signal()
                alert_pressure = sorted(
                    set(sig.get("alert_names") or ()) & set(ak["alert_pressure_names"])
                )
                pool_size = len(fanin.live) + len(fanin.joining)
                pressure = bool(alert_pressure) or gather_wait_s >= ak["gather_wait_pressure_s"]
                # never shrink while deaths are pending respawn: that is
                # churn, not slack — the supervisor owns that transition
                slack = (
                    gather_wait_s <= ak["gather_wait_slack_s"]
                    and not alert_pressure
                    and int(sig.get("pending_restarts", 0)) == 0
                )
                reason = f"gather_wait={gather_wait_s * 1e3:.1f}ms"
                if alert_pressure:
                    reason += " alerts=" + ",".join(alert_pressure)
                decision = autoscaler.observe(pool_size, pressure, slack, reason=reason)
                if decision is not None:
                    if decision["action"] == "grow":
                        for pid in range(knobs["num_players"]):
                            if pid in fanin.live or pid in fanin.joining:
                                continue
                            if supervisor.spawn_player(pid):
                                break
                    else:
                        victim = max((p for p in fanin.live if p != 0), default=None)
                        if victim is not None:
                            fanin.send_to(victim, "retire")
                    if serve_server is not None and ik is not None:
                        # serve batching capacity tracks the pool: fewer
                        # players need smaller max batches (bounded below
                        # so a minimum pool still serves)
                        npl = knobs["num_players"]
                        tgt = int(decision["target"])
                        serve_server.set_capacity(max(1, (ik["max_batch"] * tgt + npl - 1) // npl))

            opt_np = _np_tree(opt_state) if need_ckpt else None
            stats = fanin.stats(knobs["backend"])
            stats["events"] = fanin.events[-8:]
            if supervisor is not None:
                stats["supervisor"] = supervisor.stats()
            if autoscaler is not None:
                stats["autoscale"] = autoscaler.stats()
            if serve_server is not None:
                stats["serve"] = serve_server.stats()
                if serve_sup is not None:
                    stats["serve"]["supervisor"] = serve_sup.stats()
            if health.enabled:
                stats["health"] = health.stats()
            if knobs["integrity"] != "off":
                # the trainer process's boundary counters (data-frame
                # verifications, retransmit traffic): they reach the
                # lead's telemetry under transport.integrity
                from sheeprl_tpu.resilience.integrity import integrity_stats

                stats["integrity"] = integrity_stats().as_dict()
            if trainer_ledger is not None:
                # piggyback the trainer's time breakdown on the stats the
                # lead already logs: post-hoc readers get transport.where
                # without a trainer-side telemetry file
                stats["where"] = trainer_ledger.snapshot()
            if live is not None:
                # the trainer's own live plane: /status + alert rules see
                # the fleet view every round (the transport key is where
                # the health/lag/integrity/fleet stats live)
                trainer_record = {
                    "ts": time.time(),
                    "step": iter_num * policy_steps_per_iter,
                    "transport": stats,
                }
                if trainer_ledger is not None:
                    trainer_record["where"] = trainer_ledger.snapshot()
                live.observe(trainer_record)
            bcast_arrays = _flat_leaves(_np_tree(params))
            bcast_digest = _params_digest(bcast_arrays)
            fanin.broadcast(
                "params",
                arrays=bcast_arrays,
                seq=iter_num,
                extra_fn=lambda pid: (
                    train_metrics,
                    opt_np if pid == 0 else None,
                    info_scalars,
                    stats if pid == 0 else None,
                )
                + ((bcast_digest,) if digest_mode else ()),
            )
            last_completed_seq = iter_num
            hard_exit_point("trainer_exit")  # fault site: trainer crash after replying

        trainer_mon.uninstall()
        if supervisor is not None:
            supervisor.close()
        if serve_server is not None:
            # graceful drain: pending requests answered, then stop frames
            serve_server.close()
        # the lead still runs its test episode + logger shutdown after the
        # stop sentinel — give it ample time before the terminate fallback
        for proc in procs.values():
            proc.join(timeout=3600.0)
    finally:
        if supervisor is not None:
            supervisor.close()
        if serve_box.get("server") is not None:
            serve_box["server"].close(timeout=2.0)
        preemption.uninstall()
        fanin.close()
        hub.close()
        flight.close_recorder()
        obs_fleet.close_live()
        if infer_hub is not None:
            infer_hub.close()
        for proc in procs.values():
            if proc.is_alive():
                proc.terminate()
                proc.join()
