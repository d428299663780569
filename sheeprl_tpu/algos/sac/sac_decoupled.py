"""SAC decoupled — N CPU players fanning sampled batches into one TPU learner.

Counterpart of reference sheeprl/algos/sac/sac_decoupled.py (player:33,
trainer:356, main:548).  Same N-player fan-in as
``sheeprl_tpu.algos.ppo.ppo_decoupled`` (which see for the transport and
staleness machinery), with the off-policy twists of the reference:

- each PLAYER owns a shard of the envs AND of the replay buffer; every
  iteration past ``learning_starts`` the shared ``Ratio`` schedule (all
  players compute it on the same GLOBAL policy-step clock, so the
  per-round gradient-step count ``g`` agrees by construction) makes it
  sample ``g x batch_size/num_players`` transitions and ship them as
  update round ``u``'s shard;
- the trainer concatenates the per-player shards in player-id order into
  the ``(g, batch)`` layout, runs the coupled SAC ``lax.scan`` over the G
  gradient steps, and broadcasts refreshed ACTOR weights (seq = u) — the
  critics never act;
- the LEAD player (id 0) owns logger/telemetry/checkpoints; its
  ``ckpt_req`` control frame fetches the full agent + optimizer state on
  demand (reference on_checkpoint_player, :314);
- a crashed player shrinks the fan-in (smaller effective batch, one XLA
  recompile) instead of killing the run.
"""

from __future__ import annotations

import multiprocessing as mp
import time
import warnings
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.ppo.ppo_decoupled import (
    _QUEUE_TIMEOUT_S,
    _flat_leaves,
    _np_tree,
    _unflat_leaves,
    decoupled_knobs,
    spawn_players,
)
from sheeprl_tpu.algos.sac.agent import SACPlayer, build_agent
from sheeprl_tpu.algos.sac.sac import _make_optimizer, make_train_fn
from sheeprl_tpu.algos.sac.utils import prepare_obs, test
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
    assemble_shards,
    split_envs,
)
from sheeprl_tpu.parallel.wire import OverlappedSender
from sheeprl_tpu.replay import (
    ReplayServer,
    ReplayWriter,
    per_beta_schedule,
    rate_limiter_from_cfg,
    remote_replay_setting,
)
from sheeprl_tpu.resilience import (
    CheckpointManager,
    PeerDiedError,
    PreemptionHandler,
    hard_exit_point,
    parent_alive,
    restore_like,
)
from sheeprl_tpu.resilience.integrity import params_digest_fn
from sheeprl_tpu.utils.callback import load_checkpoint, restore_buffer
from sheeprl_tpu.utils.env import make_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import device_get_metrics, Ratio, save_configs
from sheeprl_tpu.optim import restore_opt_states


def _player_loop(
    cfg,
    spec,
    state_counters,
    ratio_state,
    world_size: int,
    env_offset: int,
    n_local_envs: int,
    join: bool = False,
    infer_spec=None,
) -> None:
    """Player process body (reference sac_decoupled.py:33-353).

    ``infer_spec`` (``algo.inference=remote``) routes acting through the
    trainer-side InferenceServer with this player's own actor — still
    adopting every params broadcast — as the breaker's local fallback."""
    if remote_replay_setting(cfg):
        # Reverb-style experience path: this player streams raw
        # transitions into the trainer-resident replay service instead of
        # sampling its own buffer shard (replay/service.py).  Centralized
        # inference is not wired on this path (the free-running trainer
        # has no between-rounds boundary to swap at) — see howto/serving.md.
        return _player_loop_remote(
            cfg, spec, state_counters, world_size, env_offset, n_local_envs, join=join
        )
    if join:
        raise RuntimeError(
            "supervised rejoin for sac_decoupled requires buffer.remote_replay=true "
            "(a classic player owns a buffer shard that dies with it)"
        )
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

    flight.configure_from_cfg(cfg, role=f"player{player_id}")
    live = obs_fleet.configure_from_cfg(cfg, role=f"player{player_id}")
    obs_ledger.configure_from_cfg(cfg, role=f"player{player_id}")
    runtime = MeshRuntime(devices=1, accelerator="cpu", precision=cfg.fabric.precision)
    runtime.launch()
    runtime.seed_everything(cfg.seed + player_id)

    logger = get_logger(runtime, cfg) if lead else None
    if lead:
        log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name)
        runtime.print(f"Log dir: {log_dir}")
    else:
        log_dir = os.path.join(str(cfg.root_dir), str(cfg.run_name), f"player_{player_id}")
    observability = setup_observability(runtime, cfg, log_dir if lead else None, logger=logger)
    if logger:
        logger.log_hyperparams(cfg)

    total_envs = int(cfg.env.num_envs)
    thunks = [
        make_env(cfg, cfg.seed + env_offset + i, 0, log_dir, "train", vector_env_idx=env_offset + i)
        for i in range(n_local_envs)
    ]
    envs = (
        SyncVectorEnv(thunks, autoreset_mode=AutoresetMode.SAME_STEP)
        if cfg.env.sync_env
        else AsyncVectorEnv(thunks, context="spawn", autoreset_mode=AutoresetMode.SAME_STEP)
    )
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the SAC agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.algo.mlp_keys.encoder) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")
    for k in cfg.algo.mlp_keys.encoder:
        if len(observation_space[k].shape) > 1:
            raise ValueError(
                f"Only vector observations are supported by SAC; key '{k}' has shape "
                f"{observation_space[k].shape}"
            )
    mlp_keys = list(cfg.algo.mlp_keys.encoder)

    channel = spec.player_channel(peer_alive=parent_alive, who="trainer")
    channel.send("init", extra=(observation_space, action_space))
    # wire-format v2: ship the sampled batch through the overlapped
    # device→wire pipeline (snapshot inline, digest + socket write on the
    # pipeline thread); flush before anything that must order after it
    ov_sender = OverlappedSender(channel) if knobs["wire_format"] == "v2" else None

    actor, critic, params, _ = build_agent(runtime, cfg, observation_space, action_space)
    actor_treedef = jax.tree_util.tree_structure(params["actor"])

    start_iter, policy_step, last_log, last_checkpoint = state_counters

    train_step = 0
    last_train = 0
    train_time_window = 0.0
    trainer_compiles = None  # trainer-side XLA compile count (rides the params frames)
    latest_transport_stats = None
    lead_health = None  # lead-side checkpoint health tagger (bound below)
    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(dict(cfg.metric.aggregator))

    def _apply_params_extra(frame) -> None:
        """Account a params frame's piggybacked trainer state (lead only)."""
        nonlocal train_step, train_time_window, trainer_compiles, latest_transport_stats
        train_step += world_size
        if not lead or not frame.extra:
            return
        # slot 2 (when present) is the params content digest — consumed
        # by the follower's verification, not by the accounting here
        train_metrics, transport_stats = frame.extra[:2]
        metrics = dict(train_metrics or {})
        if transport_stats is not None:
            latest_transport_stats = transport_stats
            if lead_health is not None:
                lead_health.apply_remote(transport_stats.get("health"))
        train_time_window += metrics.pop("train_time", 0.0)
        trainer_compiles = metrics.pop("trainer_compiles", trainer_compiles)
        if aggregator and not aggregator.disabled:
            for k, v in metrics.items():
                aggregator.update(k, v)

    # protocol-wait ceiling: the PR-6 liveness knobs, not the hard-coded
    # module constant — a hung broadcast fails fast with a clear error
    # when the operator tightens algo.liveness_timeout
    timeout_s = knobs["liveness_timeout"]
    follower = ParamsFollower(
        channel,
        lag=knobs["lag"],
        initial_seq=-1,
        timeout=timeout_s,
        on_stale=_apply_params_extra,
        digest_slot=2 if knobs["integrity"] == "digest" else None,
        digest_fn=params_digest_fn(
            knobs["integrity"] == "digest", knobs["params_digest_device"]
        ),
    )

    def _adopt(frame) -> None:
        """Copy actor weights out of the transport buffers; numpy straight
        to the setter — see ppo_decoupled: jnp.asarray would stage the
        params on the default backend first."""
        new_params = _unflat_leaves(actor_treedef, frame.arrays_copy())
        _apply_params_extra(frame)
        frame.release()
        player.params = new_params

    def _die_with_dump(e: PeerDiedError, policy_step_now: int, iter_now: int):
        path = None
        if lead and ckpt_mgr is not None:
            path = ckpt_mgr.emergency_dump(
                policy_step_now,
                {
                    "actor": player.params,
                    "ratio": ratio.state_dict(),
                    "iter_num": iter_now * world_size,
                    "policy_step": policy_step_now,
                },
            )
        raise RuntimeError(
            f"decoupled trainer process died at policy_step={policy_step_now}; "
            f"the player's last-known actor weights were dumped to {path} "
            "(partial state: resume from the last regular ckpt_*.ckpt instead)"
        ) from e

    # initial actor weights (trainer broadcasts seq = 0 before round 1)
    try:
        init_frame = follower.advance_to(0)
    except PeerDiedError as e:
        raise RuntimeError(
            f"decoupled trainer process died before the initial params broadcast "
            f"reached player {player_id}"
        ) from e
    assert init_frame is not None
    train_step = 0  # the initial broadcast is not an update
    # explicit host-CPU pin — see ppo_decoupled._player_loop
    host_cpu = jax.local_devices(backend="cpu")[0]
    player = SACPlayer(
        actor,
        _unflat_leaves(actor_treedef, init_frame.arrays_copy()),
        lambda obs: prepare_obs(obs, mlp_keys=mlp_keys, num_envs=n_local_envs),
        device=host_cpu,
    )
    init_frame.release()

    # centralized inference (algo.inference=remote) — see ppo_decoupled:
    # `acting` keeps the local path literally the pre-serve call
    infer_client = None
    acting = player
    if infer_spec is not None:
        from sheeprl_tpu.serve import SAC_OUT_KEYS, InferenceClient, RemoteActor, inference_knobs

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
        acting = RemoteActor(infer_client, player, mlp_keys, SAC_OUT_KEYS)
        if lead:
            observability.serve_stats = infer_client.stats

    if lead:
        save_configs(cfg, log_dir)

    # per-player buffer shard: each player keeps ITS envs' transitions
    buffer_size = cfg.buffer.size // int(total_envs) if not cfg.dry_run else 1
    rb = ReplayBuffer(
        max(buffer_size, 1),
        n_local_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{player_id}"),
        obs_keys=("observations",),
    )
    # the buffer is restored here (not shipped through the spawn pipe): a
    # materialized replay buffer can be GBs
    if cfg.checkpoint.resume_from and cfg.buffer.checkpoint:
        rb_state = load_checkpoint(cfg.checkpoint.resume_from).get("rb")
        if rb_state is not None:
            restored = restore_buffer(
                rb_state,
                memmap=cfg.buffer.memmap,
                memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{player_id}"),
            )
            del rb_state
            if restored.n_envs != n_local_envs:
                raise RuntimeError(
                    f"The restored replay buffer tracks {restored.n_envs} envs but this player "
                    f"steps {n_local_envs}; buffers only restore across runs with matching env "
                    "counts per player (num_envs / num_players)."
                )
            rb = restored

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
    policy_steps_per_iter = int(total_envs)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_steps = learning_starts - int(learning_starts > 0)
    if start_iter > 1:
        learning_starts += start_iter
        prefill_steps += start_iter

    # the Ratio runs on the GLOBAL policy-step clock (total_envs per
    # iteration), so every player derives the SAME per-round gradient-step
    # count g — the trainer asserts shard agreement on it
    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if ratio_state is not None:
        ratio.load_state_dict(ratio_state)

    # this player's share of the global batch (remainder to the first
    # players, same deterministic split as the envs)
    total_batch = int(cfg.algo.per_rank_batch_size) * world_size
    batch_shards = split_envs(total_batch, knobs["num_players"])
    local_batch = batch_shards[player_id][1]

    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed + env_offset)[0]

    cumulative_per_rank_gradient_steps = 0
    update_round = 0
    for iter_num in range(start_iter, total_iters + 1):
        observability.on_iteration(policy_step)
        hard_exit_point("player_exit", index=player_id)  # fault site: a player crash
        policy_step += policy_steps_per_iter

        with timer("Time/env_interaction_time", SumMetric, sync_on_compute=False), flight.span(
            "collect", round=iter_num
        ):
            if iter_num <= learning_starts:
                actions = envs.action_space.sample()
            else:
                actions = np.asarray(acting.get_actions(obs, runtime.next_key()))
            next_obs, rewards, terminated, truncated, infos = envs.step(
                actions.reshape(envs.action_space.shape)
            )
            rewards = rewards.reshape(n_local_envs, -1)

        if lead and cfg.metric.log_level > 0 and "final_info" in infos:
            ep = infos["final_info"].get("episode")
            if ep is not None:
                for i in np.nonzero(infos["final_info"]["_episode"])[0]:
                    if aggregator and not aggregator.disabled:
                        aggregator.update("Rewards/rew_avg", float(ep["r"][i]))
                        aggregator.update("Game/ep_len_avg", float(ep["l"][i]))
                    runtime.print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={float(ep['r'][i])}")

        real_next_obs = {k: np.array(v) for k, v in next_obs.items()}
        if "final_obs" in infos:
            for idx in np.nonzero(infos["_final_obs"])[0]:
                for k, v in infos["final_obs"][idx].items():
                    real_next_obs[k][idx] = v
        flat_next_obs = np.concatenate([real_next_obs[k] for k in mlp_keys], axis=-1).astype(np.float32)

        step_data["terminated"] = terminated.reshape(1, n_local_envs, -1).astype(np.uint8)
        step_data["truncated"] = truncated.reshape(1, n_local_envs, -1).astype(np.uint8)
        step_data["actions"] = actions.reshape(1, n_local_envs, -1).astype(np.float32)
        step_data["observations"] = np.concatenate([obs[k] for k in mlp_keys], axis=-1).astype(np.float32)[
            np.newaxis
        ]
        if not cfg.buffer.sample_next_obs:
            step_data["next_observations"] = flat_next_obs[np.newaxis]
        step_data["rewards"] = rewards[np.newaxis].astype(np.float32)
        rb.add(step_data, validate_args=cfg.buffer.validate_args)
        obs = next_obs

        # ------------------------------------------ sample-and-ship shard
        if iter_num >= learning_starts:
            # global-clock ratio: policy_step already advances total_envs
            # per iter, which is coupled's per-rank scale
            per_rank_gradient_steps = ratio(policy_step - prefill_steps + policy_steps_per_iter)
            if per_rank_gradient_steps > 0:
                g = per_rank_gradient_steps
                update_round += 1
                sample = rb.sample(
                    batch_size=g * local_batch,
                    sample_next_obs=cfg.buffer.sample_next_obs,
                )
                sample = [(k, np.asarray(v)) for k, v in sample.items()]
                try:
                    with trace_scope("ipc_send_shard"), flight.span("data_send", round=update_round):
                        # slot 2: this player's live-metrics summary
                        # (ISSUE 15) — None when the plane is off
                        send_extra = (
                            g,
                            iter_num,
                            live.beat(policy_step) if live is not None else None,
                        )
                        if ov_sender is not None:
                            ov_sender.submit(
                                "data", sample, extra=send_extra, seq=update_round, timeout=timeout_s
                            )
                        else:
                            channel.send(
                                "data", arrays=sample, extra=send_extra, seq=update_round, timeout=timeout_s
                            )
                    # fixed-lag adoption: after shipping round u, act on the
                    # actor of update u - lag (lag 0 = the lock-step protocol)
                    with trace_scope("ipc_wait_update"):
                        frame = follower.params_for_round(update_round + 1)
                except PeerDiedError as e:
                    _die_with_dump(e, policy_step, iter_num)
                if frame is not None:
                    _adopt(frame)
                cumulative_per_rank_gradient_steps += g

        # ------------------------------------------ checkpoint (lead saves,
        # trainer state requested on demand so zero-gradient-step iterations
        # and save_last still checkpoint)
        if lead and ckpt_mgr.should_checkpoint(policy_step, is_last=iter_num == total_iters):
            try:
                if ov_sender is not None:
                    ov_sender.flush(timeout=timeout_s)  # ckpt_req orders after the shard
                channel.send("ckpt_req", timeout=timeout_s)
                frame = follower.wait_tag("ckpt_state")
            except PeerDiedError as e:
                _die_with_dump(e, policy_step, iter_num)
            # the full nested trees ride pickled (checkpoint cadence only:
            # the resume path needs the real pytree structure back)
            full_state = frame.extra[0]
            frame.release()

            def _ckpt_state():
                state = {
                    "agent": full_state["agent"],
                    "opt_states": full_state["opt_states"],
                    "ratio": ratio.state_dict(),
                    # counters stored in coupled policy-step units (x world_size)
                    # so checkpoints swap between variants
                    "iter_num": iter_num * world_size,
                    "batch_size": cfg.algo.per_rank_batch_size * world_size,
                    "last_log": last_log * world_size,
                    "last_checkpoint": ckpt_mgr.last_checkpoint * world_size,
                }
                if cfg.buffer.checkpoint:
                    state["rb"] = rb
                return state

            ckpt_mgr.checkpoint_now(policy_step=policy_step, state_fn=_ckpt_state)
            if ckpt_mgr.preempted:
                runtime.print(
                    f"Preemption signal: emergency checkpoint written, stopping at iter {iter_num}"
                )
                break
        if preemption is not None and preemption.preempted:
            break  # non-lead worker: drain out so the fan-in shrinks cleanly

        if lead and cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters
        ):
            extra = {"trainer_compiles": trainer_compiles}
            if latest_transport_stats is not None:
                extra["transport"] = latest_transport_stats
            if knobs["integrity"] != "off":
                from sheeprl_tpu.resilience.integrity import integrity_stats

                extra["integrity"] = integrity_stats().as_dict()
                extra["integrity"]["params_digest_skips"] = follower.digest_skips
            observability.on_log(
                policy_step,
                train_step,
                train_time_s=train_time_window,
                extra=extra,
            )
            if logger:
                if aggregator and not aggregator.disabled:
                    logger.log_metrics(aggregator.compute(), policy_step)
                    aggregator.reset()
                logger.log_metrics(
                    {"Params/replay_ratio": cumulative_per_rank_gradient_steps * world_size / policy_step},
                    policy_step,
                )
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

    # drain the in-flight params broadcast before closing — see
    # ppo_decoupled: an unread broadcast at close resets the connection
    if ov_sender is not None:
        try:
            ov_sender.flush(timeout=30.0)  # final shard out before the drain/stop
        except Exception:
            pass
    try:
        frame = follower.advance_to(update_round, timeout=60.0)
        if frame is not None:
            _adopt(frame)
    except Exception:
        pass  # a dead/strangled trainer: nothing left to drain
    # shutdown sentinel (reference scatters -1, sac_decoupled.py:328)
    try:
        channel.send("stop")
    except Exception:
        pass  # a dead trainer cannot receive it; exit anyway
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


def _player_loop_remote(
    cfg, spec, state_counters, world_size: int, env_offset: int, n_local_envs: int, join: bool = False
) -> None:
    """Remote-replay player body: env stepping + ``ReplayWriter`` inserts.

    No local buffer, no Ratio, no sampled-batch shipping — the trainer
    owns the replay service and the training cadence.  Params adoption is
    opportunistic (newest broadcast wins): with the trainer free-running
    on its own clock there is no per-round lock-step to pin a fixed lag
    to, and the insert-credit window already bounds how far a player can
    run ahead of the last update it saw.

    ``join=True`` (supervised restart): the player is STATELESS here, so
    rejoin is nearly free — announce with a join frame, sync the step
    clock off the trainer's assign reply (the server's insert clock), and
    resume inserting on a fresh credit window."""
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

    flight.configure_from_cfg(cfg, role=f"player{player_id}")
    live = obs_fleet.configure_from_cfg(cfg, role=f"player{player_id}")
    obs_ledger.configure_from_cfg(cfg, role=f"player{player_id}")
    runtime = MeshRuntime(devices=1, accelerator="cpu", precision=cfg.fabric.precision)
    runtime.launch()
    runtime.seed_everything(cfg.seed + player_id)

    logger = get_logger(runtime, cfg) if lead else None
    if lead:
        log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name)
        runtime.print(f"Log dir: {log_dir}")
    else:
        log_dir = os.path.join(str(cfg.root_dir), str(cfg.run_name), f"player_{player_id}")
    observability = setup_observability(runtime, cfg, log_dir if lead else None, logger=logger)
    if logger:
        logger.log_hyperparams(cfg)

    thunks = [
        make_env(cfg, cfg.seed + env_offset + i, 0, log_dir, "train", vector_env_idx=env_offset + i)
        for i in range(n_local_envs)
    ]
    envs = (
        SyncVectorEnv(thunks, autoreset_mode=AutoresetMode.SAME_STEP)
        if cfg.env.sync_env
        else AsyncVectorEnv(thunks, context="spawn", autoreset_mode=AutoresetMode.SAME_STEP)
    )
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the SAC agent")
    mlp_keys = list(cfg.algo.mlp_keys.encoder)

    channel = spec.player_channel(peer_alive=parent_alive, who="trainer")
    timeout_s = knobs["liveness_timeout"]
    heartbeat = (
        HeartbeatSender(channel, interval=max(2 * knobs["liveness_interval"], 1.0))
        if knobs["supervisor"]["enabled"]
        else None
    )
    channel.send(JOIN_TAG if join else "init", extra=(observation_space, action_space))

    actor, _critic, params, _ = build_agent(runtime, cfg, observation_space, action_space)
    actor_treedef = jax.tree_util.tree_structure(params["actor"])

    start_iter, policy_step, last_log, last_checkpoint = state_counters
    writer = ReplayWriter(channel, n_local_envs, initial_credits=knobs["window"])

    train_step = 0
    last_train = 0
    train_time_window = 0.0
    trainer_compiles = None
    latest_replay_stats = None
    lead_health = None  # lead-side checkpoint health tagger (bound below)
    current_params_seq = -1
    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(dict(cfg.metric.aggregator))

    player = None  # built on the initial broadcast

    def _account_params_extra(frame) -> None:
        nonlocal train_step, train_time_window, trainer_compiles, latest_replay_stats
        if frame.seq > 0:
            train_step += world_size  # seq 0 is the initial broadcast, not an update
        if not lead or not frame.extra:
            return
        # slot 2 (when present) is the params content digest — consumed
        # by _params_frame_ok, not by the accounting here
        train_metrics, replay_stats = frame.extra[:2]
        metrics = dict(train_metrics or {})
        if replay_stats is not None:
            latest_replay_stats = replay_stats
            if lead_health is not None:
                lead_health.apply_remote(replay_stats.get("health"))
        train_time_window += metrics.pop("train_time", 0.0)
        trainer_compiles = metrics.pop("trainer_compiles", trainer_compiles)
        if aggregator and not aggregator.disabled:
            for k, v in metrics.items():
                aggregator.update(k, v)

    digest_mode = knobs["integrity"] == "digest"
    _digest = params_digest_fn(digest_mode, knobs["params_digest_device"])

    def _params_frame_ok(frame) -> bool:
        """Digest-verified adoption (algo.transport_integrity=digest):
        recompute the content digest over the received arrays; a
        mismatch skips this broadcast (the next one re-syncs)."""
        if not digest_mode or len(frame.extra) <= 2 or frame.extra[2] is None:
            return True
        from sheeprl_tpu.resilience.integrity import integrity_stats

        st = integrity_stats()
        st.params_digest_checked += 1
        if _digest(list(frame.arrays.items())) == int(frame.extra[2]):
            return True
        st.params_digest_mismatch += 1
        return False

    def _handle_frames(wait_tag: Optional[str] = None):
        """Drain the writer's queued frames: adopt the NEWEST params
        broadcast, account every update's extras, hand back the first
        ``wait_tag`` frame (caller releases it)."""
        nonlocal current_params_seq, player
        wanted = None
        newest = None
        while writer.frames:
            frame = writer.frames.popleft()
            if frame.tag == "params":
                if frame.seq > current_params_seq and _params_frame_ok(frame):
                    _account_params_extra(frame)
                    if newest is not None:
                        newest.release()
                    newest = frame
                    current_params_seq = frame.seq
                else:
                    frame.release()  # reconnect replay duplicate / corrupt
            elif wait_tag is not None and frame.tag == wait_tag and wanted is None:
                wanted = frame
            else:
                frame.release()
        if newest is not None:
            flight.fleet_event("broadcast_adopt", seq=int(newest.seq))
            new_params = _unflat_leaves(actor_treedef, newest.arrays_copy())
            newest.release()
            if player is None:
                host_cpu = jax.local_devices(backend="cpu")[0]
                player = SACPlayer(
                    actor,
                    new_params,
                    lambda obs: prepare_obs(obs, mlp_keys=mlp_keys, num_envs=n_local_envs),
                    device=host_cpu,
                )
            else:
                player.params = new_params
        return wanted

    def _wait_tag(tag: str, timeout: float):
        deadline = time.monotonic() + timeout
        while True:
            frame = _handle_frames(wait_tag=tag)
            if frame is not None:
                return frame
            if time.monotonic() > deadline:
                raise RuntimeError(f"timed out waiting for a {tag!r} frame from the trainer")
            writer.pump(0.2)

    def _die_with_dump(e: Exception, policy_step_now: int, iter_now: int):
        path = None
        if lead and ckpt_mgr is not None and player is not None:
            path = ckpt_mgr.emergency_dump(
                policy_step_now,
                {
                    "actor": player.params,
                    "iter_num": iter_now * world_size,
                    "policy_step": policy_step_now,
                },
            )
        raise RuntimeError(
            f"remote replay server (decoupled trainer process) died at "
            f"policy_step={policy_step_now}; the player's last-known actor weights were "
            f"dumped to {path} (partial state: resume from the last regular ckpt_*.ckpt "
            "instead)"
        ) from e

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
    if lead:
        save_configs(cfg, log_dir)

    total_envs = int(cfg.env.num_envs)
    if join:
        # the assign reply carries the server's insert clock, so a
        # rejoined player resumes at the pool's current step budget
        # instead of replaying the whole schedule from iteration 1
        try:
            frame = _wait_tag("assign", timeout_s)
        except PeerDiedError as e:
            raise RuntimeError(
                f"remote replay server died before answering player {player_id}'s join"
            ) from e
        server_inserts = int(frame.extra[0])
        frame.release()
        start_iter = max(start_iter, server_inserts // total_envs + 1)
        policy_step = (start_iter - 1) * total_envs
        last_log = policy_step

    # initial actor weights (trainer broadcasts seq=0 after the init round;
    # a joiner gets a directed copy with the assign reply)
    try:
        deadline = time.monotonic() + timeout_s
        while player is None:
            writer.pump(0.2)
            _handle_frames()
            if player is None and time.monotonic() > deadline:
                raise RuntimeError("initial params broadcast never arrived")
    except PeerDiedError as e:
        raise RuntimeError(
            f"remote replay server died before the initial params broadcast reached "
            f"player {player_id}"
        ) from e

    policy_steps_per_iter = int(total_envs)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    if start_iter > 1:
        learning_starts += start_iter

    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed + env_offset)[0]

    for iter_num in range(start_iter, total_iters + 1):
        observability.on_iteration(policy_step)
        hard_exit_point("player_exit", index=player_id)  # fault site: a player crash
        policy_step += policy_steps_per_iter

        with timer("Time/env_interaction_time", SumMetric, sync_on_compute=False), flight.span(
            "collect", round=iter_num
        ):
            if iter_num <= learning_starts:
                actions = envs.action_space.sample()
            else:
                actions = np.asarray(player.get_actions(obs, runtime.next_key()))
            next_obs, rewards, terminated, truncated, infos = envs.step(
                actions.reshape(envs.action_space.shape)
            )
            rewards = rewards.reshape(n_local_envs, -1)

        if lead and cfg.metric.log_level > 0 and "final_info" in infos:
            ep = infos["final_info"].get("episode")
            if ep is not None:
                for i in np.nonzero(infos["final_info"]["_episode"])[0]:
                    if aggregator and not aggregator.disabled:
                        aggregator.update("Rewards/rew_avg", float(ep["r"][i]))
                        aggregator.update("Game/ep_len_avg", float(ep["l"][i]))
                    runtime.print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={float(ep['r'][i])}")

        real_next_obs = {k: np.array(v) for k, v in next_obs.items()}
        if "final_obs" in infos:
            for idx in np.nonzero(infos["_final_obs"])[0]:
                for k, v in infos["final_obs"][idx].items():
                    real_next_obs[k][idx] = v
        flat_next_obs = np.concatenate([real_next_obs[k] for k in mlp_keys], axis=-1).astype(np.float32)

        step_data["terminated"] = terminated.reshape(1, n_local_envs, -1).astype(np.uint8)
        step_data["truncated"] = truncated.reshape(1, n_local_envs, -1).astype(np.uint8)
        step_data["actions"] = actions.reshape(1, n_local_envs, -1).astype(np.float32)
        step_data["observations"] = np.concatenate([obs[k] for k in mlp_keys], axis=-1).astype(np.float32)[
            np.newaxis
        ]
        if not cfg.buffer.sample_next_obs:
            step_data["next_observations"] = flat_next_obs[np.newaxis]
        step_data["rewards"] = rewards[np.newaxis].astype(np.float32)

        # ------------------------------------------ insert (credit-gated)
        try:
            with trace_scope("replay_insert"), flight.span("data_send", round=iter_num):
                writer.append(
                    dict(step_data),
                    timeout=timeout_s,
                    summary=live.beat(policy_step) if live is not None else None,
                )
            writer.pump(0.01)
        except PeerDiedError as e:
            _die_with_dump(e, policy_step, iter_num)
        _handle_frames()
        obs = next_obs

        # ------------------------------------------ checkpoint (lead)
        if lead and ckpt_mgr.should_checkpoint(policy_step, is_last=iter_num == total_iters):
            try:
                channel.send("ckpt_req", timeout=timeout_s)
                frame = _wait_tag("ckpt_state", timeout_s)
            except PeerDiedError as e:
                _die_with_dump(e, policy_step, iter_num)
            full_state = frame.extra[0]
            frame.release()

            def _ckpt_state():
                state = {
                    "agent": full_state["agent"],
                    "opt_states": full_state["opt_states"],
                    "ratio": full_state["ratio"],
                    "replay_server": full_state["replay_server"],
                    "iter_num": iter_num * world_size,
                    "batch_size": cfg.algo.per_rank_batch_size * world_size,
                    "last_log": last_log * world_size,
                    "last_checkpoint": ckpt_mgr.last_checkpoint * world_size,
                }
                if full_state.get("rb") is not None:
                    # top-level key: the snapshot machinery materializes
                    # buffers only there
                    state["rb"] = full_state["rb"]
                return state

            ckpt_mgr.checkpoint_now(policy_step=policy_step, state_fn=_ckpt_state)
            if ckpt_mgr.preempted:
                runtime.print(
                    f"Preemption signal: emergency checkpoint written, stopping at iter {iter_num}"
                )
                break
        if preemption is not None and preemption.preempted:
            break  # non-lead worker: stop inserting, the fan-in shrinks

        # ------------------------------------------ logging (lead)
        if lead and cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters
        ):
            replay_rec = dict(latest_replay_stats or {})
            replay_rec["writer"] = writer.stats()
            extra = {"trainer_compiles": trainer_compiles, "replay": replay_rec}
            if knobs["integrity"] != "off":
                from sheeprl_tpu.resilience.integrity import integrity_stats

                extra["integrity"] = integrity_stats().as_dict()
            observability.on_log(
                policy_step, train_step, train_time_s=train_time_window, extra=extra
            )
            if logger:
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

    # drain leftovers so an unread broadcast can't RST the connection at
    # close (see ppo_decoupled), then send the stop sentinel
    try:
        writer.pump(0.5)
        _handle_frames()
    except Exception:
        pass
    try:
        channel.send("stop")
    except Exception:
        pass  # a dead trainer cannot receive it; exit anyway
    if heartbeat is not None:
        heartbeat.close()
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
    channel.close()
    flight.close_recorder()
    obs_fleet.close_live()


@register_algorithm(decoupled=True)
def main(runtime, cfg: Dict[str, Any]):
    """Trainer process body + player spawn (reference sac_decoupled.py:356-545)."""
    runtime.seed_everything(cfg.seed)
    knobs = decoupled_knobs(cfg)
    flight.configure_from_cfg(cfg, role="trainer")
    obs_fleet.configure_from_cfg(cfg, role="trainer")
    obs_ledger.configure_from_cfg(cfg, role="trainer")

    if "minedojo" in str(cfg.env.wrapper.get("_target_", "")).lower():
        raise ValueError("MineDojo is not supported by the SAC agent")
    if len(cfg.algo.cnn_keys.encoder) > 0:
        warnings.warn("SAC cannot use image observations, the CNN keys will be ignored")
        cfg.algo.cnn_keys.encoder = []

    state = None
    if cfg.checkpoint.resume_from:
        state = load_checkpoint(cfg.checkpoint.resume_from)
        cfg.algo.per_rank_batch_size = state["batch_size"] // runtime.world_size

    start_iter = (state["iter_num"] // runtime.world_size) + 1 if state else 1
    counters = (
        start_iter,
        (state["iter_num"] // runtime.world_size) * cfg.env.num_envs if state else 0,
        state["last_log"] // runtime.world_size if state else 0,
        state["last_checkpoint"] // runtime.world_size if state else 0,
    )
    ratio_state = state["ratio"] if state else None

    if remote_replay_setting(cfg):
        # Reverb-style topology: the replay buffer lives HERE, players
        # stream raw transitions into it (replay/service.py)
        return _main_remote(runtime, cfg, knobs, state, counters, ratio_state)

    if knobs["supervisor"]["enabled"]:
        warnings.warn(
            "algo.supervisor.enabled has no effect on classic sac_decoupled: a player's "
            "buffer shard dies with it, so there is nothing lossless to restart into. "
            "Set buffer.remote_replay=true for a self-healing SAC pool."
        )

    from sheeprl_tpu.serve import inference_setting

    inference = inference_setting(cfg, knobs["num_players"])
    ctx = mp.get_context("spawn")
    hub, channels, procs, env_shards, infer_hub = spawn_players(
        cfg,
        runtime,
        ctx,
        _player_loop,
        extra_args=(counters, ratio_state, runtime.world_size),
        knobs=knobs,
        with_inference=inference == "remote",
    )
    fanin = FanIn(channels)

    # a SIGTERM delivered to the trainer only (per-process preemption) is
    # forwarded to every player; the lead owns the checkpoint files and
    # runs the emergency-save path
    preemption = PreemptionHandler(forward_to=list(procs)).install()

    def _dump_and_raise(e: PeerDiedError, what: str):
        path = None
        try:
            from sheeprl_tpu.utils.ckpt_format import save_state

            dump_dir = os.path.join(str(cfg.root_dir), str(cfg.run_name))
            os.makedirs(dump_dir, exist_ok=True)
            path = save_state(
                os.path.join(dump_dir, "emergency_trainer_0.ckpt"),
                _np_tree({"agent": params, "opt_states": opt_states}),
            )
        except Exception:
            pass
        raise RuntimeError(
            f"decoupled player process died (all {knobs['num_players']} players gone: {e}) while "
            f"the trainer waited for a {what} message; trainer params/optimizer dumped to {path} "
            "(partial state: resume from the last regular ckpt_*.ckpt instead)"
        ) from e

    try:
        try:
            _, init_frames = fanin.gather(timeout=_QUEUE_TIMEOUT_S, data_tag="init")
        except PeerDiedError as e:
            params = opt_states = None
            _dump_and_raise(e, "init")
        first = next(iter(init_frames.values()))
        observation_space, action_space = first.extra
        for f in init_frames.values():
            f.release()

        actor, critic, params, target_entropy = build_agent(
            runtime, cfg, observation_space, action_space, state["agent"] if state else None
        )
        params = runtime.replicate(
            runtime.to_param_dtype(params, exclude=("target_critic", "log_alpha"))
        )
        actor_tx = _make_optimizer(cfg.algo.actor.optimizer, runtime.precision)
        critic_tx = _make_optimizer(cfg.algo.critic.optimizer, runtime.precision)
        alpha_tx = _make_optimizer(cfg.algo.alpha.optimizer, runtime.precision)
        if state is not None:
            opt_states = restore_opt_states(
                state["opt_states"], params, runtime.precision, key_map={"alpha": "log_alpha"}
            )
        else:
            opt_states = runtime.replicate(
                {
                    "actor": actor_tx.init(params["actor"]),
                    "critic": critic_tx.init(params["critic"]),
                    "alpha": alpha_tx.init(params["log_alpha"]),
                }
            )
        train_fn = make_train_fn(
            runtime, actor, critic, (actor_tx, critic_tx, alpha_tx), cfg, target_entropy
        )
        # training health: verdicts live here; the lead player owns the
        # checkpoint files, so rollback scans the run root for the last
        # good-tagged checkpoint
        health = train_fn.health.bind(
            scan_root=str(cfg.root_dir), select=("agent", "opt_states")
        )
        ema_every = cfg.algo.critic.target_network_frequency // int(cfg.env.num_envs) + 1

        # trainer-side recompile watch — see ppo_decoupled: the jitted
        # train_fn retraces in THIS process, so the count must ride the
        # params frames to reach the lead's telemetry
        from sheeprl_tpu.obs import RecompileMonitor

        trainer_mon = RecompileMonitor(name="sac_decoupled_trainer").install()

        # centralized inference — see ppo_decoupled: the server thread
        # serves the players' obs frames with THIS process's actor params
        # (swapped between batches each round)
        serve_server = serve_sup = None
        if infer_hub is not None:
            from sheeprl_tpu.resilience import ServeSupervisor, child_alive
            from sheeprl_tpu.serve import InferenceServer, inference_knobs, make_sac_policy_fn

            ik = inference_knobs(cfg)
            serve_server = InferenceServer(
                make_sac_policy_fn(actor, cfg.algo.mlp_keys.encoder),
                params["actor"],
                deadline_ms=ik["deadline_ms"],
                max_batch=ik["max_batch"],
                seed=cfg.seed + 1,
                name="sac",
            )
            for pid, proc in enumerate(procs):
                ch = infer_hub.channel(pid, timeout=_QUEUE_TIMEOUT_S, peer_alive=proc.is_alive)
                ch.set_peer(child_alive(proc), f"player[{pid}]")
                serve_server.attach(pid, ch)
            serve_server.start()
            serve_sup = ServeSupervisor(
                serve_server,
                restart_budget=ik["restart_budget"],
                backoff_base=ik["restart_backoff_s"],
            )

        def _on_control(pid: int, frame) -> None:
            """``ckpt_req`` from the lead: answer with the full agent +
            optimizer state (pickled trees — checkpoint cadence only, and
            the resume path needs the real pytree structure back)."""
            tag = frame.tag
            frame.release()
            if tag != "ckpt_req":
                return
            fanin.send_to(
                pid,
                "ckpt_state",
                extra=({"agent": _np_tree(params), "opt_states": _np_tree(opt_states)},),
            )

        # params digest (algo.transport_integrity=digest) — see
        # ppo_decoupled: computed once per broadcast from the source
        # arrays, verified at every player's adoption
        digest_mode = knobs["integrity"] == "digest"
        _params_digest = params_digest_fn(digest_mode, knobs["params_digest_device"])

        # initial actor weights to every player (seq 0; round seqs start at 1)
        init_arrays = _flat_leaves(_np_tree(params["actor"]))
        init_digest = _params_digest(init_arrays)
        fanin.broadcast(
            "params",
            arrays=init_arrays,
            seq=0,
            extra_fn=(lambda pid: (None, None, init_digest)) if digest_mode else None,
        )

        while True:
            if serve_sup is not None:
                serve_sup.poll()
            try:
                with trace_scope("ipc_wait_rollout"), flight.span("fanin_wait"):
                    seq, frames = fanin.gather(timeout=_QUEUE_TIMEOUT_S, on_control=_on_control)
            except PeerDiedError as e:
                _dump_and_raise(e, "rollout")
            if not frames:
                break  # every player stopped
            # all players derive g/iter_num from the same global schedule
            # (slot 2, when present, is the player's live-metrics summary)
            g, iter_num = next(iter(frames.values())).extra[:2]
            gs = {f.extra[0] for f in frames.values()}
            if len(gs) != 1:
                raise RuntimeError(f"fan-in desync: players disagree on gradient steps {gs}")
            for pid, frame in frames.items():
                if len(frame.extra) > 2:
                    fanin.note_summary(pid, frame.extra[2])

            # per-player shard -> (g, local_batch, ...) then concat along the
            # batch axis in player-id order (np.array materializes private
            # rows so the transport buffers can be handed back right after)
            assembly_span = flight.span("batch_assembly", round=int(seq), shards=len(frames))
            assembly_span.__enter__()
            shards: Dict[int, Dict[str, np.ndarray]] = {}
            for pid, frame in frames.items():
                shards[pid] = {
                    k: np.array(v, dtype=np.float32).reshape(g, -1, *v.shape[2:])
                    for k, v in frame.arrays.items()
                }
                frame.release()
            data = assemble_shards(shards, axis=1)
            # FIXED batch width: a dead player's missing sample columns are
            # refilled by cycling the survivors' rows — replay draws are
            # i.i.d., so the tile only re-weights samples slightly, and the
            # train scan keeps its one XLA trace through a pool shrink
            # (the pre-elastic path recompiled for every smaller batch)
            total_batch = int(cfg.algo.per_rank_batch_size) * runtime.world_size
            have = next(iter(data.values())).shape[1]
            if have < total_batch:
                idx = np.resize(np.arange(have), total_batch)
                data = {k: v[:, idx] for k, v in data.items()}
            # shard the batch axis over the mesh so each device trains on
            # its own rows (GSPMD inserts the grad psums)
            data = runtime.shard_batch(data, axis=1)
            assembly_span.__exit__(None, None, None)
            with timer("Time/train_time", SumMetric, sync_on_compute=cfg.metric.sync_on_compute), \
                    flight.span("train_dispatch", round=int(seq)):
                params, opt_states, train_metrics = train_fn(
                    params,
                    opt_states,
                    data,
                    runtime.next_key(),
                    # per-step EMA flags: all steps of this dispatch come
                    # from this iteration (see sac.make_train_fn)
                    jnp.full((data["rewards"].shape[0],), iter_num % ema_every == 0),
                )
                train_metrics = device_get_metrics(train_metrics)
            rolled = health.tick()
            if rolled is not None:
                # rollback-to-last-good; the broadcast below ships the
                # restored actor so every player re-adopts immediately
                params = restore_like(params, rolled["agent"])
                opt_states = restore_like(opt_states, rolled["opt_states"])
                fanin.note_rollback(seq)
            if not timer.disabled:
                train_metrics["train_time"] = float(timer.compute().get("Time/train_time", 0.0))
                timer.reset()
            train_metrics["trainer_compiles"] = trainer_mon.compiles
            trainer_mon.mark_warmup_complete()  # first update done: further compiles are retraces

            if serve_server is not None:
                serve_server.swap_params(params["actor"])

            stats = fanin.stats(knobs["backend"])
            stats["events"] = fanin.events[-8:]
            if serve_server is not None:
                stats["serve"] = serve_server.stats()
                if serve_sup is not None:
                    stats["serve"]["supervisor"] = serve_sup.stats()
            if health.enabled:
                stats["health"] = health.stats()
            if knobs["integrity"] != "off":
                from sheeprl_tpu.resilience.integrity import integrity_stats

                stats["integrity"] = integrity_stats().as_dict()
            led = obs_ledger.get_ledger()
            if led is not None:
                # piggyback the trainer's time breakdown on the stats the
                # lead already logs (reaches telemetry as transport.where)
                stats["where"] = led.snapshot()
            live = obs_fleet.get_live()
            if live is not None:
                trainer_record = {
                    "ts": time.time(),
                    "step": int(iter_num) * int(cfg.env.num_envs),
                    "transport": stats,
                }
                if led is not None:
                    trainer_record["where"] = led.snapshot()
                live.observe(trainer_record)
            bcast_arrays = _flat_leaves(_np_tree(params["actor"]))
            bcast_digest = _params_digest(bcast_arrays)
            fanin.broadcast(
                "params",
                arrays=bcast_arrays,
                seq=seq,
                extra_fn=lambda pid: (train_metrics, stats if pid == 0 else None)
                + ((bcast_digest,) if digest_mode else ()),
            )
            hard_exit_point("trainer_exit")  # fault site: trainer crash after replying

        trainer_mon.uninstall()
        if serve_server is not None:
            serve_server.close()  # graceful drain: answer pending, send stops
        # the lead still runs its test episode + logger shutdown after the
        # stop sentinel — give it ample time before the terminate fallback
        for proc in procs:
            proc.join(timeout=3600.0)
    finally:
        preemption.uninstall()
        fanin.close()
        hub.close()
        if infer_hub is not None:
            infer_hub.close()
        flight.close_recorder()
        obs_fleet.close_live()
        for proc in procs:
            if proc.is_alive():
                proc.terminate()
                proc.join()


def _main_remote(runtime, cfg: Dict[str, Any], knobs, state, counters, ratio_state):
    """Remote-replay trainer body: owns the ReplayServer AND the training
    cadence.

    The trainer free-runs: each loop pumps player inserts into the
    buffer, advances the ``Ratio`` schedule on the global INSERT clock
    (one transition == one policy step, exactly the coupled loop's
    accounting), clips the granted gradient steps to the rate limiter's
    budget, trains, and broadcasts refreshed actor weights (seq = update
    round; players adopt the newest).  Insert credits stop flowing
    whenever the limiter's error budget is exhausted — a slow trainer
    therefore throttles its players instead of silently training on an
    ever-staler ratio."""
    start_iter = counters[0]

    from sheeprl_tpu.serve import inference_setting

    if inference_setting(cfg, knobs["num_players"]) == "remote":
        warnings.warn(
            "algo.inference=remote is not wired for the remote-replay SAC topology "
            "(the free-running trainer has no between-rounds boundary to swap served "
            "params at); players act locally — see howto/serving.md."
        )
    ctx = mp.get_context("spawn")
    hub, channels, proc_list, env_shards, _ = spawn_players(
        cfg, runtime, ctx, _player_loop, extra_args=(counters, ratio_state, runtime.world_size), knobs=knobs
    )
    procs: Dict[int, Any] = dict(enumerate(proc_list))

    preemption = PreemptionHandler(forward_to=list(procs.values())).install()
    params = opt_states = None
    supervisor = None

    def _dump_and_raise(e: Exception, what: str):
        path = None
        try:
            from sheeprl_tpu.utils.ckpt_format import save_state

            if params is not None:
                dump_dir = os.path.join(str(cfg.root_dir), str(cfg.run_name))
                os.makedirs(dump_dir, exist_ok=True)
                path = save_state(
                    os.path.join(dump_dir, "emergency_trainer_0.ckpt"),
                    _np_tree({"agent": params, "opt_states": opt_states}),
                )
        except Exception:
            pass
        raise RuntimeError(
            f"decoupled player process died (all {knobs['num_players']} players gone: {e}) "
            f"while the remote replay trainer waited for a {what}; trainer params/optimizer "
            f"dumped to {path} (partial state: resume from the last regular ckpt_*.ckpt instead)"
        ) from e

    try:
        # ---- init round: every player announces its spaces first (FIFO
        # per channel guarantees init precedes any rb_insert)
        spaces = None
        for pid, ch in channels.items():
            deadline = time.monotonic() + _QUEUE_TIMEOUT_S
            while True:
                try:
                    frame = ch.recv(timeout=max(deadline - time.monotonic(), 0.01))
                except PeerDiedError as e:
                    _dump_and_raise(e, "init message")
                if frame.tag == "init":
                    spaces = frame.extra
                    frame.release()
                    break
                frame.release()
        observation_space, action_space = spaces

        actor, critic, params, target_entropy = build_agent(
            runtime, cfg, observation_space, action_space, state["agent"] if state else None
        )
        params = runtime.replicate(
            runtime.to_param_dtype(params, exclude=("target_critic", "log_alpha"))
        )
        actor_tx = _make_optimizer(cfg.algo.actor.optimizer, runtime.precision)
        critic_tx = _make_optimizer(cfg.algo.critic.optimizer, runtime.precision)
        alpha_tx = _make_optimizer(cfg.algo.alpha.optimizer, runtime.precision)
        if state is not None:
            opt_states = restore_opt_states(
                state["opt_states"], params, runtime.precision, key_map={"alpha": "log_alpha"}
            )
        else:
            opt_states = runtime.replicate(
                {
                    "actor": actor_tx.init(params["actor"]),
                    "critic": critic_tx.init(params["critic"]),
                    "alpha": alpha_tx.init(params["log_alpha"]),
                }
            )
        prioritized = bool(cfg.buffer.get("prioritized", False))
        train_fn = make_train_fn(
            runtime, actor, critic, (actor_tx, critic_tx, alpha_tx), cfg, target_entropy,
            prioritized=prioritized,
        )
        health = train_fn.health.bind(
            scan_root=str(cfg.root_dir), select=("agent", "opt_states")
        )
        total_envs = int(cfg.env.num_envs)
        ema_every = cfg.algo.critic.target_network_frequency // total_envs + 1

        learning_starts_t = int(cfg.algo.learning_starts) if not cfg.dry_run else 0
        limiter = rate_limiter_from_cfg(cfg, default_min_size=max(learning_starts_t, 1))
        buffer_size = cfg.buffer.size // total_envs if not cfg.dry_run else 1
        server = ReplayServer(
            max(buffer_size, 1),
            env_shards,
            channels,
            obs_keys=("observations",),
            limiter=limiter,
            prioritized=prioritized,
            per_alpha=float(cfg.buffer.get("per_alpha", 0.6)),
            per_eps=float(cfg.buffer.get("per_eps", 1e-6)),
            device=runtime.device,
            credit_window=knobs["window"],
            integrity=knobs["integrity"],
        )
        if state is not None and state.get("replay_server") is not None:
            server.load_state_dict(state["replay_server"], rb_state=state.get("rb"))

        # elastic pool: remote-replay players are stateless writers, so a
        # supervised restart is lossless — the buffer, limiter and clock
        # all live here with the server
        supervisor = None
        if knobs["supervisor"]["enabled"]:
            from sheeprl_tpu.resilience import PlayerSupervisor

            def _respawn_args(pid, spec):
                offset, count = env_shards[pid]
                return (cfg, spec, counters, ratio_state, runtime.world_size, offset, count, True)

            supervisor = PlayerSupervisor(
                ctx,
                hub,
                server,
                _player_loop,
                _respawn_args,
                procs,
                restart_budget=knobs["supervisor"]["restart_budget"],
                backoff_base=knobs["supervisor"]["backoff_base"],
                backoff_max=knobs["supervisor"]["backoff_max"],
                heartbeat_timeout=knobs["supervisor"]["heartbeat_timeout"],
                preemption=preemption,
                join_timeout=knobs["liveness_timeout"],
            )
        beta_fn = per_beta_schedule(
            cfg.buffer.get("per_beta", 0.4),
            cfg.buffer.get("per_beta_end", 1.0),
            int(cfg.algo.total_steps),
        )
        ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
        if ratio_state is not None:
            ratio.load_state_dict(ratio_state)

        from sheeprl_tpu.obs import RecompileMonitor

        trainer_mon = RecompileMonitor(name="sac_remote_replay_trainer").install()

        batch_unit = int(cfg.algo.per_rank_batch_size) * runtime.world_size
        need_rows = 2 if cfg.buffer.sample_next_obs else 1
        update_round = 0
        pending_g = 0
        # FIXED dispatch size: the free-running loop grants a different g
        # every pass, and every distinct g is a fresh XLA trace of the
        # train scan — dispatching in exact dispatch_batch-sized chunks
        # keeps it to one trace (leftover steps wait for the next grants)
        dispatch_g = max(1, int(cfg.algo.get("dispatch_batch", 1)))
        last_metrics: Dict[str, Any] = {}

        digest_mode = knobs["integrity"] == "digest"
        _params_digest = params_digest_fn(digest_mode, knobs["params_digest_device"])

        def _actor_arrays_digest():
            arrays = _flat_leaves(_np_tree(params["actor"]))
            return arrays, _params_digest(arrays)

        def _broadcast_params(seq: int, extras) -> None:
            arrays, digest = _actor_arrays_digest()
            flight.fleet_event(
                "broadcast_publish", tag="params", seq=int(seq), n=len(server.broadcast_targets)
            )
            # server.channels, not the spawn-time dict: a supervised
            # restart on the queue backend swaps in a fresh channel
            for pid in server.broadcast_targets:
                try:
                    extra = extras(pid)
                    if digest_mode:
                        # digest rides slot 2 of every params frame's extra
                        extra = (tuple(extra) + (None, None))[:2] + (digest,)
                    server.channels[pid].send(
                        "params",
                        arrays=arrays,
                        extra=extra,
                        seq=seq,
                        timeout=_QUEUE_TIMEOUT_S,
                    )
                except Exception as e:  # noqa: BLE001 — mark the player dead, keep serving the rest
                    server._mark_dead(pid, f"params broadcast failed: {e}")

        def _on_control(pid: int, frame) -> None:
            tag = frame.tag
            frame.release()
            if tag == JOIN_TAG:
                # supervised restart dialed back in: sync its step clock to
                # the server's insert clock and hand it the current actor
                # (it missed every broadcast while dead); its credit window
                # was already reset by begin_join
                try:
                    server.channels[pid].send(
                        "assign", extra=(server.total_inserts,), timeout=_QUEUE_TIMEOUT_S
                    )
                    arrays, digest = _actor_arrays_digest()
                    server.channels[pid].send(
                        "params",
                        arrays=arrays,
                        extra=(None, None, digest) if digest_mode else (),
                        seq=update_round,
                        timeout=_QUEUE_TIMEOUT_S,
                    )
                except Exception as e:  # noqa: BLE001
                    server._mark_dead(pid, f"join reply failed: {e}")
                return
            if tag != "ckpt_req":
                return
            try:
                reply = {
                    "agent": _np_tree(params),
                    "opt_states": _np_tree(opt_states),
                    "ratio": ratio.state_dict(),
                    "replay_server": server.state_dict(),
                }
                if cfg.buffer.checkpoint:
                    # the trainer-resident buffer rides to the lead pickled
                    # (checkpoint cadence only; disable buffer.checkpoint
                    # for buffers too big to ship over the transport)
                    reply["rb"] = server.rb
                server.channels[pid].send("ckpt_state", extra=(reply,), timeout=_QUEUE_TIMEOUT_S)
            except (PeerDiedError, OSError) as e:
                server._mark_dead(pid, f"ckpt_state reply failed: {e}")

        # initial weights (players block on this before stepping)
        _broadcast_params(0, lambda pid: ())

        while not server.all_stopped:
            if supervisor is not None:
                supervisor.poll()
            try:
                server.pump(0.05, on_control=_on_control)
            except PeerDiedError as e:
                if supervisor is not None and supervisor.recoverable():
                    time.sleep(0.2)
                    continue
                _dump_and_raise(e, "replay insert")
            # fault site: the whole replay service dies with the trainer
            hard_exit_point("replay_server_exit")
            clock = server.total_inserts  # transitions == policy steps
            if clock >= learning_starts_t and server.data_ready(need_rows):
                pending_g += ratio(max(clock - learning_starts_t, 0) + total_envs)
            g = pending_g
            if limiter is not None and g > 0:
                g = min(g, limiter.sample_allowance(g * batch_unit) // batch_unit)
            # one whole chunk per pass: a partial chunk waits for more
            # grants, a backlog drains across passes (pumping in between)
            g = dispatch_g if g >= dispatch_g else 0
            if g <= 0:
                continue
            with trace_scope("replay_sample"):
                data, sample_idx = server.sample(
                    g,
                    batch_unit,
                    runtime.next_key(),
                    beta_fn(clock),
                    sample_next_obs=cfg.buffer.sample_next_obs,
                    obs_keys=("observations",),
                )
            if sample_idx is None:
                data = runtime.shard_batch(data, axis=1)
            iter_equiv = clock // total_envs
            ema_flags = jnp.full((g,), iter_equiv % ema_every == 0)
            with timer("Time/train_time", SumMetric, sync_on_compute=cfg.metric.sync_on_compute), \
                    flight.span("train_dispatch", round=update_round + 1):
                if prioritized:
                    params, opt_states, train_metrics, td_abs = train_fn(
                        params, opt_states, data, runtime.next_key(), ema_flags
                    )
                else:
                    params, opt_states, train_metrics = train_fn(
                        params, opt_states, data, runtime.next_key(), ema_flags
                    )
                train_metrics = device_get_metrics(train_metrics)
            if sample_idx is not None:
                server.update_priorities(sample_idx, td_abs)
            rolled = health.tick()
            if rolled is not None:
                params = restore_like(params, rolled["agent"])
                opt_states = restore_like(opt_states, rolled["opt_states"])
                # the anomalous window's inserts are suspect: de-prioritize
                # everything written since the last verdict-clean horizon
                server.quarantine_recent()
            elif health.enabled and health.last_ok:
                server.mark_health_horizon()
            pending_g -= g
            if not timer.disabled:
                train_metrics["train_time"] = float(timer.compute().get("Time/train_time", 0.0))
                timer.reset()
            train_metrics["trainer_compiles"] = trainer_mon.compiles
            trainer_mon.mark_warmup_complete()
            last_metrics = train_metrics
            update_round += 1
            stats = server.stats()
            stats["beta"] = round(beta_fn(clock), 4)
            stats["events"] = server.events[-8:]
            if health.enabled:
                stats["health"] = health.stats()
            if supervisor is not None:
                stats["supervisor"] = supervisor.stats()
            if knobs["integrity"] != "off":
                from sheeprl_tpu.resilience.integrity import integrity_stats

                stats["integrity"] = integrity_stats().as_dict()
            led = obs_ledger.get_ledger()
            if led is not None:
                stats["where"] = led.snapshot()
            live = obs_fleet.get_live()
            if live is not None:
                # the remote-replay lead files these under "replay", so
                # the trainer's plane observes the same spelling (one
                # alert-rule key covers both processes)
                trainer_record = {"ts": time.time(), "step": int(clock), "replay": stats}
                if led is not None:
                    trainer_record["where"] = led.snapshot()
                live.observe(trainer_record)
            _broadcast_params(
                update_round,
                lambda pid: (last_metrics, stats if pid == 0 else None),
            )
            server.grant_credits()  # sampling freed SPI budget: resume inserts

        trainer_mon.uninstall()
        if supervisor is not None:
            supervisor.close()
        # the lead still runs its test episode + logger shutdown after the
        # stop sentinel — give it ample time before the terminate fallback
        for proc in procs.values():
            proc.join(timeout=3600.0)
    finally:
        if supervisor is not None:
            supervisor.close()
        preemption.uninstall()
        hub.close()
        flight.close_recorder()
        obs_fleet.close_live()
        for proc in procs.values():
            if proc.is_alive():
                proc.terminate()
                proc.join()
