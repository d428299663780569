"""DroQ — TPU-native main loop (reference sheeprl/algos/droq/droq.py
train:31, main:141).

Differences from SAC faithfully kept: high replay ratio (20), dropout+
LayerNorm critics, per-minibatch critic updates with EMA after every
critic step, a SEPARATE batch for the single actor/alpha update, and the
actor objective using the ensemble MEAN q-value (droq.py:124) instead of
the min. The G critic minibatches run as one ``lax.scan``."""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.droq.agent import build_agent, droq_ensemble_apply
from sheeprl_tpu.algos.sac.agent import SACPlayer, actor_action_and_log_prob
from sheeprl_tpu.algos.sac.loss import (
    critic_loss,
    critic_loss_weighted,
    entropy_loss,
    policy_loss,
    td_error_abs,
)
from sheeprl_tpu.algos.sac.sac import _make_optimizer
from sheeprl_tpu.algos.sac.utils import prepare_obs, test
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.device_buffer import maybe_create_for_transitions
from sheeprl_tpu.obs import setup_observability, trace_scope
from sheeprl_tpu.replay import per_beta_schedule, rate_limiter_from_cfg
from sheeprl_tpu.resilience import CheckpointManager
from sheeprl_tpu.resilience.sentinel import guard_update, restore_like
from sheeprl_tpu.utils.callback import load_checkpoint, restore_buffer
from sheeprl_tpu.utils.env import make_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import MetricFetchGate, device_get_metrics, Ratio, save_configs
from sheeprl_tpu.optim import restore_opt_states


def make_train_fn(
    runtime, actor, critic, txs, cfg: Dict[str, Any], target_entropy: float, prioritized: bool = False
):
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    num_critics = int(cfg.algo.critic.n)
    actor_tx, critic_tx, alpha_tx = txs

    def _core(params, opt_states, critic_data, actor_data, key, dp_axes):
        """``prioritized`` consumes ``critic_data["is_weights"]`` and
        returns per-minibatch |TD| for the priority updates (the actor
        batch stays unweighted — see loss.critic_loss_weighted); the
        False path traces exactly the pre-PER computation.  ``dp_axes``
        is the shard_map DDP core: batch rows are device-local and every
        component gradient carries an explicit ``pmean`` (see sac.py)."""
        if dp_axes is not None:
            # per-shard noise stream (dropout masks, action sampling)
            key = jax.random.fold_in(key, runtime.layout.flat_rank())
        alpha = jnp.exp(params["log_alpha"])

        # ---------------- G critic minibatches (Algorithm 2, lines 5-9)
        def critic_step(carry, inp):
            cparams, ctarget, copt = carry
            batch, k = inp
            k_next, k_drop = jax.random.split(k)
            next_actions, next_logp = actor_action_and_log_prob(
                actor, params["actor"], batch["next_observations"], k_next
            )
            qf_next = droq_ensemble_apply(
                critic, ctarget, batch["next_observations"], next_actions
            )
            min_qf_next = qf_next.min(-1, keepdims=True) - alpha * next_logp
            target = jax.lax.stop_gradient(
                batch["rewards"] + (1 - batch["terminated"]) * gamma * min_qf_next
            )

            if prioritized:

                def qf_loss_fn_w(cp):
                    q = droq_ensemble_apply(critic, cp, batch["observations"], batch["actions"], k_drop)
                    return (
                        critic_loss_weighted(q, target, num_critics, batch["is_weights"]),
                        td_error_abs(q, target),
                    )

                (qf_loss, td_abs), grads = jax.value_and_grad(qf_loss_fn_w, has_aux=True)(cparams)
            else:

                def qf_loss_fn(cp):
                    q = droq_ensemble_apply(critic, cp, batch["observations"], batch["actions"], k_drop)
                    return critic_loss(q, target, num_critics)

                qf_loss, grads = jax.value_and_grad(qf_loss_fn)(cparams)
                td_abs = None
            if dp_axes is not None:
                # explicit DDP gradient all-reduce (NCCL-equivalent psum)
                grads = jax.lax.pmean(grads, dp_axes)
                qf_loss = jax.lax.pmean(qf_loss, dp_axes)
            updates, copt = critic_tx.update(grads, copt, cparams)
            cparams = optax.apply_updates(cparams, updates)
            ctarget = optax.incremental_update(cparams, ctarget, tau)  # EMA per step
            return (cparams, ctarget, copt), ((qf_loss, td_abs) if prioritized else qf_loss)

        g = critic_data["rewards"].shape[0]
        keys = jax.random.split(key, g + 3)
        (new_critic, new_target, new_critic_opt), critic_ys = jax.lax.scan(
            critic_step,
            (params["critic"], params["target_critic"], opt_states["critic"]),
            (critic_data, keys[:g]),
        )
        qf_losses, td_abs = critic_ys if prioritized else (critic_ys, None)

        # ---------------- single actor + alpha update on a separate batch
        def actor_loss_fn(ap):
            actions, logp = actor_action_and_log_prob(actor, ap, actor_data["observations"], keys[g])
            q = droq_ensemble_apply(critic, new_critic, actor_data["observations"], actions, keys[g + 1])
            return policy_loss(alpha, logp, q.mean(-1, keepdims=True)), logp

        (actor_loss, logp), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(params["actor"])
        if dp_axes is not None:
            actor_grads = jax.lax.pmean(actor_grads, dp_axes)
            actor_loss = jax.lax.pmean(actor_loss, dp_axes)
        updates, new_actor_opt = actor_tx.update(actor_grads, opt_states["actor"], params["actor"])
        new_actor = optax.apply_updates(params["actor"], updates)

        alpha_loss, alpha_grad = jax.value_and_grad(lambda la: entropy_loss(la, logp, target_entropy))(
            params["log_alpha"]
        )
        if dp_axes is not None:
            alpha_grad = jax.lax.pmean(alpha_grad, dp_axes)
            alpha_loss = jax.lax.pmean(alpha_loss, dp_axes)
        updates, new_alpha_opt = alpha_tx.update(alpha_grad, opt_states["alpha"], params["log_alpha"])
        new_log_alpha = optax.apply_updates(params["log_alpha"], updates)

        new_params = {
            "actor": new_actor,
            "critic": new_critic,
            "target_critic": new_target,
            "log_alpha": new_log_alpha,
        }
        new_opts = {"actor": new_actor_opt, "critic": new_critic_opt, "alpha": new_alpha_opt}
        metrics = {
            "Loss/value_loss": qf_losses.mean(),
            "Loss/policy_loss": actor_loss,
            "Loss/alpha_loss": alpha_loss,
            # actor+alpha grad norm (critic grads live inside the scan;
            # its health is covered by the value loss + update norm)
            "Grads/agent": optax.global_norm((actor_grads, alpha_grad)),
        }
        if prioritized:
            # (G, B) |TD| rides back for update_priorities — stays on device
            return new_params, new_opts, metrics, td_abs
        return new_params, new_opts, metrics

    def train(params, opt_states, critic_data, actor_data, key):
        if runtime.ddp_gate(critic_data["rewards"].shape[1], "DroQ"):
            # explicit DDP core over the flattened batch axes (see sac.py)
            from jax.sharding import PartitionSpec as SMP

            from sheeprl_tpu.parallel.sharding import BATCH_AXES
            from jax import shard_map

            critic_specs = jax.tree_util.tree_map(lambda _: SMP(None, BATCH_AXES), critic_data)
            actor_specs = jax.tree_util.tree_map(lambda _: SMP(BATCH_AXES), actor_data)
            td_spec = (SMP(None, BATCH_AXES),) if prioritized else ()

            def body(params, opt_states, critic_data, actor_data, key):
                return _core(params, opt_states, critic_data, actor_data, key, BATCH_AXES)

            return shard_map(
                body,
                mesh=runtime.mesh,
                in_specs=(SMP(), SMP(), critic_specs, actor_specs, SMP()),
                out_specs=(SMP(), SMP(), SMP()) + td_spec,
                check_vma=False,
            )(params, opt_states, critic_data, actor_data, key)
        return _core(params, opt_states, critic_data, actor_data, key, None)

    # training health sentinel hook (resilience/sentinel.py)
    return guard_update(runtime, train, cfg, n_state=2, donate_argnums=(0, 1))


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    import gymnasium as gym
    from gymnasium.vector import AsyncVectorEnv, AutoresetMode, SyncVectorEnv

    world_size = runtime.world_size
    runtime.seed_everything(cfg.seed)
    state = load_checkpoint(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None

    logger = get_logger(runtime, cfg)
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name)
    runtime.print(f"Log dir: {log_dir}")
    observability = setup_observability(runtime, cfg, log_dir, logger=logger)
    if logger:
        logger.log_hyperparams(cfg)

    total_envs = cfg.env.num_envs * world_size
    thunks = [
        make_env(cfg, cfg.seed + i, 0, log_dir if runtime.is_global_zero else None, "train", vector_env_idx=i)
        for i in range(total_envs)
    ]
    envs = (
        SyncVectorEnv(thunks, autoreset_mode=AutoresetMode.SAME_STEP)
        if cfg.env.sync_env
        else AsyncVectorEnv(thunks, context="spawn", autoreset_mode=AutoresetMode.SAME_STEP)
    )
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the DroQ agent")
    if len(cfg.algo.mlp_keys.encoder) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")
    mlp_keys = list(cfg.algo.mlp_keys.encoder)

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

    player = SACPlayer(
        actor,
        params["actor"],
        lambda obs: prepare_obs(obs, mlp_keys=mlp_keys, num_envs=total_envs),
        device=runtime.player_device(params["actor"]),
    )

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(dict(cfg.metric.aggregator))

    buffer_size = cfg.buffer.size // total_envs if not cfg.dry_run else 1
    rb = ReplayBuffer(
        max(buffer_size, 1),
        total_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{runtime.global_rank}"),
        obs_keys=("observations",),
    )
    if state and cfg.buffer.checkpoint:
        rb = restore_buffer(state["rb"], memmap=cfg.buffer.memmap)
    # HBM-resident replay window + on-device sampling (data/device_buffer.py)
    device_cache = maybe_create_for_transitions(
        cfg, runtime, rb, state if state and cfg.buffer.checkpoint else None
    )
    # prioritized replay + samples-per-insert rate control (see sac.py —
    # DroQ shares the same critic-side PER semantics)
    prioritized = device_cache is not None and device_cache.prioritized
    beta_fn = per_beta_schedule(
        cfg.buffer.get("per_beta", 0.4),
        cfg.buffer.get("per_beta_end", 1.0),
        int(cfg.algo.total_steps),
    )
    limiter = rate_limiter_from_cfg(cfg, default_min_size=max(int(cfg.algo.learning_starts), 1))
    if limiter is not None and state is not None and state.get("rate_limiter"):
        limiter.load_state_dict(state["rate_limiter"])

    last_train = 0
    train_step = 0
    start_iter = (state["iter_num"] // world_size) + 1 if state else 1
    policy_step = state["iter_num"] * cfg.env.num_envs if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    policy_steps_per_iter = int(total_envs)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_steps = learning_starts - int(learning_starts > 0)
    if state:
        cfg.algo.per_rank_batch_size = state["batch_size"] // world_size
        learning_starts += start_iter
        prefill_steps += start_iter

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state:
        ratio.load_state_dict(state["ratio"])

    ckpt_mgr = CheckpointManager(
        runtime, cfg, log_dir, observability=observability, last_checkpoint=last_checkpoint
    )
    train_fn = make_train_fn(
        runtime, actor, critic, (actor_tx, critic_tx, alpha_tx), cfg, target_entropy,
        prioritized=prioritized,
    )
    health = train_fn.health.bind(ckpt_mgr=ckpt_mgr, select=("agent", "opt_states"))
    if health.enabled:
        observability.health_stats = health.stats

    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed)[0]
    cumulative_per_rank_gradient_steps = 0

    metric_fetch_gate = MetricFetchGate(cfg.metric.get("fetch_every", 1))
    for iter_num in range(start_iter, total_iters + 1):
        observability.on_iteration(policy_step)
        policy_step += policy_steps_per_iter

        with timer("Time/env_interaction_time", SumMetric, sync_on_compute=False):
            if iter_num <= learning_starts:
                actions = envs.action_space.sample()
            else:
                actions = np.asarray(player.get_actions(obs, runtime.next_key()))
            next_obs, rewards, terminated, truncated, infos = envs.step(
                actions.reshape(envs.action_space.shape)
            )
            rewards = rewards.reshape(total_envs, -1)

        if cfg.metric.log_level > 0 and "final_info" in infos:
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

        step_data["terminated"] = terminated.reshape(1, total_envs, -1).astype(np.uint8)
        step_data["truncated"] = truncated.reshape(1, total_envs, -1).astype(np.uint8)
        step_data["actions"] = actions.reshape(1, total_envs, -1).astype(np.float32)
        step_data["observations"] = np.concatenate([obs[k] for k in mlp_keys], axis=-1).astype(np.float32)[
            np.newaxis
        ]
        if not cfg.buffer.sample_next_obs:
            step_data["next_observations"] = flat_next_obs[np.newaxis]
        step_data["rewards"] = rewards[np.newaxis].astype(np.float32)
        rb.add(step_data, validate_args=cfg.buffer.validate_args)
        if limiter is not None:
            limiter.insert(total_envs)
        if device_cache is not None:
            device_cache.add(step_data)
        obs = next_obs

        if iter_num >= learning_starts:
            per_rank_gradient_steps = ratio(
                (policy_step - prefill_steps + policy_steps_per_iter) / world_size
            )
            bs = cfg.algo.per_rank_batch_size * world_size
            if limiter is not None and per_rank_gradient_steps > 0:
                # sample-side throttle: clip the granted critic minibatches
                # to the SPI budget (DroQ's high replay ratio is exactly the
                # regime where training outruns collection)
                allowed = limiter.sample_allowance(per_rank_gradient_steps * bs) // bs
                if allowed < per_rank_gradient_steps:
                    limiter.sample_stalls += 1
                per_rank_gradient_steps = allowed
            if per_rank_gradient_steps > 0:
                g = per_rank_gradient_steps
                sample_idx = None
                if device_cache is not None and device_cache.can_sample_transitions(
                    cfg.buffer.sample_next_obs
                ):
                    # on-device gathers + casts; nothing crosses the link
                    if prioritized:
                        sampled, sample_idx = device_cache.sample_transitions_per(
                            g, bs, runtime.next_key(),
                            beta_fn(policy_step),
                            sample_next_obs=cfg.buffer.sample_next_obs,
                            obs_keys=("observations",),
                        )
                        critic_data = {k: v.astype(jnp.float32) for k, v in sampled.items()}
                    else:
                        critic_data = {
                            k: v.astype(jnp.float32)
                            for k, v in device_cache.sample_transitions(
                                g, bs, runtime.next_key(),
                                sample_next_obs=cfg.buffer.sample_next_obs,
                                obs_keys=("observations",),
                            ).items()
                        }
                    actor_data = {
                        k: v[0].astype(jnp.float32)
                        for k, v in device_cache.sample_transitions(
                            1, bs, runtime.next_key(),
                            sample_next_obs=cfg.buffer.sample_next_obs,
                            obs_keys=("observations",),
                        ).items()
                    }
                else:
                    critic_sample = rb.sample(batch_size=g * bs, sample_next_obs=cfg.buffer.sample_next_obs)
                    critic_data = {
                        k: np.asarray(v, np.float32).reshape(g, bs, *v.shape[2:])
                        for k, v in critic_sample.items()
                    }
                    if prioritized:
                        # the cache bailed at runtime: train unweighted on
                        # the uniform host sample, no priorities to update
                        critic_data["is_weights"] = np.ones((g, bs, 1), np.float32)
                    actor_sample = rb.sample(batch_size=bs, sample_next_obs=cfg.buffer.sample_next_obs)
                    actor_data = {
                        k: np.asarray(v, np.float32).reshape(bs, *v.shape[2:])
                        for k, v in actor_sample.items()
                    }
                    # shard the batch axes over the mesh so each device trains
                    # on its own rows (GSPMD inserts the grad psums)
                    critic_data = runtime.shard_batch(critic_data, axis=1)
                    actor_data = runtime.shard_batch(actor_data, axis=0)
                if limiter is not None:
                    limiter.sample(g * bs)
                with timer("Time/train_time", SumMetric, sync_on_compute=cfg.metric.sync_on_compute):
                    if prioritized:
                        params, opt_states, train_metrics, td_abs = train_fn(
                            params, opt_states, critic_data, actor_data, runtime.next_key()
                        )
                    else:
                        params, opt_states, train_metrics = train_fn(
                            params, opt_states, critic_data, actor_data, runtime.next_key()
                        )
                if sample_idx is not None:
                    device_cache.update_priorities(sample_idx, td_abs)
                rolled = health.tick()
                if rolled is not None:
                    params = restore_like(params, rolled["agent"])
                    opt_states = restore_like(opt_states, rolled["opt_states"])
                player.params = params["actor"]
                cumulative_per_rank_gradient_steps += g
                train_step += world_size
                if aggregator and not aggregator.disabled and metric_fetch_gate():
                    with trace_scope("block_until_ready"):
                        fetched_metrics = device_get_metrics(train_metrics)
                    for k, v in fetched_metrics.items():
                        aggregator.update(k, v)

        if cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters
        ):
            replay_extra = None
            if prioritized or limiter is not None:
                replay_rec: Dict[str, Any] = {}
                if prioritized:
                    replay_rec["prioritized"] = True
                    replay_rec["beta"] = round(beta_fn(policy_step), 4)
                if limiter is not None:
                    replay_rec["limiter"] = limiter.stats()
                replay_extra = {"replay": replay_rec}
            observability.on_log(policy_step, train_step, extra=replay_extra)
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
                    if timer_metrics.get("Time/train_time", 0) > 0:
                        logger.log_metrics(
                            {"Time/sps_train": (train_step - last_train) / timer_metrics["Time/train_time"]},
                            policy_step,
                        )
                    if timer_metrics.get("Time/env_interaction_time", 0) > 0:
                        logger.log_metrics(
                            {
                                "Time/sps_env_interaction": (
                                    (policy_step - last_log) / world_size * cfg.env.action_repeat
                                )
                                / timer_metrics["Time/env_interaction_time"]
                            },
                            policy_step,
                        )
                    timer.reset()
            last_log = policy_step
            last_train = train_step

        def _ckpt_state():
            ckpt_state = {
                "agent": params,
                "opt_states": opt_states,
                "ratio": ratio.state_dict(),
                "iter_num": iter_num * world_size,
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": ckpt_mgr.last_checkpoint,
            }
            if cfg.buffer.checkpoint:
                ckpt_state["rb"] = rb
            if device_cache is not None and device_cache.prioritized:
                ckpt_state["replay_priority"] = device_cache.priority_state()
            if limiter is not None:
                ckpt_state["rate_limiter"] = limiter.state_dict()
            return ckpt_state

        ckpt_mgr.maybe_checkpoint(
            policy_step=policy_step, is_last=iter_num == total_iters, state_fn=_ckpt_state
        )
        if ckpt_mgr.preempted:
            runtime.print(
                f"Preemption signal: emergency checkpoint written, stopping at iter {iter_num}"
            )
            break

    ckpt_mgr.close()
    envs.close()
    observability.close()
    if runtime.is_global_zero and cfg.algo.run_test:
        test_rew = test(player, runtime, cfg, log_dir)
        if logger:
            logger.log_metrics({"Test/cumulative_reward": test_rew}, policy_step)
    if logger:
        logger.finalize()
