"""SAC-AE — TPU-native main loop (reference sheeprl/algos/sac_ae/sac_ae.py
train:35, main:120).

One jitted ``lax.scan`` over the iteration's G gradient steps; per-step
cadences (actor every N, decoder every M, target EMA every K cumulative
gradient steps) are ``lax.cond`` branches keyed on a carried counter, so the
whole schedule compiles once. Five optimizers as in the reference: critic
(encoder + q-ensemble jointly), actor, alpha, encoder, decoder — the
encoder is stepped by both the critic and the autoencoder losses with
separate optimizer states (reference sac_ae.py:61-117)."""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.sac.loss import critic_loss, entropy_loss, policy_loss
from sheeprl_tpu.algos.sac.sac import _make_optimizer
from sheeprl_tpu.algos.sac_ae.agent import SACAEPlayer, build_agent
from sheeprl_tpu.algos.sac_ae.utils import prepare_obs, preprocess_obs, test
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.device_buffer import maybe_create_for_transitions
from sheeprl_tpu.obs import setup_observability, trace_scope
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

sg = jax.lax.stop_gradient


def make_train_fn(runtime, modules, txs, cfg: Dict[str, Any], target_entropy: float):
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    encoder_tau = float(cfg.algo.encoder.tau)
    l2_lambda = float(cfg.algo.decoder.l2_lambda)
    target_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    actor_freq = int(cfg.algo.actor.per_rank_update_freq)
    decoder_freq = int(cfg.algo.decoder.per_rank_update_freq)
    num_critics = int(cfg.algo.critic.n)
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    cnn_keys_dec = tuple(cfg.algo.cnn_keys.decoder)
    mlp_keys_dec = tuple(cfg.algo.mlp_keys.decoder)
    critic_tx, actor_tx, alpha_tx, encoder_tx, decoder_tx = txs

    def _norm(data, prefix=""):
        obs = {}
        for k in cnn_keys:
            obs[k] = data[prefix + k] / 255.0
        for k in mlp_keys:
            obs[k] = data[prefix + k]
        return obs

    def train(params, opt_states, data, key, counter0):
        """data: (G, B, ...); counter0: cumulative gradient-step counter at
        the start of this call (host int, traced)."""

        def one_step(carry, inp):
            params, opt_states, counter = carry
            batch, k = inp
            k1, k2, k3 = jax.random.split(k, 3)
            alpha = jnp.exp(params["log_alpha"])
            obs = _norm(batch)
            next_obs = _norm(batch, "next_")

            # ------------------------- critic update (encoder + ensemble)
            next_actions, next_logp = modules.actions_and_log_probs(
                params["critic"]["encoder"], params["actor"], next_obs, k1
            )
            target_feat = modules.critic_features(params["target"]["encoder"], next_obs)
            qf_next = modules.q_values(params["target"]["qfs"], target_feat, next_actions)
            min_qf_next = qf_next.min(-1, keepdims=True) - alpha * next_logp
            next_qf_value = sg(
                batch["rewards"] + (1 - batch["terminated"]) * gamma * min_qf_next
            )

            def qf_loss_fn(cp):
                feat = modules.critic_features(cp["encoder"], obs)
                qf_values = modules.q_values(cp["qfs"], feat, batch["actions"])
                return critic_loss(qf_values, next_qf_value, num_critics)

            qf_loss, qf_grads = jax.value_and_grad(qf_loss_fn)(params["critic"])
            updates, new_critic_opt = critic_tx.update(qf_grads, opt_states["critic"], params["critic"])
            new_critic = optax.apply_updates(params["critic"], updates)

            # ------------------------- target EMA (qfs tau, encoder tau)
            def do_ema():
                return {
                    "encoder": optax.incremental_update(
                        new_critic["encoder"], params["target"]["encoder"], encoder_tau
                    ),
                    "qfs": optax.incremental_update(
                        new_critic["qfs"], params["target"]["qfs"], tau
                    ),
                }

            new_target = jax.lax.cond(
                counter % target_freq == 0, do_ema, lambda: params["target"]
            )

            # ------------------------- actor + alpha update (delayed)
            def do_actor():
                def actor_loss_fn(ap):
                    actions, logp = modules.actions_and_log_probs(
                        new_critic["encoder"], ap, obs, k2
                    )
                    feat = modules.critic_features(new_critic["encoder"], obs)
                    q = modules.q_values(new_critic["qfs"], feat, actions)
                    return policy_loss(alpha, logp, q.min(-1, keepdims=True)), logp

                (a_loss, logp), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
                    params["actor"]
                )
                upd, new_actor_opt = actor_tx.update(actor_grads, opt_states["actor"], params["actor"])
                new_actor = optax.apply_updates(params["actor"], upd)

                al_loss, alpha_grad = jax.value_and_grad(
                    lambda la: entropy_loss(la, sg(logp), target_entropy)
                )(params["log_alpha"])
                upd, new_alpha_opt = alpha_tx.update(alpha_grad, opt_states["alpha"], params["log_alpha"])
                new_log_alpha = optax.apply_updates(params["log_alpha"], upd)
                return new_actor, new_actor_opt, new_log_alpha, new_alpha_opt, a_loss, al_loss

            new_actor, new_actor_opt, new_log_alpha, new_alpha_opt, actor_loss_v, alpha_loss_v = (
                jax.lax.cond(
                    counter % actor_freq == 0,
                    do_actor,
                    lambda: (
                        params["actor"],
                        opt_states["actor"],
                        params["log_alpha"],
                        opt_states["alpha"],
                        jnp.zeros(()),
                        jnp.zeros(()),
                    ),
                )
            )

            # ------------------------- autoencoder update (encoder+decoder)
            def do_ae():
                def ae_loss_fn(enc_dec):
                    enc_params, dec_params = enc_dec
                    hidden = modules.critic_features(enc_params, obs)
                    reconstruction = modules.decode(dec_params, hidden)
                    loss = jnp.zeros(())
                    l2 = (0.5 * (hidden**2).sum(-1)).mean()
                    for kk in cnn_keys_dec:
                        target = preprocess_obs(batch[kk], k3, bits=5)
                        loss += jnp.mean((target - reconstruction[kk]) ** 2) + l2_lambda * l2
                    for kk in mlp_keys_dec:
                        loss += jnp.mean((batch[kk] - reconstruction[kk]) ** 2) + l2_lambda * l2
                    return loss

                rec_loss, (enc_grads, dec_grads) = jax.value_and_grad(ae_loss_fn)(
                    (new_critic["encoder"], params["decoder"])
                )
                upd, new_enc_opt = encoder_tx.update(
                    enc_grads, opt_states["encoder"], new_critic["encoder"]
                )
                new_enc = optax.apply_updates(new_critic["encoder"], upd)
                upd, new_dec_opt = decoder_tx.update(
                    dec_grads, opt_states["decoder"], params["decoder"]
                )
                new_dec = optax.apply_updates(params["decoder"], upd)
                return new_enc, new_enc_opt, new_dec, new_dec_opt, rec_loss

            new_encoder, new_enc_opt, new_decoder, new_dec_opt, rec_loss_v = jax.lax.cond(
                counter % decoder_freq == 0,
                do_ae,
                lambda: (
                    new_critic["encoder"],
                    opt_states["encoder"],
                    params["decoder"],
                    opt_states["decoder"],
                    jnp.zeros(()),
                ),
            )

            new_params = {
                "critic": {"encoder": new_encoder, "qfs": new_critic["qfs"]},
                "target": new_target,
                "actor": new_actor,
                "decoder": new_decoder,
                "log_alpha": new_log_alpha,
            }
            new_opt_states = {
                "critic": new_critic_opt,
                "actor": new_actor_opt,
                "alpha": new_alpha_opt,
                "encoder": new_enc_opt,
                "decoder": new_dec_opt,
            }
            losses = jnp.stack([qf_loss, actor_loss_v, alpha_loss_v, rec_loss_v])
            flags = jnp.stack(
                [
                    jnp.ones(()),
                    (counter % actor_freq == 0).astype(jnp.float32),
                    (counter % actor_freq == 0).astype(jnp.float32),
                    (counter % decoder_freq == 0).astype(jnp.float32),
                ]
            )
            return (new_params, new_opt_states, counter + 1), (losses, flags)

        g = data["rewards"].shape[0]
        keys = jax.random.split(key, g)
        (params, opt_states, _), (losses, flags) = jax.lax.scan(
            one_step, (params, opt_states, counter0), (data, keys)
        )
        totals = flags.sum(0)
        mean_losses = losses.sum(0) / jnp.maximum(totals, 1.0)
        metrics = {
            "Loss/value_loss": mean_losses[0],
            "Loss/policy_loss": mean_losses[1],
            "Loss/alpha_loss": mean_losses[2],
            "Loss/reconstruction_loss": mean_losses[3],
        }
        return params, opt_states, metrics

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
        raise ValueError("Only continuous action space is supported for the SAC-AE agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if (
        len(set(cfg.algo.cnn_keys.decoder) - set(cfg.algo.cnn_keys.encoder)) > 0
        or len(set(cfg.algo.mlp_keys.decoder) - set(cfg.algo.mlp_keys.encoder)) > 0
    ):
        raise RuntimeError("The decoder keys must be contained in the encoder ones")
    obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)
    cnn_keys = list(cfg.algo.cnn_keys.encoder)

    modules, params, target_entropy = build_agent(
        runtime, cfg, observation_space, action_space, state["agent"] if state else None
    )
    params = runtime.replicate(
        runtime.to_param_dtype(params, exclude=("target", "log_alpha"))
    )

    critic_tx = _make_optimizer(cfg.algo.critic.optimizer, runtime.precision)
    actor_tx = _make_optimizer(cfg.algo.actor.optimizer, runtime.precision)
    alpha_tx = _make_optimizer(cfg.algo.alpha.optimizer, runtime.precision)
    encoder_tx = _make_optimizer(cfg.algo.encoder.optimizer, runtime.precision)
    decoder_tx = _make_optimizer(cfg.algo.decoder.optimizer, runtime.precision)
    if state is not None:
        # the encoder opt state pairs with the encoder SUBTREE nested under
        # the critic params (shared critic/encoder tree, see init below)
        params_for_opt = {**params, "encoder": params["critic"]["encoder"]}
        opt_states = restore_opt_states(
            state["opt_states"], params_for_opt, runtime.precision, key_map={"alpha": "log_alpha"}
        )
    else:
        opt_states = runtime.replicate(
            {
                "critic": critic_tx.init(params["critic"]),
                "actor": actor_tx.init(params["actor"]),
                "alpha": alpha_tx.init(params["log_alpha"]),
                "encoder": encoder_tx.init(params["critic"]["encoder"]),
                "decoder": decoder_tx.init(params["decoder"]),
            }
        )

    player_params = {"encoder": params["critic"]["encoder"], "actor": params["actor"]}
    player = SACAEPlayer(
        modules,
        player_params,
        lambda obs: prepare_obs(obs, cnn_keys=cnn_keys, num_envs=total_envs),
        device=runtime.player_device(player_params),
    )

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(dict(cfg.metric.aggregator))

    buffer_size = cfg.buffer.size // int(total_envs) if not cfg.dry_run else 1
    rb = ReplayBuffer(
        max(buffer_size, 1),
        total_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{runtime.global_rank}"),
        obs_keys=tuple(obs_keys),
    )
    if state and cfg.buffer.checkpoint:
        rb = restore_buffer(
            state["rb"],
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{runtime.global_rank}"),
        )
    # HBM-resident replay window + on-device sampling (data/device_buffer.py)
    device_cache = maybe_create_for_transitions(
        cfg, runtime, rb, state if state and cfg.buffer.checkpoint else None
    )

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
        runtime, modules, (critic_tx, actor_tx, alpha_tx, encoder_tx, decoder_tx), cfg, target_entropy
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

        for k in obs_keys:
            step_data[k] = obs[k][np.newaxis]
            if not cfg.buffer.sample_next_obs:
                step_data[f"next_{k}"] = real_next_obs[k][np.newaxis]
        step_data["terminated"] = terminated.reshape(1, total_envs, -1).astype(np.uint8)
        step_data["truncated"] = truncated.reshape(1, total_envs, -1).astype(np.uint8)
        step_data["actions"] = actions.reshape(1, total_envs, -1).astype(np.float32)
        step_data["rewards"] = rewards[np.newaxis].astype(np.float32)
        rb.add(step_data, validate_args=cfg.buffer.validate_args)
        if device_cache is not None:
            device_cache.add(step_data)
        obs = next_obs

        if iter_num >= learning_starts:
            per_rank_gradient_steps = ratio(
                (policy_step - prefill_steps + policy_steps_per_iter) / world_size
            )
            if per_rank_gradient_steps > 0:
                g = per_rank_gradient_steps
                batch_total = g * cfg.algo.per_rank_batch_size * world_size
                if device_cache is not None and device_cache.can_sample_transitions(
                    cfg.buffer.sample_next_obs
                ):
                    # on-device gather + cast (pixels stay uint8 in HBM and
                    # widen to f32 on device); nothing crosses the link
                    data = {
                        k: v.astype(jnp.float32)
                        for k, v in device_cache.sample_transitions(
                            g,
                            cfg.algo.per_rank_batch_size * world_size,
                            runtime.next_key(),
                            sample_next_obs=cfg.buffer.sample_next_obs,
                            obs_keys=tuple(obs_keys),
                        ).items()
                    }
                else:
                    sample = rb.sample(
                        batch_size=batch_total,
                        sample_next_obs=cfg.buffer.sample_next_obs,
                    )
                    data = {
                        k: np.asarray(v, dtype=np.float32).reshape(
                            g, cfg.algo.per_rank_batch_size * world_size, *v.shape[2:]
                        )
                        for k, v in sample.items()
                    }
                    # shard the batch axis over the mesh so each device
                    # trains on its own rows (GSPMD inserts the grad psums)
                    data = runtime.shard_batch(data, axis=1)
                with timer("Time/train_time", SumMetric, sync_on_compute=cfg.metric.sync_on_compute):
                    params, opt_states, train_metrics = train_fn(
                        params,
                        opt_states,
                        data,
                        runtime.next_key(),
                        jnp.asarray(cumulative_per_rank_gradient_steps),
                    )
                rolled = health.tick()
                if rolled is not None:
                    params = restore_like(params, rolled["agent"])
                    opt_states = restore_like(opt_states, rolled["opt_states"])
                player.params = {"encoder": params["critic"]["encoder"], "actor": params["actor"]}
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
            observability.on_log(policy_step, train_step)
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
