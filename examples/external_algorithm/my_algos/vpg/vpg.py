"""Vanilla policy gradient (REINFORCE with a learned value baseline) as an
EXTERNAL algorithm: this file lives outside the sheeprl_tpu package and
registers itself through the public registry.

The walkthrough in howto/register_external_algorithm.md builds this file
up section by section.  TPU-first structure (the same rules the built-in
algorithms follow):

- ONE jitted update per iteration; the returns-to-go recursion is a
  reversed ``lax.scan``, not a Python loop;
- the update takes and returns ALL mutable state (params, opt state);
- env interaction stays host-side, with the policy pinned via
  ``runtime.player_device`` so an env step does not wait on the chip;
- no minibatch shuffling, so the update needs no ``shard_map``: with the
  rollout sharded over the mesh's env axis GSPMD parallelizes the global
  mean losses correctly on its own (contrast ppo.py, whose epoch shuffle
  is exactly what forces its explicit DDP core).
"""

from __future__ import annotations

import os
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from my_algos.vpg.agent import build_agent, prepare_obs, VPGPlayer
from my_algos.vpg.utils import test
from sheeprl_tpu.algos.ppo.ppo import build_ppo_optimizer
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.optim import restore_opt_states
from sheeprl_tpu.utils.callback import CheckpointCallback, load_checkpoint
from sheeprl_tpu.utils.env import make_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import device_get_metrics, save_configs


def make_update_fn(runtime, module, tx, cfg: Dict[str, Any]):
    gamma = float(cfg.algo.gamma)
    vf_coef = float(cfg.algo.vf_coef)
    ent_coef = float(cfg.algo.ent_coef)

    def update(params, opt_state, obs, actions, rewards, dones, next_obs):
        """obs (T, N, D), actions (T, N), rewards/dones (T, N, 1)."""

        def loss_fn(p):
            logits, values = module.apply(p, obs)  # (T, N, A), (T, N)
            _, next_value = module.apply(p, next_obs)  # bootstrap (N,)

            def ret_step(carry, inp):
                r, d = inp
                g = r + gamma * carry * (1.0 - d)
                return g, g

            _, returns = jax.lax.scan(
                ret_step,
                next_value,
                (rewards[..., 0], dones[..., 0]),
                reverse=True,
            )  # (T, N)
            adv = returns - jax.lax.stop_gradient(values)
            logp_all = jax.nn.log_softmax(logits)
            logp = jnp.take_along_axis(logp_all, actions[..., None].astype(jnp.int32), -1)[..., 0]
            pg_loss = -(logp * jax.lax.stop_gradient(adv)).mean()
            v_loss = 0.5 * jnp.square(values - jax.lax.stop_gradient(returns)).mean()
            entropy = -(jnp.exp(logp_all) * logp_all).sum(-1).mean()
            total = pg_loss + vf_coef * v_loss - ent_coef * entropy
            return total, (pg_loss, v_loss)

        (_, (pg_loss, v_loss)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, {"Loss/policy_loss": pg_loss, "Loss/value_loss": v_loss}

    # setup_step jits under the mesh and donates the old params/opt buffers
    return runtime.setup_step(update, donate_argnums=(0, 1))


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    if len(cfg.algo.cnn_keys.encoder) > 0:
        raise ValueError("vpg supports only vector observations (mlp keys)")
    world_size = runtime.world_size
    runtime.seed_everything(cfg.seed)

    state = load_checkpoint(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None

    logger = get_logger(runtime, cfg)
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name)
    runtime.print(f"Log dir: {log_dir}")
    if logger:
        logger.log_hyperparams(cfg)

    from gymnasium.vector import AutoresetMode, SyncVectorEnv

    total_envs = cfg.env.num_envs * world_size
    envs = SyncVectorEnv(
        [
            make_env(cfg, cfg.seed + i, 0, log_dir if runtime.is_global_zero else None,
                     "train", vector_env_idx=i)
            for i in range(total_envs)
        ],
        autoreset_mode=AutoresetMode.SAME_STEP,
    )
    if not isinstance(envs.single_action_space, gym.spaces.Discrete):
        raise ValueError("vpg needs a single Discrete action space")
    obs_keys = list(cfg.algo.mlp_keys.encoder)
    actions_dim = (int(envs.single_action_space.n),)

    module, params = build_agent(
        runtime, actions_dim, False, cfg, envs.single_observation_space,
        state["agent"] if state else None,
    )
    params = runtime.replicate(runtime.to_param_dtype(params))
    # the shared optimizer factory honors EVERY key the composed /optim
    # group sets (eps, betas, weight_decay) plus precision master weights —
    # optax.adam(lr) alone would silently drop them
    tx = build_ppo_optimizer(cfg.algo.optimizer, 0.0, runtime.precision)
    opt_state = (
        runtime.replicate(tx.init(params))
        if state is None
        else restore_opt_states(state["optimizer"], params, runtime.precision)
    )
    update_fn = make_update_fn(runtime, module, tx, cfg)
    player = VPGPlayer(module, params, obs_keys, total_envs,
                       device=runtime.player_device(params))

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)
    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(dict(cfg.metric.aggregator))

    rb = ReplayBuffer(
        cfg.algo.rollout_steps,
        total_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{runtime.global_rank}"),
        obs_keys=obs_keys,
    )
    ckpt_cb = CheckpointCallback(keep_last=cfg.checkpoint.keep_last)

    start_iter = (state["iter_num"] // world_size) + 1 if state else 1
    policy_step = state["iter_num"] * cfg.env.num_envs * cfg.algo.rollout_steps if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    policy_steps_per_iter = int(cfg.env.num_envs * cfg.algo.rollout_steps * world_size)
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1

    step_data: Dict[str, np.ndarray] = {}
    next_obs_np = envs.reset(seed=cfg.seed)[0]
    for iter_num in range(start_iter, total_iters + 1):
        with timer("Time/env_interaction_time", SumMetric, sync_on_compute=False):
            for _ in range(cfg.algo.rollout_steps):
                policy_step += cfg.env.num_envs * world_size
                actions, _, _ = player.get_actions(next_obs_np, runtime.next_key())
                actions = np.asarray(actions)
                obs, rewards, terminated, truncated, info = envs.step(actions)
                rewards = rewards.astype(np.float32)
                # time-limit truncation is NOT termination: bootstrap the
                # cut episode's tail with gamma * V(final_obs) so the
                # returns/value targets stay unbiased (same treatment as
                # the built-in PPO/A2C)
                truncated_envs = np.nonzero(truncated)[0]
                if len(truncated_envs) > 0:
                    real_next_obs = {k: np.array(v) for k, v in obs.items()}
                    for env_idx in truncated_envs:
                        final = info["final_obs"][env_idx]
                        for k in obs_keys:
                            real_next_obs[k][env_idx] = final[k]
                    vals = np.asarray(player.get_values(real_next_obs))
                    rewards[truncated_envs] += cfg.algo.gamma * vals[truncated_envs]
                dones = np.logical_or(terminated, truncated)
                for k in obs_keys:
                    step_data[k] = next_obs_np[k][np.newaxis]
                step_data["actions"] = actions.reshape(1, total_envs, 1).astype(np.float32)
                step_data["rewards"] = rewards.reshape(1, total_envs, 1).astype(np.float32)
                step_data["dones"] = dones.reshape(1, total_envs, 1).astype(np.float32)
                rb.add(step_data, validate_args=cfg.buffer.validate_args)
                next_obs_np = obs

                if cfg.metric.log_level > 0 and "final_info" in info:
                    ep = info["final_info"].get("episode")
                    if ep is not None:
                        for i in np.nonzero(info["final_info"]["_episode"])[0]:
                            if aggregator and "Rewards/rew_avg" in aggregator:
                                aggregator.update("Rewards/rew_avg", float(ep["r"][i]))
                            if aggregator and "Game/ep_len_avg" in aggregator:
                                aggregator.update("Game/ep_len_avg", float(ep["l"][i]))
                            runtime.print(
                                f"Rank-0: policy_step={policy_step}, reward_env_{i}={float(ep['r'][i])}"
                            )

        data = rb.to_arrays()
        # env-axis sharding: each mesh device gets its own env columns
        obs_dev = runtime.shard_batch(
            jnp.concatenate(
                [jnp.asarray(data[k], jnp.float32).reshape(*data[k].shape[:2], -1) for k in obs_keys],
                axis=-1,
            ),
            axis=1,
        )
        next_obs_dev = runtime.shard_batch(prepare_obs(next_obs_np, obs_keys, total_envs), axis=0)
        with timer("Time/train_time", SumMetric, sync_on_compute=cfg.metric.sync_on_compute):
            params, opt_state, train_metrics = update_fn(
                params, opt_state, obs_dev,
                runtime.shard_batch(jnp.asarray(data["actions"][..., 0]), axis=1),
                runtime.shard_batch(jnp.asarray(data["rewards"]), axis=1),
                runtime.shard_batch(jnp.asarray(data["dones"]), axis=1),
                next_obs_dev,
            )
        player.params = params

        if aggregator and not aggregator.disabled:
            for k, v in device_get_metrics(train_metrics).items():
                aggregator.update(k, v)
        if cfg.metric.log_level > 0 and logger and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters
        ):
            if aggregator and not aggregator.disabled:
                logger.log_metrics(aggregator.compute(), policy_step)
                aggregator.reset()
            if not timer.disabled:
                timer_metrics = timer.compute()
                if timer_metrics.get("Time/train_time", 0) > 0:
                    logger.log_metrics(
                        {"Time/sps_train": (iter_num - start_iter + 1) / timer_metrics["Time/train_time"]},
                        policy_step,
                    )
                if timer_metrics.get("Time/env_interaction_time", 0) > 0:
                    logger.log_metrics(
                        {
                            "Time/sps_env_interaction": (policy_step - last_log)
                            / timer_metrics["Time/env_interaction_time"]
                        },
                        policy_step,
                    )
                timer.reset()
            last_log = policy_step

        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            iter_num == total_iters and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            ckpt_cb.save(
                runtime,
                os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{runtime.global_rank}.ckpt"),
                {
                    "agent": params,
                    "optimizer": opt_state,
                    "iter_num": iter_num * world_size,
                    "batch_size": cfg.algo.rollout_steps * world_size,
                    "last_log": last_log,
                    "last_checkpoint": last_checkpoint,
                },
            )

    envs.close()
    if runtime.is_global_zero and cfg.algo.run_test:
        test(player, runtime, cfg, log_dir)
    if logger:
        logger.finalize()
