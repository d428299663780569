"""Recurrent PPO — TPU-native main loop.

Counterpart of reference sheeprl/algos/ppo_recurrent/ppo_recurrent.py
(train:30, main:120). TPU-first design decisions:

- the reference splits rollouts into episodes, chunks them to
  ``per_rank_sequence_length`` and pads to a ragged max length
  (ppo_recurrent.py:424-444) — dynamic shapes. Here the (T, B) rollout is
  reshaped into fixed contiguous chunks of ``per_rank_sequence_length``
  (``rollout_steps`` must be a multiple, same check as reference
  ppo_recurrent.py:226-228) and episode boundaries are enforced by masked
  in-scan LSTM state resets (``is_first`` = shifted dones), so every
  sequence is full-length, no padding/mask, and the whole
  epochs x minibatches BPTT update is ONE jitted ``lax.scan`` program;
- stored per-step ``prev_hx``/``prev_cx`` provide exact chunk-boundary
  initial states (the reference stores these per step too,
  ppo_recurrent.py:345-347);
- GAE runs on-device over the full (T, B) rollout before chunking.
"""

from __future__ import annotations

import copy
import os
import warnings
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.ppo import _set_lr, build_ppo_optimizer
from sheeprl_tpu.algos.ppo.utils import normalize_obs
from sheeprl_tpu.algos.ppo_recurrent.agent import RecurrentPPOPlayer, build_agent, evaluate_actions
from sheeprl_tpu.algos.ppo_recurrent.utils import prepare_obs, test
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.obs import setup_observability, trace_scope
from sheeprl_tpu.parallel.pipeline import OnPolicyCollector, PipelinedCollector, RolloutPayload, detach_copy, resolve_overlap_setting
from sheeprl_tpu.resilience import CheckpointManager
from sheeprl_tpu.resilience.sentinel import guard_update, restore_like
from sheeprl_tpu.utils.callback import load_checkpoint
from sheeprl_tpu.utils.env import make_train_envs, resolve_env_backend
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import (
    MetricFetchGate,
    device_get_metrics,
    gae,
    normalize_tensor,
    polynomial_decay,
    print_config,
    save_configs,
    start_async_host_copy,
)
from sheeprl_tpu.optim import restore_opt_states
from jax import shard_map


def make_update_fn(runtime, module, tx, cfg: Dict[str, Any], obs_keys: Sequence[str]):
    """Single jitted recurrent-PPO update: GAE -> chunk into sequences ->
    epochs x minibatches of truncated-BPTT clipped-surrogate steps."""
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    update_epochs = int(cfg.algo.update_epochs)
    num_batches = max(1, int(cfg.algo.per_rank_num_batches))
    sl = int(cfg.algo.per_rank_sequence_length)
    gamma = float(cfg.algo.gamma)
    gae_lambda = float(cfg.algo.gae_lambda)
    vf_coef = float(cfg.algo.vf_coef)
    clip_vloss = bool(cfg.algo.clip_vloss)
    reduction = str(cfg.algo.loss_reduction)
    normalize_adv = bool(cfg.algo.normalize_advantages)
    reset_on_done = bool(cfg.algo.reset_recurrent_state_on_done)

    world_size = int(runtime.world_size)

    def _core(params, opt_state, data, next_values, key, clip_coef, ent_coef, pmean_axis):
        # ------------------------------------------------- GAE on (T, B)
        returns, advantages = gae(
            data["rewards"], data["values"], data["dones"], next_values, gamma, gae_lambda
        )
        data = {**data, "returns": returns, "advantages": advantages}

        # is_first[t] = done[t-1]; chunk starts use stored prev_hx/prev_cx
        T, B = data["rewards"].shape[:2]
        if reset_on_done:
            is_first = jnp.concatenate(
                [jnp.zeros((1, B, 1), data["dones"].dtype), data["dones"][:-1]], axis=0
            )
        else:
            is_first = jnp.zeros((T, B, 1), data["dones"].dtype)
        data = {**data, "is_first": is_first}

        # ------------------------------------- chunk (T, B) -> (sl, n_seqs)
        n_chunks = T // sl
        n_seqs = n_chunks * B

        def to_seq(x):
            x = x.reshape(n_chunks, sl, B, *x.shape[2:])
            x = jnp.moveaxis(x, 0, 1)  # (sl, n_chunks, B, ...)
            return x.reshape(sl, n_seqs, *x.shape[3:])

        seq = {k: to_seq(v) for k, v in data.items() if k not in ("prev_hx", "prev_cx")}
        # per-sequence initial LSTM state = stored state at chunk start
        hx0 = data["prev_hx"].reshape(n_chunks, sl, B, -1)[:, 0].reshape(n_seqs, -1)
        cx0 = data["prev_cx"].reshape(n_chunks, sl, B, -1)[:, 0].reshape(n_seqs, -1)

        mb_size = max(1, n_seqs // num_batches)
        num_minibatches = max(1, -(-n_seqs // mb_size))
        n_used = num_minibatches * mb_size

        def loss_fn(p, mb, mb_hx, mb_cx):
            obs = {k: mb[k].astype(jnp.float32) for k in obs_keys}
            obs = normalize_obs(obs, cnn_keys, obs_keys)
            new_logprobs, entropy, new_values = evaluate_actions(
                module, p, obs, mb["prev_actions"], mb["is_first"].astype(jnp.float32),
                mb_hx, mb_cx, mb["actions"],
            )
            adv = mb["advantages"]
            if normalize_adv:
                adv = normalize_tensor(adv)
            pg = policy_loss(new_logprobs, mb["logprobs"], adv, clip_coef, reduction)
            vl = value_loss(new_values, mb["values"], mb["returns"], clip_coef, clip_vloss, reduction)
            ent = entropy_loss(entropy, reduction)
            total = pg + vf_coef * vl + ent_coef * ent
            return total, jnp.stack([pg, vl, ent])

        grad_fn = jax.grad(loss_fn, has_aux=True)

        def mb_step(carry, inp):
            params, opt_state = carry
            mb, mb_hx, mb_cx = inp
            grads, losses = grad_fn(params, mb, mb_hx, mb_cx)
            if pmean_axis is not None:
                # DDP gradient all-reduce across the rank-local sequences
                grads = jax.lax.pmean(grads, pmean_axis)
                losses = jax.lax.pmean(losses, pmean_axis)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), losses

        def epoch_step(carry, ekey):
            params, opt_state = carry
            perm = jax.random.permutation(ekey, n_seqs)
            if n_used > n_seqs:
                perm = jnp.concatenate([perm, perm[: n_used - n_seqs]])
            shuffled = jax.tree_util.tree_map(
                lambda x: x[:, perm]
                .reshape(sl, num_minibatches, mb_size, *x.shape[2:])
                .swapaxes(0, 1),
                seq,
            )
            sh_hx = hx0[perm].reshape(num_minibatches, mb_size, -1)
            sh_cx = cx0[perm].reshape(num_minibatches, mb_size, -1)
            (params, opt_state), losses = jax.lax.scan(
                mb_step, (params, opt_state), (shuffled, sh_hx, sh_cx)
            )
            return (params, opt_state), losses.mean(0)

        keys = jax.random.split(key, update_epochs)
        (params, opt_state), losses = jax.lax.scan(epoch_step, (params, opt_state), keys)
        mean_losses = losses.mean(0)
        metrics = {
            "Loss/policy_loss": mean_losses[0],
            "Loss/value_loss": mean_losses[1],
            "Loss/entropy_loss": mean_losses[2],
        }
        return params, opt_state, metrics

    def update(params, opt_state, data, next_values, key, clip_coef, ent_coef, lr):
        opt_state = _set_lr(opt_state, lr)
        if runtime.ddp_gate(data["rewards"].shape[1], "recurrent-PPO"):
            # rank-local DDP core under shard_map: the sequence-shuffle
            # gather cannot stay sharded under GSPMD (it would replicate
            # the whole BPTT update on every device — see ppo.py's
            # _update_shard_map); each rank chunks and shuffles its own
            # env columns' sequences (per_rank_num_batches is per-rank by
            # definition) with a pmean per minibatch step
            from jax.sharding import PartitionSpec as SMP

            from sheeprl_tpu.parallel.sharding import BATCH_AXES

            data_specs = jax.tree_util.tree_map(lambda _: SMP(None, BATCH_AXES), data)

            def body(params, opt_state, data, next_values, key, clip_coef, ent_coef):
                rank_key = jax.random.fold_in(key, runtime.layout.flat_rank())
                return _core(
                    params, opt_state, data, next_values, rank_key, clip_coef, ent_coef, BATCH_AXES
                )

            return shard_map(
                body,
                mesh=runtime.mesh,
                in_specs=(SMP(), SMP(), data_specs, SMP(BATCH_AXES), SMP(), SMP(), SMP()),
                out_specs=(SMP(), SMP(), SMP()),
                check_vma=False,
            )(params, opt_state, data, next_values, key, clip_coef, ent_coef)
        return _core(params, opt_state, data, next_values, key, clip_coef, ent_coef, None)

    # training health sentinel hook (resilience/sentinel.py)
    return guard_update(runtime, update, cfg, n_state=2, donate_argnums=(0, 1))


class RecurrentCollector(OnPolicyCollector):
    """Rollout stepper for the recurrent player: captures the pre-action
    LSTM state + previous actions per step, resets recurrent state on
    done, and values the final observation for the GAE bootstrap."""

    def collect(self, iter_num: int, inline: bool, key_fn) -> RolloutPayload:
        import time as _time

        cfg = self.cfg
        payload = RolloutPayload(iter_num)
        step_data = self._step_data
        next_obs_np = self.next_obs
        for _ in range(cfg.algo.rollout_steps):
            self.policy_step += cfg.env.num_envs * self.world_size

            # state BEFORE acting — what the policy is conditioned on
            prev_hx = np.asarray(self.player.hx)
            prev_cx = np.asarray(self.player.cx)
            prev_actions_np = np.asarray(self.player.prev_actions).reshape(self.total_envs, -1)

            cm = (
                timer("Time/env_interaction_time", SumMetric, sync_on_compute=False)
                if inline
                else None
            )
            t0 = None
            if cm is not None:
                cm.__enter__()
            else:
                t0 = _time.perf_counter()
            try:
                flat_actions, real_actions, logprobs, values = self.player.get_actions(
                    next_obs_np, key_fn()
                )
                start_async_host_copy(flat_actions, logprobs, values)
                real_actions_np = np.asarray(real_actions)
                obs, rewards, terminated, truncated, info = self.envs.step(
                    real_actions_np.reshape(self.envs.action_space.shape)
                )
                truncated_envs = np.nonzero(truncated)[0]
                if len(truncated_envs) > 0:
                    real_next_obs = {k: np.array(v) for k, v in obs.items()}
                    for env_idx in truncated_envs:
                        final = info["final_obs"][env_idx]
                        for k in self.obs_keys:
                            real_next_obs[k][env_idx] = final[k]
                    vals = np.asarray(self.player.get_values(real_next_obs)).reshape(
                        self.total_envs, -1
                    )
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
                    payload.env_seconds += _time.perf_counter() - t0

            for k in self.obs_keys:
                step_data[k] = next_obs_np[k][np.newaxis]
            step_data["dones"] = dones[np.newaxis]
            step_data["values"] = np.asarray(values).reshape(1, self.total_envs, -1)
            step_data["actions"] = np.asarray(flat_actions).reshape(1, self.total_envs, -1)
            step_data["logprobs"] = np.asarray(logprobs).reshape(1, self.total_envs, -1)
            step_data["rewards"] = rewards[np.newaxis]
            step_data["prev_hx"] = prev_hx[np.newaxis]
            step_data["prev_cx"] = prev_cx[np.newaxis]
            step_data["prev_actions"] = prev_actions_np[np.newaxis]
            self.rb.add(step_data, validate_args=cfg.buffer.validate_args)

            next_obs_np = obs
            if cfg.algo.reset_recurrent_state_on_done and dones.any():
                self.player.reset_states(dones)

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
        # host round-trip: the player may live on the CPU backend while the
        # update runs under the accelerator mesh
        payload.extras["next_values"] = np.asarray(self.player.get_values(next_obs_np)).reshape(
            self.total_envs, -1
        )
        payload.policy_step_end = self.policy_step
        return payload


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    if "minedojo" in str(cfg.env.wrapper.get("_target_", "")).lower():
        raise ValueError(
            "MineDojo is not currently supported by the Recurrent PPO agent "
            "(no action-mask handling); use one of the Dreamer agents."
        )
    if cfg.algo.rollout_steps % cfg.algo.per_rank_sequence_length != 0:
        raise ValueError(
            f"rollout_steps ({cfg.algo.rollout_steps}) must be a multiple of "
            f"per_rank_sequence_length ({cfg.algo.per_rank_sequence_length})"
        )

    initial_ent_coef = copy.deepcopy(cfg.algo.ent_coef)
    initial_clip_coef = copy.deepcopy(cfg.algo.clip_coef)

    world_size = runtime.world_size
    runtime.seed_everything(cfg.seed)

    state = None
    if cfg.checkpoint.resume_from:
        state = load_checkpoint(cfg.checkpoint.resume_from)

    logger = get_logger(runtime, cfg)
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name)
    runtime.print(f"Log dir: {log_dir}")
    observability = setup_observability(runtime, cfg, log_dir, logger=logger)
    if logger:
        logger.log_hyperparams(cfg)

    # ------------------------------------------------------------- envs
    import gymnasium as gym

    total_envs = cfg.env.num_envs * world_size
    # env backend dispatch (howto/jax-envs.md): host = the gymnasium
    # vector stack (bit-exact pre-backend behavior), jax = device-resident
    # envs + the fused recurrent collect path below
    env_backend = resolve_env_backend(cfg)
    envs = make_train_envs(cfg, runtime, log_dir)
    observation_space = envs.single_observation_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    cnn_keys = cfg.algo.cnn_keys.encoder
    mlp_keys = cfg.algo.mlp_keys.encoder
    obs_keys = cnn_keys + mlp_keys
    if obs_keys == []:
        raise RuntimeError("Specify at least one of `cnn_keys.encoder` or `mlp_keys.encoder`")
    if cfg.metric.log_level > 0:
        runtime.print("Encoder CNN keys:", cnn_keys)
        runtime.print("Encoder MLP keys:", mlp_keys)

    is_continuous = isinstance(envs.single_action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(envs.single_action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        envs.single_action_space.shape
        if is_continuous
        else (envs.single_action_space.nvec.tolist() if is_multidiscrete else [envs.single_action_space.n])
    )
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)

    # ------------------------------------------------------------- agent
    module, params = build_agent(
        runtime, actions_dim, is_continuous, cfg, observation_space, state["agent"] if state else None
    )
    params = runtime.replicate(runtime.to_param_dtype(params))
    tx = build_ppo_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm, runtime.precision)
    opt_state = (
        runtime.replicate(tx.init(params))
        if state is None
        else restore_opt_states(state["optimizer"], params, runtime.precision)
    )

    def _prep(obs):
        return prepare_obs(obs, cnn_keys=cnn_keys, num_envs=total_envs)

    player = RecurrentPPOPlayer(module, params, _prep, num_envs=total_envs, device=runtime.player_device(params))

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(dict(cfg.metric.aggregator))

    # ------------------------------------------------------------- buffer
    if cfg.buffer.size < cfg.algo.rollout_steps:
        raise ValueError(
            f"The size of the buffer ({cfg.buffer.size}) cannot be lower "
            f"than the rollout steps ({cfg.algo.rollout_steps})"
        )
    rb = ReplayBuffer(
        cfg.buffer.size,
        total_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{runtime.global_rank}"),
        obs_keys=obs_keys,
    )

    # ------------------------------------------------------------- counters
    last_train = 0
    train_step = 0
    start_iter = (state["iter_num"] // world_size) + 1 if state else 1
    policy_step = state["iter_num"] * cfg.env.num_envs * cfg.algo.rollout_steps if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    policy_steps_per_iter = int(cfg.env.num_envs * cfg.algo.rollout_steps * world_size)
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    if state:
        cfg.algo.per_rank_num_batches = state["num_batches"] // world_size

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_iter != 0:
        warnings.warn(
            f"metric.log_every ({cfg.metric.log_every}) is not a multiple of "
            f"policy_steps_per_iter ({policy_steps_per_iter}); metrics log at the next multiple."
        )

    ckpt_mgr = CheckpointManager(
        runtime, cfg, log_dir, observability=observability, last_checkpoint=last_checkpoint
    )
    update_fn = make_update_fn(runtime, module, tx, cfg, obs_keys)
    health = update_fn.health.bind(ckpt_mgr=ckpt_mgr, select=("agent", "optimizer"))
    if health.enabled:
        observability.health_stats = health.stats

    lr0 = float(cfg.algo.optimizer.get("learning_rate", cfg.algo.optimizer.get("lr", 1e-3)))
    current_lr = lr0
    current_clip = float(cfg.algo.clip_coef)
    current_ent = float(cfg.algo.ent_coef)

    # ------------------------------------------------------------- run
    # collect/train pipeline: overlap_collect=True steps iteration t+1's
    # envs on a background thread while iteration t trains (params
    # staleness <= 1); False keeps the serial pre-pipeline order bit-exact;
    # "auto" turns it on only where a spare host core exists for the
    # collector thread (single-core hosts stay serial)
    overlap = resolve_overlap_setting(cfg)  # always off on the jax backend
    if overlap:
        # the player's device_put is a no-op on a same-device tree, so its
        # initial weights alias the buffers update 1 donates — detach them
        # before the collector thread starts acting on them
        player.params = detach_copy(params)
    if env_backend == "jax":
        # fused recurrent collect (envs/jax/collect.py): the scan carry
        # threads (env state, hx, cx, prev_actions); one program per rollout
        from sheeprl_tpu.envs.jax.collect import FusedRecurrentCollector

        collector = FusedRecurrentCollector(
            envs=envs,
            module=module,
            params=params,
            cfg=cfg,
            runtime=runtime,
            obs_keys=obs_keys,
            total_envs=total_envs,
            world_size=world_size,
            aggregator=aggregator,
            policy_step=policy_step,
        )
        observability.jaxenv_stats = collector.stats
        adopt_params_fn = collector.adopt

        def _pack(payload):
            # already device arrays; only the mesh layout is (re)applied
            with trace_scope("host_to_device"):
                payload.data = runtime.shard_batch(dict(payload.data), axis=1)
                payload.extras["next_values"] = runtime.shard_batch(
                    payload.extras["next_values"], axis=0
                )

    else:
        collector = RecurrentCollector(
            envs=envs,
            player=player,
            rb=rb,
            cfg=cfg,
            runtime=runtime,
            obs_keys=obs_keys,
            total_envs=total_envs,
            world_size=world_size,
            aggregator=aggregator,
            clip_rewards_fn=clip_rewards_fn,
            policy_step=policy_step,
        )
        adopt_params_fn = lambda p: setattr(player, "params", p)
        player.init_states()

        def _pack(payload):
            # env-axis sharding: each mesh device receives only its columns; on
            # the overlapped path this runs on the collector thread, so the
            # host->device upload of rollout t+1 overlaps train step t
            local_data = {
                k: v.astype(jnp.float32) if v.dtype not in (jnp.uint8,) else np.array(v)
                for k, v in payload.data.items()
            }
            host_next_values = payload.extras["next_values"]
            # the upload sources must outlive the update that reads them —
            # CPU device_put zero-copy aliases aligned host buffers without
            # keeping them alive
            payload.host_refs.append((local_data, host_next_values))
            with trace_scope("host_to_device"):
                payload.data = runtime.shard_batch(local_data, axis=1)
                payload.extras["next_values"] = runtime.shard_batch(host_next_values, axis=0)

    pipeline = PipelinedCollector(
        runtime,
        collector.collect,
        _pack,
        start_iter=start_iter,
        total_iters=total_iters,
        overlap=overlap,
        seed=cfg.seed,
        adopt_params_fn=adopt_params_fn,
    )
    metric_fetch_gate = MetricFetchGate(cfg.metric.get("fetch_every", 1))

    for iter_num, payload in pipeline:
        observability.on_iteration(policy_step)
        payload.apply_events(aggregator, runtime, cfg.metric.log_level)
        policy_step = payload.policy_step_end

        with timer("Time/train_time", SumMetric, sync_on_compute=cfg.metric.sync_on_compute):
            params, opt_state, train_metrics = update_fn(
                params,
                opt_state,
                payload.data,
                payload.extras["next_values"],
                runtime.next_key(),
                jnp.float32(current_clip),
                jnp.float32(current_ent),
                jnp.float32(current_lr),
            )
        pipeline.publish(iter_num, params)
        train_step += world_size

        rolled = health.tick()
        if rolled is not None:
            params = restore_like(params, rolled["agent"])
            opt_state = restore_like(opt_state, rolled["optimizer"])

        if aggregator and not aggregator.disabled and metric_fetch_gate():
            with trace_scope("block_until_ready"):
                fetched_metrics = device_get_metrics(train_metrics)
            for k, v in fetched_metrics.items():
                aggregator.update(k, v)

        # ------------------------------------------------- logging
        if cfg.metric.log_level > 0 and logger:
            logger.log_metrics({"Info/learning_rate": current_lr}, policy_step)
            logger.log_metrics({"Info/clip_coef": current_clip, "Info/ent_coef": current_ent}, policy_step)
            if policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters:
                observability.on_log(policy_step, train_step)
                if aggregator and not aggregator.disabled:
                    logger.log_metrics(aggregator.compute(), policy_step)
                    aggregator.reset()
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

        # ------------------------------------------------- annealing
        if cfg.algo.anneal_lr:
            current_lr = polynomial_decay(iter_num, initial=lr0, final=0.0, max_decay_steps=total_iters, power=1.0)
        if cfg.algo.anneal_clip_coef:
            current_clip = polynomial_decay(
                iter_num, initial=initial_clip_coef, final=0.0, max_decay_steps=total_iters, power=1.0
            )
        if cfg.algo.anneal_ent_coef:
            current_ent = polynomial_decay(
                iter_num, initial=initial_ent_coef, final=0.0, max_decay_steps=total_iters, power=1.0
            )

        # ------------------------------------------------- checkpoint
        ckpt_mgr.maybe_checkpoint(
            policy_step=policy_step,
            is_last=iter_num == total_iters,
            state_fn=lambda: {
                "agent": params,
                "optimizer": opt_state,
                "iter_num": iter_num * world_size,
                "num_batches": cfg.algo.per_rank_num_batches * world_size,
                "last_log": last_log,
                "last_checkpoint": ckpt_mgr.last_checkpoint,
            },
        )
        if ckpt_mgr.preempted:
            runtime.print(f"Preemption signal: emergency checkpoint written, stopping at iter {iter_num}")
            break

    pipeline.close()  # before envs.close(): the collector may be mid-step
    player.params = params  # the test episode runs on the final weights
    ckpt_mgr.close()
    envs.close()
    observability.close()
    if runtime.is_global_zero and cfg.algo.run_test:
        test_rew = test(player, runtime, cfg, log_dir)
        if logger:
            logger.log_metrics({"Test/cumulative_reward": test_rew}, policy_step)
    if logger:
        logger.finalize()
