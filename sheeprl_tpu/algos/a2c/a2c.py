"""A2C — TPU-native main loop (reference sheeprl/algos/a2c/a2c.py:26,118).

Same rollout scaffold as PPO; the update differs: a single optimizer step
per iteration with gradients accumulated over minibatches (the reference's
``no_backward_sync`` + deferred ``optimizer.step``). In jax that's a
``lax.scan`` summing grads over minibatch chunks, then one ``tx.update`` —
the whole thing one jitted function."""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.a2c.loss import policy_loss, value_loss
from sheeprl_tpu.algos.ppo.agent import build_agent, evaluate_actions, get_values, PPOPlayer
from sheeprl_tpu.algos.ppo.ppo import _set_lr, build_ppo_optimizer
from sheeprl_tpu.algos.ppo.utils import normalize_obs, prepare_obs, test
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.obs import flight, setup_observability, trace_scope
from sheeprl_tpu.parallel.pipeline import OnPolicyCollector, PipelinedCollector, detach_copy, resolve_overlap_setting
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
    save_configs,
)
from sheeprl_tpu.optim import restore_opt_states
from jax import shard_map


def make_update_fn(runtime, module, tx, cfg: Dict[str, Any], obs_keys: Sequence[str]):
    mb_size = int(cfg.algo.per_rank_batch_size) * runtime.world_size
    gamma = float(cfg.algo.gamma)
    gae_lambda = float(cfg.algo.gae_lambda)
    vf_coef = float(cfg.algo.vf_coef)
    reduction = str(cfg.algo.loss_reduction)
    normalize_adv = bool(cfg.algo.get("normalize_advantages", False))
    ent_coef = float(cfg.algo.ent_coef)

    world_size = int(runtime.world_size)

    def _core(params, opt_state, data, next_obs, key, local_mb, pmean_axis):
        """GAE + shuffled minibatch gradient ACCUMULATION + one update.

        Runs either on the whole rollout (single device) or, under
        shard_map, on a rank's env columns with ``local_mb`` rows per
        minibatch and a ``pmean`` over ``pmean_axis`` before the single
        optimizer step — the accumulate-then-step structure means the
        rank-local decomposition is EXACTLY the global computation
        (sum over minibatches of per-minibatch means)."""
        next_values = get_values(
            module, params, normalize_obs({k: next_obs[k].astype(jnp.float32) for k in obs_keys}, (), obs_keys)
        )
        returns, advantages = gae(
            data["rewards"], data["values"], data["dones"], next_values, gamma, gae_lambda
        )
        data = {**data, "returns": returns, "advantages": advantages}
        n_total = data["rewards"].shape[0] * data["rewards"].shape[1]
        flat = {k: v.reshape(n_total, *v.shape[2:]) for k, v in data.items()}
        num_minibatches = max(1, -(-n_total // local_mb))
        n_used = num_minibatches * local_mb

        def loss_fn(p, mb):
            obs = normalize_obs({k: mb[k].astype(jnp.float32) for k in obs_keys}, (), obs_keys)
            logprobs, entropy, new_values = evaluate_actions(module, p, obs, mb["actions"])
            adv = normalize_tensor(mb["advantages"]) if normalize_adv else mb["advantages"]
            pg = policy_loss(logprobs, adv, reduction)
            vl = value_loss(new_values, mb["returns"], reduction)
            total = pg + vf_coef * vl - ent_coef * entropy.mean()
            return total, jnp.stack([pg, vl])

        grad_fn = jax.grad(loss_fn, has_aux=True)

        perm = jax.random.permutation(key, n_total)
        if n_used > n_total:  # pad by wrapping as many times as needed
            perm = jnp.tile(perm, -(-n_used // n_total))[:n_used]
        shuffled = jax.tree_util.tree_map(
            lambda x: x[perm].reshape(num_minibatches, local_mb, *x.shape[1:]), flat
        )

        def mb_step(acc, mb):
            grads, losses = grad_fn(params, mb)
            acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            return acc, losses

        zero_grads = jax.tree_util.tree_map(jnp.zeros_like, params)
        grads, losses = jax.lax.scan(mb_step, zero_grads, shuffled)
        if pmean_axis is not None:
            grads = jax.lax.pmean(grads, pmean_axis)
            losses = jax.lax.pmean(losses, pmean_axis)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        mean_losses = losses.mean(0)
        return params, opt_state, {
            "Loss/policy_loss": mean_losses[0],
            "Loss/value_loss": mean_losses[1],
            # accumulated-gradient global norm: telemetry + the training
            # sentinel's z-score monitor
            "Grads/agent": optax.global_norm(grads),
        }

    def update(params, opt_state, data, next_obs, key, lr):
        opt_state = _set_lr(opt_state, lr)
        if runtime.ddp_gate(data["rewards"].shape[1], "A2C"):
            # rank-local DDP core: the epoch-shuffle gather cannot stay
            # sharded under GSPMD (it would replicate the whole update on
            # every device — see ppo.py's _update_shard_map).  Specs and
            # the gradient pmean cover BOTH mesh axes (parallel/sharding):
            # every device is a batch shard regardless of the (d, f) split,
            # and the reduction lowers to explicit jax.lax collectives.
            from jax.sharding import PartitionSpec as SMP

            from sheeprl_tpu.parallel.sharding import BATCH_AXES

            data_specs = jax.tree_util.tree_map(lambda _: SMP(None, BATCH_AXES), data)
            obs_specs = jax.tree_util.tree_map(lambda _: SMP(BATCH_AXES), next_obs)

            def body(params, opt_state, data, next_obs, key):
                rank_key = jax.random.fold_in(key, runtime.layout.flat_rank())
                return _core(
                    params, opt_state, data, next_obs, rank_key,
                    mb_size // world_size, BATCH_AXES,
                )

            return shard_map(
                body,
                mesh=runtime.mesh,
                in_specs=(SMP(), SMP(), data_specs, obs_specs, SMP()),
                out_specs=(SMP(), SMP(), SMP()),
                check_vma=False,
            )(params, opt_state, data, next_obs, key)
        return _core(params, opt_state, data, next_obs, key, mb_size, None)

    # training health sentinel hook (resilience/sentinel.py)
    return guard_update(runtime, update, cfg, n_state=2, donate_argnums=(0, 1))


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    if len(cfg.algo.cnn_keys.encoder) > 0:
        raise ValueError("A2C supports only vector observations (mlp keys)")

    world_size = runtime.world_size
    runtime.seed_everything(cfg.seed)

    state = load_checkpoint(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None

    logger = get_logger(runtime, cfg)
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name)
    runtime.print(f"Log dir: {log_dir}")
    if logger:
        logger.log_hyperparams(cfg)

    import gymnasium as gym

    total_envs = cfg.env.num_envs * world_size
    # env backend dispatch (howto/jax-envs.md): host = the gymnasium
    # vector stack (bit-exact pre-backend behavior), jax = device-resident
    # envs + the fused collect path below
    env_backend = resolve_env_backend(cfg)
    envs = make_train_envs(cfg, runtime, log_dir)
    observation_space = envs.single_observation_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    obs_keys = list(cfg.algo.mlp_keys.encoder)

    is_continuous = isinstance(envs.single_action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(envs.single_action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        envs.single_action_space.shape
        if is_continuous
        else (envs.single_action_space.nvec.tolist() if is_multidiscrete else [envs.single_action_space.n])
    )

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
    player = PPOPlayer(
        module,
        params,
        lambda obs: prepare_obs(obs, num_envs=total_envs),
        device=runtime.player_device(params),
    )

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(dict(cfg.metric.aggregator))
    observability = setup_observability(runtime, cfg, log_dir, logger=logger)

    rb = ReplayBuffer(
        cfg.buffer.size,
        total_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{runtime.global_rank}"),
        obs_keys=obs_keys,
    )

    last_train = 0
    train_step = 0
    start_iter = (state["iter_num"] // world_size) + 1 if state else 1
    policy_step = state["iter_num"] * cfg.env.num_envs * cfg.algo.rollout_steps if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    policy_steps_per_iter = int(cfg.env.num_envs * cfg.algo.rollout_steps * world_size)
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    if state:
        cfg.algo.per_rank_batch_size = state["batch_size"] // world_size

    ckpt_mgr = CheckpointManager(
        runtime, cfg, log_dir, observability=observability, last_checkpoint=last_checkpoint
    )
    update_fn = make_update_fn(runtime, module, tx, cfg, obs_keys)
    health = update_fn.health.bind(ckpt_mgr=ckpt_mgr, select=("agent", "optimizer"))
    if health.enabled:
        observability.health_stats = health.stats
    lr0 = float(cfg.algo.optimizer.get("learning_rate", 1e-3))
    current_lr = lr0

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
        # fused collect (envs/jax/collect.py): policy + env + append as
        # one lax.scan per rollout; the payload is born on device
        from sheeprl_tpu.envs.jax.collect import FusedOnPolicyCollector

        collector = FusedOnPolicyCollector(
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
                payload.next_obs = runtime.shard_batch(dict(payload.next_obs), axis=0)

    else:
        collector = OnPolicyCollector(
            envs=envs,
            player=player,
            rb=rb,
            cfg=cfg,
            runtime=runtime,
            obs_keys=obs_keys,
            total_envs=total_envs,
            world_size=world_size,
            aggregator=aggregator,
            policy_step=policy_step,
        )
        adopt_params_fn = lambda p: setattr(player, "params", p)

        def _pack(payload):
            # env-axis sharding: each mesh device receives only its columns; on
            # the overlapped path this runs on the collector thread, so the
            # host->device upload of rollout t+1 overlaps train step t
            local_data = {k: v.astype(jnp.float32) for k, v in payload.data.items()}
            # np.array (copy), not asarray: SyncVectorEnv mutates its obs
            # buffer in place and CPU device_put zero-copy aliases host memory
            host_next_obs = {k: np.array(payload.next_obs[k]) for k in obs_keys}
            # the upload sources must outlive the update that reads them —
            # device_put's zero-copy alias does not keep them alive itself
            payload.host_refs.append((local_data, host_next_obs))
            with trace_scope("host_to_device"):
                payload.data = runtime.shard_batch(local_data, axis=1)
                payload.next_obs = runtime.shard_batch(host_next_obs, axis=0)

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

        with timer("Time/train_time", SumMetric, sync_on_compute=cfg.metric.sync_on_compute), flight.span(
            "train_step", round=iter_num
        ):
            params, opt_state, train_metrics = update_fn(
                params, opt_state, payload.data, payload.next_obs, runtime.next_key(), jnp.float32(current_lr)
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

        if cfg.metric.log_level > 0 and logger:
            logger.log_metrics({"Info/learning_rate": current_lr}, policy_step)
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

        if cfg.algo.anneal_lr:
            current_lr = polynomial_decay(iter_num, initial=lr0, final=0.0, max_decay_steps=total_iters, power=1.0)

        def _ckpt_state():
            state = {
                "agent": params,
                "optimizer": opt_state,
                "iter_num": iter_num * world_size,
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": ckpt_mgr.last_checkpoint,
            }
            # opt-in on-policy buffer persistence (buffer.checkpoint_on_policy):
            # the rollout is cheap to regenerate, but the resilience benchmark
            # needs a replay-buffer-bearing state on this loop
            if cfg.buffer.get("checkpoint_on_policy", False):
                state["rb"] = rb
            return state

        ckpt_mgr.maybe_checkpoint(
            policy_step=policy_step, is_last=iter_num == total_iters, state_fn=_ckpt_state
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
