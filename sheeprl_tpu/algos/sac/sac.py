"""SAC (coupled) — TPU-native main loop (reference sheeprl/algos/sac/sac.py
train:32, main:82).

TPU-first decisions:
- all G gradient steps of an iteration run as ONE jitted ``lax.scan`` over a
  (G, B, ...) batch sampled host-side in a single call (the reference also
  samples once per iteration to cut communications, sac.py:306);
- critic ensemble is vmapped (see agent.py), EMA targets via
  ``optax.incremental_update`` gated by ``lax.cond`` on the
  target_network_frequency schedule;
- log_alpha's gradient over the data-sharded batch is implicitly
  all-reduced by XLA (the reference all_reduces it by hand, sac.py:72);
- the replay ratio scheduler (``Ratio``) stays host-side — the number of
  gradient steps G is data shape, so distinct G values each compile once.
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.sac.agent import (
    SACPlayer,
    actor_action_and_log_prob,
    build_agent,
    critic_ensemble_apply,
)
from sheeprl_tpu.algos.sac.loss import (
    critic_loss,
    critic_loss_weighted,
    entropy_loss,
    policy_loss,
    td_error_abs,
)
from sheeprl_tpu.algos.sac.utils import prepare_obs, test
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.device_buffer import maybe_create_for_transitions
from sheeprl_tpu.obs import setup_observability, trace_scope
from sheeprl_tpu.replay import per_beta_schedule, rate_limiter_from_cfg
from sheeprl_tpu.resilience import CheckpointManager
from sheeprl_tpu.resilience.sentinel import guard_update, restore_like
from sheeprl_tpu.utils.callback import load_checkpoint, restore_buffer
from sheeprl_tpu.utils.env import make_train_envs, resolve_env_backend
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import MetricFetchGate, device_get_metrics, Ratio, save_configs
from sheeprl_tpu.optim import restore_opt_states


def _make_optimizer(optim_cfg: Dict[str, Any], precision: str = "32-true") -> optax.GradientTransformation:
    from sheeprl_tpu.optim import build_optimizer

    return build_optimizer(optim_cfg, precision=precision)


def make_train_fn(
    runtime, actor, critic, txs, cfg: Dict[str, Any], target_entropy: float, prioritized: bool = False
):
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    num_critics = int(cfg.algo.critic.n)
    actor_tx, critic_tx, alpha_tx = txs

    def _core(params, opt_states, data, key, do_ema, dp_axes):
        """params: {actor, critic, target_critic, log_alpha};
        data: (G, B, ...) pytree; one scan step per gradient step;
        do_ema: (G,) bool — per-step target soft-update flags (the reference
        EMAs once per env iteration, so the flags carry each gradient
        step's originating-iteration schedule through the scan).
        ``prioritized`` additionally consumes ``data["is_weights"]`` and
        returns the per-step |TD| for the priority updates — the False
        path traces exactly the pre-PER computation.

        ``dp_axes`` (the shard_map DDP core): each device runs this on its
        own batch rows with an explicit gradient ``pmean`` after every
        component's grad — per-shard means of equal-sized shards compose
        to the exact global-batch mean, so the decomposition is the
        single-device computation, now lowered to ``jax.lax`` collectives
        instead of whatever GSPMD propagation resolves."""

        def one_step(carry, inp):
            params, opt_states = carry
            batch, k, do_ema_step = inp
            if dp_axes is not None:
                # per-shard noise stream: identical keys would sample the
                # SAME action noise pattern on every batch shard
                k = jax.random.fold_in(k, runtime.layout.flat_rank())
            k1, k2 = jax.random.split(k)
            alpha = jnp.exp(params["log_alpha"])

            # ---------------- critic update (Eq. 5)
            next_actions, next_logp = actor_action_and_log_prob(
                actor, params["actor"], batch["next_observations"], k1
            )
            qf_next = critic_ensemble_apply(
                critic, params["target_critic"], batch["next_observations"], next_actions
            )
            min_qf_next = qf_next.min(-1, keepdims=True) - alpha * next_logp
            next_qf_value = batch["rewards"] + (1 - batch["terminated"]) * gamma * min_qf_next
            next_qf_value = jax.lax.stop_gradient(next_qf_value)

            if prioritized:

                def qf_loss_fn_w(cp):
                    qf_values = critic_ensemble_apply(critic, cp, batch["observations"], batch["actions"])
                    loss = critic_loss_weighted(
                        qf_values, next_qf_value, num_critics, batch["is_weights"]
                    )
                    return loss, td_error_abs(qf_values, next_qf_value)

                (qf_loss, td_abs), qf_grads = jax.value_and_grad(qf_loss_fn_w, has_aux=True)(
                    params["critic"]
                )
            else:

                def qf_loss_fn(cp):
                    qf_values = critic_ensemble_apply(critic, cp, batch["observations"], batch["actions"])
                    return critic_loss(qf_values, next_qf_value, num_critics)

                qf_loss, qf_grads = jax.value_and_grad(qf_loss_fn)(params["critic"])
                td_abs = None
            if dp_axes is not None:
                # explicit DDP gradient all-reduce (NCCL-equivalent psum)
                qf_grads = jax.lax.pmean(qf_grads, dp_axes)
                qf_loss = jax.lax.pmean(qf_loss, dp_axes)
            updates, new_critic_opt = critic_tx.update(qf_grads, opt_states["critic"], params["critic"])
            new_critic = optax.apply_updates(params["critic"], updates)

            # ---------------- EMA target (reference qfs_target_ema)
            new_target = jax.lax.cond(
                do_ema_step,
                lambda: optax.incremental_update(new_critic, params["target_critic"], tau),
                lambda: params["target_critic"],
            )

            # ---------------- actor update (Eq. 7)
            def actor_loss_fn(ap):
                actions, logp = actor_action_and_log_prob(actor, ap, batch["observations"], k2)
                q = critic_ensemble_apply(critic, new_critic, batch["observations"], actions)
                return policy_loss(alpha, logp, q.min(-1, keepdims=True)), logp

            (actor_loss, logp), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
                params["actor"]
            )
            if dp_axes is not None:
                actor_grads = jax.lax.pmean(actor_grads, dp_axes)
                actor_loss = jax.lax.pmean(actor_loss, dp_axes)
            updates, new_actor_opt = actor_tx.update(actor_grads, opt_states["actor"], params["actor"])
            new_actor = optax.apply_updates(params["actor"], updates)

            # ---------------- alpha update (Eq. 17); grad is a global-batch
            # mean -> XLA psums it across the data axis
            def alpha_loss_fn(la):
                return entropy_loss(la, logp, target_entropy)

            alpha_loss, alpha_grad = jax.value_and_grad(alpha_loss_fn)(params["log_alpha"])
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
            new_opt_states = {"actor": new_actor_opt, "critic": new_critic_opt, "alpha": new_alpha_opt}
            # pre-clip global grad norm (all components): telemetry + the
            # training sentinel's z-score monitor
            grad_norm = optax.global_norm((qf_grads, actor_grads, alpha_grad))
            losses = jnp.stack([qf_loss, actor_loss, alpha_loss, grad_norm])
            ys = (losses, td_abs) if prioritized else losses
            return (new_params, new_opt_states), ys

        g = data["rewards"].shape[0]
        keys = jax.random.split(key, g)
        (params, opt_states), ys = jax.lax.scan(
            one_step, (params, opt_states), (data, keys, do_ema)
        )
        losses, td_abs = ys if prioritized else (ys, None)
        mean_losses = losses.mean(0)
        metrics = {
            "Loss/value_loss": mean_losses[0],
            "Loss/policy_loss": mean_losses[1],
            "Loss/alpha_loss": mean_losses[2],
            "Grads/agent": mean_losses[3],
        }
        if prioritized:
            # (G, B) |TD| rides back for update_priorities — stays on device
            return params, opt_states, metrics, td_abs
        return params, opt_states, metrics

    def train(params, opt_states, data, key, do_ema):
        if runtime.ddp_gate(data["rewards"].shape[1], "SAC"):
            # explicit DDP core (shard_map over the flattened batch axes):
            # each device scans its own batch rows and the per-component
            # grad pmeans ARE the gradient all-reduce — the collectives
            # appear verbatim in the lowered program instead of hinging on
            # GSPMD propagation of the sampled batch's layout
            from jax.sharding import PartitionSpec as SMP

            from sheeprl_tpu.parallel.sharding import BATCH_AXES
            from jax import shard_map

            data_specs = jax.tree_util.tree_map(lambda _: SMP(None, BATCH_AXES), data)
            td_spec = (SMP(None, BATCH_AXES),) if prioritized else ()

            def body(params, opt_states, data, key, do_ema):
                return _core(params, opt_states, data, key, do_ema, BATCH_AXES)

            return shard_map(
                body,
                mesh=runtime.mesh,
                in_specs=(SMP(), SMP(), data_specs, SMP(), SMP()),
                out_specs=(SMP(), SMP(), SMP()) + td_spec,
                check_vma=False,
            )(params, opt_states, data, key, do_ema)
        return _core(params, opt_states, data, key, do_ema, None)

    # training health sentinel hook (resilience/sentinel.py)
    return guard_update(runtime, train, cfg, n_state=2, donate_argnums=(0, 1))


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    import gymnasium as gym

    if "minedojo" in str(cfg.env.wrapper.get("_target_", "")).lower():
        raise ValueError("MineDojo is not supported by the SAC agent")

    world_size = runtime.world_size
    runtime.seed_everything(cfg.seed)

    state = load_checkpoint(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None

    if len(cfg.algo.cnn_keys.encoder) > 0:
        warnings.warn("SAC cannot use image observations, the CNN keys will be ignored")
        cfg.algo.cnn_keys.encoder = []

    logger = get_logger(runtime, cfg)
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name)
    runtime.print(f"Log dir: {log_dir}")
    observability = setup_observability(runtime, cfg, log_dir, logger=logger)
    if logger:
        logger.log_hyperparams(cfg)

    total_envs = cfg.env.num_envs * world_size
    # env backend dispatch (howto/jax-envs.md): SAC's off-policy loop is
    # step-at-a-time, so env_backend=jax rides the JaxVectorEnv adapter
    # (all envs stepped by ONE jitted program per iteration) rather than a
    # fused rollout scan — the loop body runs unchanged either way
    resolve_env_backend(cfg)
    envs = make_train_envs(cfg, runtime, log_dir)
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

    actor, critic, params, target_entropy = build_agent(
        runtime, cfg, observation_space, action_space, state["agent"] if state else None
    )
    # bf16-true: bf16 param storage; EMA target + log_alpha keep f32 (small
    # per-step updates drown in bf16 rounding); optimizers hold f32 masters
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
        opt_states = {
            "actor": actor_tx.init(params["actor"]),
            "critic": critic_tx.init(params["critic"]),
            "alpha": alpha_tx.init(params["log_alpha"]),
        }
        opt_states = runtime.replicate(opt_states)

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

    buffer_size = cfg.buffer.size // int(total_envs) if not cfg.dry_run else 1
    rb = ReplayBuffer(
        max(buffer_size, 1),
        total_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{runtime.global_rank}"),
        obs_keys=("observations",),
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
    # prioritized replay (replay/priority_tree.py): lives with the device
    # cache; False (default) keeps the uniform samplers bit-exact
    prioritized = device_cache is not None and device_cache.prioritized
    beta_fn = per_beta_schedule(
        cfg.buffer.get("per_beta", 0.4),
        cfg.buffer.get("per_beta_end", 1.0),
        int(cfg.algo.total_steps),
    )
    # samples-per-insert rate control (replay/rate_limiter.py): in the
    # coupled loop the limiter clips the ratio-granted gradient steps when
    # sampling runs ahead of collection (inserts can't be blocked — the
    # loop IS the collector), and its stats ride telemetry
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
    # training health: anomalous gradient dispatches are skipped inside
    # the jitted scan; a tripped skip budget rolls agent+optimizer back to
    # the last good checkpoint and re-seeds the update key stream
    health = train_fn.health.bind(ckpt_mgr=ckpt_mgr, select=("agent", "opt_states"))
    if health.enabled:
        observability.health_stats = health.stats
    ema_every = cfg.algo.critic.target_network_frequency // policy_steps_per_iter + 1

    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed)[0]

    # dispatch batching: accumulate the ratio-granted gradient steps of
    # several env iterations into ONE jitted scan dispatch. Default 1 keeps
    # the reference's per-step cadence; >1 amortizes per-dispatch latency
    # (the same trade the reference's decoupled SAC makes by training on a
    # stale player) — essential when the accelerator sits behind a
    # high-latency link.
    dispatch_batch = max(1, int(cfg.algo.get("dispatch_batch", 1)))
    pending_iters = list(state.get("pending_iters", [])) if state else []
    # cache appends batch on the same cadence as the gradient dispatches:
    # rows accumulate host-side and land as ONE windowed append right
    # before the cache is sampled (per-step appends cost a jit dispatch +
    # H2D each, which re-introduces the per-step link latency that
    # dispatch_batch exists to amortize)
    pending_cache_rows = []

    def flush_cache_rows():
        if pending_cache_rows:
            window = {
                k: np.concatenate([r[k] for r in pending_cache_rows], axis=0)
                for k in pending_cache_rows[0]
            }
            device_cache.add(window)
            pending_cache_rows.clear()

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

        # real next obs (substitute final obs for autoreset rows)
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
            if dispatch_batch > 1:
                pending_cache_rows.append(dict(step_data))
                if len(pending_cache_rows) >= dispatch_batch:
                    flush_cache_rows()
            else:
                device_cache.add(step_data)
        obs = next_obs

        if iter_num >= learning_starts:
            # benchmark protocol pins 1 gradient step/iter (reference sac.py:299-304)
            per_rank_gradient_steps = (
                ratio((policy_step - prefill_steps + policy_steps_per_iter) / world_size)
                if not cfg.get("run_benchmarks", False)
                else 1
            )
            if per_rank_gradient_steps > 0:
                # remember which iteration granted each pending step so the
                # dispatch reproduces the reference's per-iteration EMA
                # cadence and step accounting exactly
                pending_iters.extend([iter_num] * per_rank_gradient_steps)
            batch_unit = cfg.algo.per_rank_batch_size * world_size
            dispatch_ready = bool(pending_iters) and (
                len(pending_iters) >= dispatch_batch or iter_num == total_iters
            )
            g_take = len(pending_iters)
            if limiter is not None and dispatch_ready:
                # sample-side throttle: dispatch only the gradient steps the
                # SPI budget allows; the rest stay pending until collection
                # catches up (recorded as a sampler stall for telemetry)
                g_take = min(g_take, limiter.sample_allowance(g_take * batch_unit) // batch_unit)
                if g_take == 0:
                    limiter.sample_stalls += 1
                    dispatch_ready = False
            if dispatch_ready:
                g = g_take
                ema_flags = np.asarray(
                    [it % ema_every == 0 for it in pending_iters[:g]], dtype=bool
                )
                iters_in_window = len(set(pending_iters[:g]))
                pending_iters = pending_iters[g:]
                batch_total = g * batch_unit
                if device_cache is not None:
                    flush_cache_rows()  # sampled content must match the host rb
                sample_idx = None
                if device_cache is not None and device_cache.can_sample_transitions(
                    cfg.buffer.sample_next_obs
                ):
                    # on-device gather + cast; nothing crosses the link
                    if prioritized:
                        sampled, sample_idx = device_cache.sample_transitions_per(
                            g,
                            batch_unit,
                            runtime.next_key(),
                            beta_fn(policy_step),
                            sample_next_obs=cfg.buffer.sample_next_obs,
                            obs_keys=("observations",),
                        )
                        data = {k: v.astype(jnp.float32) for k, v in sampled.items()}
                    else:
                        data = {
                            k: v.astype(jnp.float32)
                            for k, v in device_cache.sample_transitions(
                                g,
                                batch_unit,
                                runtime.next_key(),
                                sample_next_obs=cfg.buffer.sample_next_obs,
                                obs_keys=("observations",),
                            ).items()
                        }
                else:
                    sample = rb.sample(
                        batch_size=batch_total,
                        sample_next_obs=cfg.buffer.sample_next_obs,
                    )
                    # reshape host-side: eager jnp ops in the hot loop pay a
                    # dispatch each; jit transfers the numpy batch in one copy
                    data = {
                        k: np.asarray(v, dtype=np.float32).reshape(
                            g, batch_unit, *v.shape[2:]
                        )
                        for k, v in sample.items()
                    }
                    if prioritized:
                        # the cache bailed at runtime (budget / key-set
                        # change): train unweighted on the uniform host
                        # sample, no priorities to update
                        data["is_weights"] = np.ones((g, batch_unit, 1), np.float32)
                    # shard the batch axis over the mesh so each device
                    # trains on its own rows (GSPMD inserts the grad psums)
                    data = runtime.shard_batch(data, axis=1)
                if limiter is not None:
                    limiter.sample(batch_total)
                with timer("Time/train_time", SumMetric, sync_on_compute=cfg.metric.sync_on_compute):
                    if prioritized:
                        params, opt_states, train_metrics, td_abs = train_fn(
                            params,
                            opt_states,
                            data,
                            runtime.next_key(),
                            jnp.asarray(ema_flags),
                        )
                    else:
                        params, opt_states, train_metrics = train_fn(
                            params,
                            opt_states,
                            data,
                            runtime.next_key(),
                            jnp.asarray(ema_flags),
                        )
                if sample_idx is not None:
                    # priority feedback: |TD| of every gradient step lands
                    # back in the tree — one device dispatch, no host sync
                    device_cache.update_priorities(sample_idx, td_abs)
                rolled = health.tick()
                if rolled is not None:
                    params = restore_like(params, rolled["agent"])
                    opt_states = restore_like(opt_states, rolled["opt_states"])
                player.params = params["actor"]
                cumulative_per_rank_gradient_steps += g
                train_step += world_size * iters_in_window
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
                # undispatched ratio-granted gradient steps (dispatch_batch>1)
                "pending_iters": list(pending_iters),
                "iter_num": iter_num * world_size,
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": ckpt_mgr.last_checkpoint,
            }
            if cfg.buffer.checkpoint:
                ckpt_state["rb"] = rb
            if device_cache is not None and device_cache.prioritized:
                # tree state is NOT derivable from the host buffer — it
                # rides the snapshot so a resume keeps its priorities
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
