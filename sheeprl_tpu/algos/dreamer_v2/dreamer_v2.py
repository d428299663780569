"""DreamerV2 — TPU-native main loop (reference
sheeprl/algos/dreamer_v2/dreamer_v2.py train:41, main:390).

TPU-first design (same skeleton as DV3):
- ONE jitted gradient step: ``lax.scan`` dynamic learning (the reference's
  python time loop, dreamer_v2.py:148-162), KL-balanced world-model update,
  ``lax.scan`` imagination, mixed reinforce/dynamics-backprop actor update
  (objective_mix), Normal critic regression on TD(lambda) targets;
- hard target-critic refresh every ``per_rank_target_network_update_freq``
  gradient steps driven by the host counter (reference
  dreamer_v2.py:306-313);
- ``sequential`` (EnvIndependent over SequentialReplayBuffer) or
  ``episode`` (EpisodeBuffer with prioritize_ends) replay, selected by
  ``buffer.type`` (reference dreamer_v2.py:496-518).

Buffer-row semantics differ from V3: row t holds (o_t, a_{t-1->t}, r_t),
i.e. the action/reward that LED TO o_t, so the train step consumes actions
unshifted (reference diagram dreamer_v2.py:108-116)."""

from __future__ import annotations

import os
from functools import partial
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.dreamer_v2.agent import RSSM, PlayerDV2, build_agent
from sheeprl_tpu.ops.dyn_bptt import dyn_bptt_setting, dyn_rssm_sequence, extract_dyn_params_v2
from sheeprl_tpu.algos.dreamer_v2.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v2.utils import compute_lambda_values, prepare_obs, test
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.config.compose import _locate
from sheeprl_tpu.data.device_buffer import maybe_create_for, sequence_batches
from sheeprl_tpu.data.buffers import (
    EnvIndependentReplayBuffer,
    EpisodeBuffer,
    SequentialReplayBuffer,
)
from sheeprl_tpu.obs import setup_observability, trace_scope
from sheeprl_tpu.resilience import CheckpointManager
from sheeprl_tpu.resilience.sentinel import guard_update, restore_like
from sheeprl_tpu.utils.callback import load_checkpoint, restore_buffer
from sheeprl_tpu.utils.distribution import (
    Bernoulli,
    Independent,
    Normal,
    OneHotCategorical,
)
from sheeprl_tpu.utils.env import make_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import fetch_actions, MetricFetchGate, device_get_metrics, Ratio, save_configs, scan_remat, scan_unroll_setting
from sheeprl_tpu.optim import restore_opt_states

sg = jax.lax.stop_gradient


def _make_optimizer(optim_cfg, clip_gradients, precision="32-true"):
    from sheeprl_tpu.optim import build_optimizer

    return build_optimizer(optim_cfg, clip_gradients, precision)


def make_train_fn(runtime, world_model, actor, critic, txs, cfg, is_continuous, actions_dim):
    """Build the single jitted DV2 gradient step."""
    wm_tx, actor_tx, critic_tx = txs
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    cnn_keys_dec = tuple(cfg.algo.cnn_keys.decoder)
    mlp_keys_dec = tuple(cfg.algo.mlp_keys.decoder)
    stochastic_size = int(cfg.algo.world_model.stochastic_size)
    discrete_size = int(cfg.algo.world_model.discrete_size)
    stoch_state_size = stochastic_size * discrete_size
    recurrent_state_size = int(cfg.algo.world_model.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    ent_coef = float(cfg.algo.actor.ent_coef)
    objective_mix = float(cfg.algo.actor.objective_mix)
    kl_balancing_alpha = float(cfg.algo.world_model.kl_balancing_alpha)
    kl_free_nats = float(cfg.algo.world_model.kl_free_nats)
    kl_free_avg = bool(cfg.algo.world_model.kl_free_avg)
    kl_regularizer = float(cfg.algo.world_model.kl_regularizer)
    discount_scale_factor = float(cfg.algo.world_model.discount_scale_factor)
    use_continues = bool(cfg.algo.world_model.use_continues)
    # scan tuning inherited from the measured DV3 work (same structure,
    # same latency-bound bodies — see dreamer_v3.make_train_fn)
    scan_unroll = scan_unroll_setting(cfg, "dyn")
    img_unroll = scan_unroll_setting(cfg, "img")
    _remat = scan_remat

    rssm = world_model.rssm
    # efficient-BPTT dynamic scan (see dreamer_v3 / ops/dyn_bptt.py); the
    # DV2 variant: elu, Dense biases, optional LNs, no unimix, zero resets
    dyn_bptt = dyn_bptt_setting(cfg) and rssm.act in ("silu", "elu")

    def train(params, opt_states, data, key):
        T, B = data["rewards"].shape[:2]
        k_dyn, k_img = jax.random.split(key)

        batch_obs = {k: data[k] / 255.0 - 0.5 for k in cnn_keys}
        batch_obs.update({k: data[k] for k in mlp_keys})
        # the first element of a sampled sequence is treated as an episode
        # start (reference dreamer_v2.py:128)
        is_first = data["is_first"].at[0].set(1.0)
        # the rollout's sampling RNG, hoisted out of the scan body into one
        # batched gumbel draw (the scan bodies are latency-bound)
        dyn_noise_q = jax.random.gumbel(
            k_dyn, (T, B, stochastic_size, discrete_size), jnp.float32
        )

        # ---------------------------------------------------- world model
        def wm_loss_fn(wm_params):
            embedded_obs = world_model.encoder.apply(wm_params["encoder"], batch_obs)
            # embed half of the representation model's first matmul, batched
            # over the whole sequence (see RSSM.representation_embed_proj) —
            # keeps the (embed_dim, units) kernel-grad accumulator out of
            # the backward while-loop
            emb_proj = rssm.apply(
                wm_params["rssm"], embedded_obs, method=RSSM.representation_embed_proj
            )

            if dyn_bptt:
                recurrent_states, zst_, posteriors_logits = dyn_rssm_sequence(
                    jnp.zeros((B, stochastic_size * discrete_size)),
                    jnp.zeros((B, recurrent_state_size)),
                    data["actions"],
                    emb_proj,
                    is_first,
                    dyn_noise_q,
                    jnp.zeros((B, recurrent_state_size)),  # V2: zero resets
                    jnp.zeros((B, stochastic_size * discrete_size)),
                    extract_dyn_params_v2(wm_params["rssm"], recurrent_state_size),
                    eps_proj=1e-6,  # DenseActLn uses flax LayerNorm defaults
                    eps_rep=1e-6,
                    unimix=0.0,
                    discrete=discrete_size,
                    matmul_dtype=rssm.dtype,
                    unroll=scan_unroll,
                    act=rssm.act,
                    proj_ln=rssm.recurrent_layer_norm,
                    rep_ln=rssm.layer_norm,
                )
                posteriors = zst_.reshape(T, B, stochastic_size, discrete_size)
            else:
                def dyn_step(carry, inp):
                    posterior, recurrent_state = carry
                    action, emb, first, nq_t = inp
                    recurrent_state, posterior, posterior_logits = rssm.apply(
                        wm_params["rssm"], posterior, recurrent_state, action, emb, first,
                        None, noise=nq_t, method=RSSM.dynamic_posterior_from_proj,
                    )
                    return (posterior, recurrent_state), (
                        recurrent_state, posterior, posterior_logits,
                    )

                init = (
                    jnp.zeros((B, stochastic_size, discrete_size)),
                    jnp.zeros((B, recurrent_state_size)),
                )
                _, (recurrent_states, posteriors, posteriors_logits) = jax.lax.scan(
                    _remat(dyn_step), init,
                    (data["actions"], emb_proj, is_first, dyn_noise_q),
                    unroll=scan_unroll,
                )
            # prior logits for the KL, batched over the stacked recurrent
            # states (the prior SAMPLE is unused by the world-model loss)
            priors_logits, _ = rssm.apply(
                wm_params["rssm"], recurrent_states, None, sample_state=False,
                method=RSSM._transition,
            )
            latent_states = jnp.concatenate(
                [posteriors.reshape(T, B, -1), recurrent_states], -1
            )
            reconstructed_obs = world_model.observation_model.apply(
                wm_params["observation_model"], latent_states
            )
            po = {
                k: Independent(Normal(v, jnp.ones_like(v)), len(v.shape[2:]))
                for k, v in reconstructed_obs.items()
                if k in cnn_keys_dec + mlp_keys_dec
            }
            pr = Independent(
                Normal(world_model.reward_model.apply(wm_params["reward_model"], latent_states), 1.0), 1
            )
            if use_continues:
                pc = Independent(
                    Bernoulli(
                        logits=world_model.continue_model.apply(
                            wm_params["continue_model"], latent_states
                        )
                    ),
                    1,
                )
                continues_targets = (1 - data["terminated"]) * gamma
            else:
                pc = continues_targets = None
            pl = priors_logits.reshape(T, B, stochastic_size, discrete_size)
            psl = posteriors_logits.reshape(T, B, stochastic_size, discrete_size)
            rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
                po,
                batch_obs,
                pr,
                data["rewards"],
                pl,
                psl,
                kl_balancing_alpha,
                kl_free_nats,
                kl_free_avg,
                kl_regularizer,
                pc,
                continues_targets,
                discount_scale_factor,
            )
            aux = {
                "posteriors": posteriors,
                "recurrent_states": recurrent_states,
                "posteriors_logits": psl,
                "priors_logits": pl,
                "kl": kl.mean(),
                "state_loss": state_loss,
                "reward_loss": reward_loss,
                "observation_loss": observation_loss,
                "continue_loss": continue_loss,
            }
            return rec_loss, aux

        (rec_loss, wm_aux), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(
            params["world_model"]
        )
        updates, new_wm_opt = wm_tx.update(wm_grads, opt_states["world_model"], params["world_model"])
        new_wm_params = optax.apply_updates(params["world_model"], updates)

        # ---------------------------------------------------- imagination
        # B-MAJOR flatten (T,B,..)->(B,T,..)->(B*T,..): keeps the mesh's
        # batch sharding through the merge (a T-major flatten interleaves
        # the shards and GSPMD replicates the imagination phase on every
        # device); downstream ops reduce over the merged axis, so the
        # order change is semantics-free
        imagined_prior0 = sg(wm_aux["posteriors"]).swapaxes(0, 1).reshape(T * B, stoch_state_size)
        recurrent_state0 = sg(wm_aux["recurrent_states"]).swapaxes(0, 1).reshape(T * B, recurrent_state_size)
        true_continue = (1 - data["terminated"]).swapaxes(0, 1).reshape(T * B, 1) * gamma

        # imagination RNG hoisted out of the scan body (see the dynamic
        # scan); actor keys pre-split outside
        k_img_n, k_img_a = jax.random.split(k_img)
        img_noise = jax.random.gumbel(
            k_img_n, (horizon, T * B, stochastic_size, discrete_size), jnp.float32
        )
        act_keys = jax.random.split(k_img_a, horizon + 1)

        def actor_loss_fn(actor_params):
            latent0 = jnp.concatenate([imagined_prior0, recurrent_state0], -1)

            def img_step(carry, inp):
                prior, rec, latent = carry
                k_act, n_t = inp
                acts, _ = actor.apply(actor_params, sg(latent), False, k_act)
                action = jnp.concatenate(acts, -1)
                prior, rec = rssm.apply(
                    new_wm_params["rssm"], prior, rec, action, None, noise=n_t,
                    method=RSSM.imagination,
                )
                prior = prior.reshape(-1, stoch_state_size)
                latent = jnp.concatenate([prior, rec], -1)
                return (prior, rec, latent), (latent, action)

            # remat: without it the while loop carries every step's
            # residuals for the backward pass (see dreamer_v3)
            _, (latents, actions_seq) = jax.lax.scan(
                _remat(img_step), (imagined_prior0, recurrent_state0, latent0),
                (act_keys[:horizon], img_noise),
                unroll=img_unroll,
            )
            # traj[0] is the replayed posterior state; actions[0] is a
            # placeholder zero action (reference dreamer_v2.py:237-247)
            imagined_trajectories = jnp.concatenate([latent0[None], latents], 0)  # (H+1, TB, L)
            imagined_actions = jnp.concatenate(
                [jnp.zeros_like(actions_seq[:1]), actions_seq], 0
            )

            predicted_target_values = critic.apply(params["target_critic"], imagined_trajectories)
            predicted_rewards = world_model.reward_model.apply(
                new_wm_params["reward_model"], imagined_trajectories
            )
            if use_continues:
                continues = jax.nn.sigmoid(
                    world_model.continue_model.apply(
                        new_wm_params["continue_model"], imagined_trajectories
                    )
                )
                continues = jnp.concatenate([true_continue[None], continues[1:]], 0)
            else:
                continues = jnp.ones_like(predicted_rewards) * gamma

            lambda_values = compute_lambda_values(
                predicted_rewards[:-1],
                predicted_target_values[:-1],
                continues[:-1],
                bootstrap=predicted_target_values[-1:],
                lmbda=lmbda,
            )  # (H, TB, 1)
            discount = sg(
                jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-1]], 0), 0)
            )

            _, policies = actor.apply(actor_params, sg(imagined_trajectories[:-2]), False, act_keys[-1])

            # dynamics backprop through the imagined rollout
            dynamics = lambda_values[1:]
            # reinforce with the target critic as baseline
            advantage = sg(lambda_values[1:] - predicted_target_values[:-2])
            splits = np.cumsum(actions_dim)[:-1].tolist()
            sub_actions = (
                [imagined_actions] if is_continuous else jnp.split(imagined_actions, splits, -1)
            )
            reinforce = (
                jnp.stack(
                    [
                        p.log_prob(sg(a[1:-1]))[..., None]
                        for p, a in zip(policies, sub_actions)
                    ],
                    -1,
                ).sum(-1)
                * advantage
            )
            objective = objective_mix * reinforce + (1 - objective_mix) * dynamics
            try:
                entropy = ent_coef * jnp.stack([p.entropy() for p in policies], -1).sum(-1)
            except NotImplementedError:
                entropy = jnp.zeros_like(objective[..., 0])
            policy_loss = -jnp.mean(sg(discount[:-2]) * (objective + entropy[..., None]))
            aux = {
                "imagined_trajectories": sg(imagined_trajectories),
                "lambda_values": sg(lambda_values),
                "discount": discount,
            }
            return policy_loss, aux

        (policy_loss, actor_aux), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            params["actor"]
        )
        updates, new_actor_opt = actor_tx.update(actor_grads, opt_states["actor"], params["actor"])
        new_actor_params = optax.apply_updates(params["actor"], updates)

        # ---------------------------------------------------- critic
        traj = actor_aux["imagined_trajectories"][:-1]
        discount = actor_aux["discount"]
        lambda_values = actor_aux["lambda_values"]

        def critic_loss_fn(critic_params):
            qv = Independent(Normal(critic.apply(critic_params, traj), 1.0), 1)
            return -jnp.mean(discount[:-1, ..., 0] * qv.log_prob(lambda_values))

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(params["critic"])
        updates, new_critic_opt = critic_tx.update(critic_grads, opt_states["critic"], params["critic"])
        new_critic_params = optax.apply_updates(params["critic"], updates)

        new_params = {
            "world_model": new_wm_params,
            "actor": new_actor_params,
            "critic": new_critic_params,
            "target_critic": params["target_critic"],
        }
        new_opt_states = {
            "world_model": new_wm_opt,
            "actor": new_actor_opt,
            "critic": new_critic_opt,
        }
        post_ent = Independent(
            OneHotCategorical(logits=sg(wm_aux["posteriors_logits"])), 1
        ).entropy().mean()
        prior_ent = Independent(
            OneHotCategorical(logits=sg(wm_aux["priors_logits"])), 1
        ).entropy().mean()
        metrics = {
            "Loss/world_model_loss": rec_loss,
            "Loss/observation_loss": wm_aux["observation_loss"],
            "Loss/reward_loss": wm_aux["reward_loss"],
            "Loss/state_loss": wm_aux["state_loss"],
            "Loss/continue_loss": wm_aux["continue_loss"],
            "State/kl": wm_aux["kl"],
            "State/post_entropy": post_ent,
            "State/prior_entropy": prior_ent,
            "Loss/policy_loss": policy_loss,
            "Loss/value_loss": value_loss,
            "Grads/world_model": optax.global_norm(wm_grads),
            "Grads/actor": optax.global_norm(actor_grads),
            "Grads/critic": optax.global_norm(critic_grads),
        }
        return new_params, new_opt_states, metrics

    # training health sentinel hook (resilience/sentinel.py)
    return guard_update(runtime, train, cfg, n_state=2, donate_argnums=(0, 1))


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    import gymnasium as gym
    from gymnasium.vector import AsyncVectorEnv, AutoresetMode, SyncVectorEnv

    world_size = runtime.world_size
    runtime.seed_everything(cfg.seed)
    state = load_checkpoint(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None

    cfg.env.frame_stack = 1

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

    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape
        if is_continuous
        else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(set(cfg.algo.cnn_keys.decoder) - set(cfg.algo.cnn_keys.encoder)) > 0:
        raise RuntimeError("The CNN keys of the decoder must be contained in the encoder ones")
    if len(set(cfg.algo.mlp_keys.decoder) - set(cfg.algo.mlp_keys.encoder)) > 0:
        raise RuntimeError("The MLP keys of the decoder must be contained in the encoder ones")
    obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder

    world_model, actor, critic, params = build_agent(
        runtime,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        state["world_model"] if state else None,
        state["actor"] if state else None,
        state["critic"] if state else None,
        state["target_critic"] if state else None,
    )
    # no f32 carve-out for the target critic: DV2 HARD-updates it (a
    # wholesale copy of the bf16 critic every ``hard_update_freq`` steps,
    # including step 0), so bf16 storage loses nothing — unlike the
    # EMA targets in DV3/SAC
    params = runtime.replicate(runtime.to_param_dtype(params))

    precision = runtime.precision
    wm_tx = _make_optimizer(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients, precision)
    actor_tx = _make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients, precision)
    critic_tx = _make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients, precision)
    if state is not None:
        opt_states = restore_opt_states(state["opt_states"], params, runtime.precision)
    else:
        opt_states = runtime.replicate(
            {
                "world_model": wm_tx.init(params["world_model"]),
                "actor": actor_tx.init(params["actor"]),
                "critic": critic_tx.init(params["critic"]),
            }
        )

    player_params = {"world_model": params["world_model"], "actor": params["actor"]}
    player = PlayerDV2(
        world_model,
        actor,
        player_params,
        actions_dim,
        total_envs,
        cfg.algo.world_model.stochastic_size,
        cfg.algo.world_model.recurrent_model.recurrent_state_size,
        discrete_size=cfg.algo.world_model.discrete_size,
        expl_amount=float(cfg.algo.actor.get("expl_amount", 0.0)),
        device=runtime.player_device(player_params),
    )

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(dict(cfg.metric.aggregator))

    buffer_size = cfg.buffer.size // total_envs if not cfg.dry_run else 2
    buffer_type = str(cfg.buffer.get("type", "sequential")).lower()
    if buffer_type == "sequential":
        rb = EnvIndependentReplayBuffer(
            max(buffer_size, 2),
            n_envs=total_envs,
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{runtime.global_rank}"),
            buffer_cls=SequentialReplayBuffer,
        )
    elif buffer_type == "episode":
        rb = EpisodeBuffer(
            max(buffer_size, 4),
            minimum_episode_length=1 if cfg.dry_run else cfg.algo.per_rank_sequence_length,
            n_envs=total_envs,
            obs_keys=obs_keys,
            prioritize_ends=cfg.buffer.get("prioritize_ends", False),
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{runtime.global_rank}"),
        )
    else:
        raise ValueError(
            f"Unrecognized buffer type: must be one of `sequential` or `episode`, received: {buffer_type}"
        )
    if state and cfg.buffer.checkpoint:
        rb = restore_buffer(state["rb"], memmap=cfg.buffer.memmap)
    # HBM-resident replay window + on-device sampling (data/device_buffer.py)
    device_cache = maybe_create_for(cfg, runtime, rb, state)

    train_step = 0
    last_train = 0
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
        runtime, world_model, actor, critic, (wm_tx, actor_tx, critic_tx), cfg, is_continuous, actions_dim
    )
    health = train_fn.health.bind(ckpt_mgr=ckpt_mgr, select=("agent", "opt_states"))
    if health.enabled:
        observability.health_stats = health.stats

    @jax.jit
    def _hard_update(critic_params):
        return jax.tree_util.tree_map(jnp.copy, critic_params)

    # seed the buffer with the initial zero-action row (reference
    # dreamer_v2.py:571-585)
    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed)[0]
    for k in obs_keys:
        step_data[k] = obs[k][np.newaxis]
    step_data["terminated"] = np.zeros((1, total_envs, 1))
    step_data["truncated"] = np.zeros((1, total_envs, 1))
    if cfg.dry_run:
        step_data["truncated"] = step_data["truncated"] + 1
        step_data["terminated"] = step_data["terminated"] + 1
    step_data["actions"] = np.zeros((1, total_envs, int(np.sum(actions_dim))))
    step_data["rewards"] = np.zeros((1, total_envs, 1))
    step_data["is_first"] = np.ones_like(step_data["terminated"])
    rb.add(step_data, validate_args=cfg.buffer.validate_args)
    if device_cache is not None:
        device_cache.add(step_data)
    player.init_states()

    cumulative_per_rank_gradient_steps = 0
    metric_fetch_gate = MetricFetchGate(cfg.metric.get("fetch_every", 1))
    for iter_num in range(start_iter, total_iters + 1):
        observability.on_iteration(policy_step)
        policy_step += policy_steps_per_iter

        with timer("Time/env_interaction_time", SumMetric, sync_on_compute=False):
            if iter_num <= learning_starts and cfg.checkpoint.resume_from is None:
                real_actions = actions = np.array(envs.action_space.sample())
                if not is_continuous:
                    actions = np.concatenate(
                        [
                            np.eye(act_dim, dtype=np.float32)[act]
                            for act, act_dim in zip(actions.reshape(len(actions_dim), -1), actions_dim)
                        ],
                        axis=-1,
                    )
            else:
                prepared = prepare_obs(obs, cnn_keys=cfg.algo.cnn_keys.encoder, num_envs=total_envs)
                mask = {k: v for k, v in prepared.items() if k.startswith("mask")} or None
                action_list = player.get_actions(prepared, runtime.next_key(), mask=mask)
                actions, real_actions = fetch_actions(
                    action_list, actions_dim, is_continuous, total_envs
                )

            step_data["is_first"] = np.logical_or(
                step_data["terminated"], step_data["truncated"]
            ).astype(np.float32)
            next_obs, rewards, terminated, truncated, infos = envs.step(
                np.asarray(real_actions).reshape(envs.action_space.shape)
            )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)
            if cfg.dry_run and buffer_type == "episode":
                dones = np.ones_like(dones)
                terminated = np.ones_like(terminated)

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

        # row t holds the obs reached by the action taken this iteration
        for k in obs_keys:
            step_data[k] = real_next_obs[k][np.newaxis]
        obs = next_obs

        step_data["terminated"] = terminated.reshape((1, total_envs, -1)).astype(np.float32)
        step_data["truncated"] = truncated.reshape((1, total_envs, -1)).astype(np.float32)
        step_data["actions"] = np.asarray(actions).reshape(1, total_envs, -1)
        step_data["rewards"] = clip_rewards_fn(rewards.reshape((1, total_envs, -1)))
        rb.add(step_data, validate_args=cfg.buffer.validate_args)
        if device_cache is not None:
            device_cache.add(step_data)

        dones_idxes = dones.nonzero()[0].tolist()
        reset_envs = len(dones_idxes)
        if reset_envs > 0:
            reset_data = {}
            for k in obs_keys:
                reset_data[k] = (next_obs[k][dones_idxes])[np.newaxis]
            reset_data["terminated"] = np.zeros((1, reset_envs, 1))
            reset_data["truncated"] = np.zeros((1, reset_envs, 1))
            reset_data["actions"] = np.zeros((1, reset_envs, int(np.sum(actions_dim))))
            reset_data["rewards"] = np.zeros((1, reset_envs, 1))
            reset_data["is_first"] = np.ones_like(reset_data["terminated"])
            rb.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)
            if device_cache is not None:
                device_cache.add(reset_data, dones_idxes)
            step_data["terminated"][:, dones_idxes] = 0.0
            step_data["truncated"][:, dones_idxes] = 0.0
            player.init_states(dones_idxes)

        # ------------------------------------------------------ train
        if iter_num >= learning_starts:
            ratio_steps = policy_step - prefill_steps * policy_steps_per_iter
            per_rank_gradient_steps = ratio(ratio_steps / world_size)
            if per_rank_gradient_steps > 0:
                with sequence_batches(
                    rb, device_cache, runtime, per_rank_gradient_steps,
                    cfg.algo.per_rank_batch_size * world_size,
                    cfg.algo.per_rank_sequence_length, runtime.next_key(),
                    prioritize_ends=cfg.buffer.get("prioritize_ends", False),
                ) as feed:
                    with timer("Time/train_time", SumMetric, sync_on_compute=cfg.metric.sync_on_compute):
                        for batch in feed:
                            if (
                                cumulative_per_rank_gradient_steps
                                % cfg.algo.critic.per_rank_target_network_update_freq
                                == 0
                            ):
                                params["target_critic"] = _hard_update(params["critic"])
                            params, opt_states, train_metrics = train_fn(
                                params, opt_states, batch, runtime.next_key()
                            )
                            cumulative_per_rank_gradient_steps += 1
                    train_step += world_size
                rolled = health.tick()
                if rolled is not None:
                    params = restore_like(params, rolled["agent"])
                    opt_states = restore_like(opt_states, rolled["opt_states"])
                player.params = {"world_model": params["world_model"], "actor": params["actor"]}
                if aggregator and not aggregator.disabled and metric_fetch_gate():
                    with trace_scope("block_until_ready"):
                        fetched_metrics = device_get_metrics(train_metrics)
                    for k, v in fetched_metrics.items():
                        aggregator.update(k, v)

        # ------------------------------------------------------ logging
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

        # ------------------------------------------------------ checkpoint
        def _ckpt_state():
            ckpt_state = {
                "world_model": params["world_model"],
                "actor": params["actor"],
                "critic": params["critic"],
                "target_critic": params["target_critic"],
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
