"""DreamerV3 — TPU-native main loop (reference
sheeprl/algos/dreamer_v3/dreamer_v3.py train:48, main:361).

TPU-first design:
- ONE jitted gradient step covering the whole pipeline: dynamic learning
  (``lax.scan`` over the sequence — the reference's python time loop,
  dreamer_v3.py:113-146), world-model update, imagination (``lax.scan``
  over the horizon), Moments normalization, actor update, critic update.
  Three optax states threaded through;
- the percentile Moments state is part of the carried train state; its
  quantile over the (data-sharded) lambda-values is globally correct under
  SPMD (the reference all_gathers by hand, utils.py:57);
- EMA target-critic update is a tiny separate jitted call driven by the
  host cadence counter (reference dreamer_v3.py:674-680);
- the stateful player (masked RSSM resets on dones) acts where
  ``runtime.player_device`` puts it: beside a chip on the host CPU backend
  while its weights are small, on the training device (sharing the
  learner's arrays) once they are large.
"""

from __future__ import annotations

import os
import time
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.dreamer_v3.agent import RSSM, build_agent, build_player
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import (
    compute_lambda_values,
    init_moments,
    prepare_obs,
    test,
    update_moments,
)
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.device_buffer import maybe_create_for, sequence_batches
from sheeprl_tpu.envs.wrappers import RestartOnException
from sheeprl_tpu.ops.dyn_bptt import (
    dyn_bptt_setting,
    dyn_rssm_sequence,
    extract_dyn_params,
    rssm_dyn_bptt_eligible,
)
from sheeprl_tpu.obs import setup_observability, trace_scope
from sheeprl_tpu.resilience import CheckpointManager
from sheeprl_tpu.resilience.sentinel import guard_update, restore_like
from sheeprl_tpu.utils.callback import load_checkpoint, restore_buffer
from sheeprl_tpu.utils.distribution import (
    BernoulliSafeMode,
    Independent,
    MSEDistribution,
    OneHotCategorical,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.utils.env import make_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import (
    MetricFetchGate,
    Ratio,
    device_get_metrics,
    fetch_actions,
    save_configs,
    scan_remat,
    scan_unroll_setting,
)
from sheeprl_tpu.optim import restore_opt_states

sg = jax.lax.stop_gradient


def _make_optimizer(optim_cfg, clip_gradients, precision="32-true"):
    from sheeprl_tpu.optim import build_optimizer

    return build_optimizer(optim_cfg, clip_gradients, precision)


def make_wm_grad_fn(runtime, world_model, cfg, detach_heads: bool = False):
    """The DreamerV3 world-model loss and its gradient, for every update of the
    family: ``wm_grad(wm_params, data, key) -> ((rec_loss, aux), grads)``.
    ``detach_heads`` hands the reward and continue heads ``stop_gradient`` of
    the latents (Plan2Explore's exploration phase); nothing else differs."""
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    cnn_keys_dec = tuple(cfg.algo.cnn_keys.decoder)
    mlp_keys_dec = tuple(cfg.algo.mlp_keys.decoder)
    stochastic_size = int(cfg.algo.world_model.stochastic_size)
    discrete_size = int(cfg.algo.world_model.discrete_size)
    recurrent_state_size = int(cfg.algo.world_model.recurrent_model.recurrent_state_size)
    kl_dynamic = float(cfg.algo.world_model.kl_dynamic)
    kl_representation = float(cfg.algo.world_model.kl_representation)
    kl_free_nats = float(cfg.algo.world_model.kl_free_nats)
    kl_regularizer = float(cfg.algo.world_model.kl_regularizer)
    continue_scale_factor = float(cfg.algo.world_model.continue_scale_factor)
    decoupled = bool(cfg.algo.world_model.decoupled_rssm)
    # scan bodies at Dreamer sizes are launch/latency-bound (B=16 rows keep
    # every matmul far below an MXU tile): unrolling lets XLA fuse across
    # iterations and cuts while-loop trip counts, which round-3 profiling
    # showed to be 56% of device step time (dv3_profile_r3.json); "dots"
    # remat (utils.scan_remat) measured best for the dynamic scan on a v5e
    # (16.15 ms vs 16.78 ms without remat even at B=16 rows)
    scan_unroll = scan_unroll_setting(cfg, "dyn")

    rssm = world_model.rssm
    # efficient-BPTT dynamic scan (ops/dyn_bptt.py): same fwd lax.scan, but a
    # custom VJP whose reverse loop carries only (dh, dz) — the four weight
    # accumulators leave the backward while-loop's carry
    dyn_bptt = dyn_bptt_setting(cfg) and rssm_dyn_bptt_eligible(rssm)

    def wm_grad(wm_params, data, k_dyn):
        T, B = data["rewards"].shape[:2]
        with jax.named_scope("wm_encoder"):
            batch_obs = {k: data[k] / 255.0 - 0.5 for k in cnn_keys}
            batch_obs.update({k: data[k] for k in mlp_keys})
        is_first = data["is_first"].at[0].set(1.0)
        # shift actions: a_t in the buffer acted AFTER o_t; the RSSM input at
        # t is the PREVIOUS action (reference dreamer_v3.py:104)
        batch_actions = jnp.concatenate(
            [jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], axis=0
        )

        # all the rollout's categorical-sampling randomness is drawn HERE, in
        # two batched gumbel ops, instead of 3 threefry chains per scan
        # iteration — the scan bodies are latency-bound, so op count inside
        # the sequential loop is what sets the step time
        with jax.named_scope("wm_dynamics"):
            noise_shape = (T, B, stochastic_size, discrete_size)
            dyn_noise_q = jax.random.gumbel(k_dyn, noise_shape, jnp.float32)

        # the CNN encoder converts to the compute dtype at its first conv
        # anyway; handing it a bf16 copy halves the biggest single input read
        # (the (T, B, 64, 64, C) pixel stack).  MLP observations stay f32:
        # their encoder applies symlog BEFORE the first Dense, so pre-rounding
        # them would change the compression.  Loss targets keep f32 originals.
        with jax.named_scope("wm_encoder"):
            enc_obs = {k: batch_obs[k].astype(runtime.compute_dtype) for k in cnn_keys}
            enc_obs.update({k: batch_obs[k] for k in mlp_keys})

        def wm_loss_fn(wm_params):
            with jax.named_scope("wm_encoder"):
                embedded_obs = world_model.encoder.apply(wm_params["encoder"], enc_obs)  # (T, B, E)
            with jax.named_scope("wm_dynamics"):
                # constant wrt t: evaluate the learned initial state (which runs
                # the transition MLP) ONCE instead of in every scan iteration
                init_states = rssm.apply(
                    wm_params["rssm"], (B,), method=RSSM.get_initial_states
                )
                init_states = (init_states[0], init_states[1].reshape(B, -1))

                if decoupled:
                    # posterior depends only on obs (reference DecoupledRSSM:501;
                    # dreamer_v3.py:117-131): compute all posteriors up front,
                    # roll the recurrent model with the previous-step posterior
                    posteriors_logits, posteriors = rssm.apply(
                        wm_params["rssm"], embedded_obs, None, noise=dyn_noise_q,
                        method=RSSM._representation,
                    )
                    prev_posteriors = jnp.concatenate(
                        [jnp.zeros_like(posteriors[:1]), posteriors[:-1]], 0
                    )

                    # the recurrent model's input projection sees only
                    # [z_{t-1}, a_t] — all known up front here — so it batches
                    # over the whole sequence and the scan body shrinks to the
                    # is_first-gated GRU cell (RSSM.recurrent_features_seq)
                    feats = rssm.apply(
                        wm_params["rssm"], prev_posteriors, batch_actions,
                        is_first, init_states[1],
                        method=RSSM.recurrent_features_seq,
                    )

                    if rssm.seq_scan_eligible(int(feats.shape[-1])):
                        # the whole recurrence in ONE Pallas kernel (weights
                        # VMEM-resident across time, efficient-BPTT custom VJP)
                        recurrent_states = rssm.apply(
                            wm_params["rssm"], feats, is_first, init_states[0],
                            method=RSSM.gru_sequence_gated,
                        )
                    else:
                        @jax.named_scope("wm_dynamics")  # as img_step below
                        def dyn_step_dec(recurrent_state, inp):
                            feat, first = inp
                            recurrent_state = rssm.apply(
                                wm_params["rssm"],
                                feat,
                                recurrent_state,
                                first,
                                init_states[0],
                                method=RSSM.gru_step_gated,
                            )
                            return recurrent_state, recurrent_state

                        _, recurrent_states = jax.lax.scan(
                            dyn_step_dec,
                            jnp.zeros((B, recurrent_state_size)),
                            (feats, is_first),
                            unroll=scan_unroll,
                        )
                else:

                    # embed half of the representation model's first matmul,
                    # batched over the whole sequence (see representation_embed_proj)
                    emb_proj = rssm.apply(
                        wm_params["rssm"], embedded_obs, method=RSSM.representation_embed_proj
                    )

                    if dyn_bptt:
                        hs_, zst_, mixed_ = dyn_rssm_sequence(
                            jnp.zeros((B, stochastic_size * discrete_size)),
                            jnp.zeros((B, recurrent_state_size)),
                            batch_actions,
                            emb_proj,
                            is_first,
                            dyn_noise_q,
                            init_states[0],
                            init_states[1],
                            extract_dyn_params(wm_params["rssm"], recurrent_state_size),
                            eps_proj=rssm.eps,
                            eps_rep=rssm.eps,
                            unimix=rssm.unimix,
                            discrete=discrete_size,
                            matmul_dtype=rssm.dtype,
                            unroll=scan_unroll,
                        )
                        recurrent_states = hs_
                        posteriors = zst_.reshape(T, B, stochastic_size, discrete_size)
                        posteriors_logits = mixed_
                    else:
                        @jax.named_scope("wm_dynamics")  # as img_step below
                        def dyn_step(carry, inp):
                            posterior, recurrent_state = carry
                            action, emb, first, nq_t = inp
                            recurrent_state, posterior, posterior_logits = rssm.apply(
                                wm_params["rssm"],
                                posterior,
                                recurrent_state,
                                action,
                                emb,
                                first,
                                init_states,
                                noise=nq_t,
                                method=RSSM.dynamic_posterior,
                            )
                            return (posterior, recurrent_state), (
                                recurrent_state,
                                posterior,
                                posterior_logits,
                            )

                        init = (
                            jnp.zeros((B, stochastic_size, discrete_size)),
                            jnp.zeros((B, recurrent_state_size)),
                        )
                        _, (recurrent_states, posteriors, posteriors_logits) = jax.lax.scan(
                            scan_remat(dyn_step), init,
                            (batch_actions, emb_proj, is_first, dyn_noise_q),
                            unroll=scan_unroll,
                        )
                # prior logits for the KL, batched over the stacked recurrent
                # states of the whole sequence (the prior SAMPLE is unused by
                # the world-model loss, so nothing prior-related needs to live
                # inside the sequential scan)
                priors_logits, _ = rssm.apply(
                    wm_params["rssm"], recurrent_states, None, sample_state=False,
                    method=RSSM._transition,
                )
            with jax.named_scope("wm_heads"):
                latent_states = jnp.concatenate(
                    [posteriors.reshape(T, B, -1), recurrent_states], -1
                )
                reconstructed_obs = world_model.observation_model.apply(
                    wm_params["observation_model"], latent_states
                )
                po = {
                    k: MSEDistribution(reconstructed_obs[k], dims=len(reconstructed_obs[k].shape[2:]))
                    for k in cnn_keys_dec
                }
                po.update(
                    {
                        k: SymlogDistribution(
                            reconstructed_obs[k], dims=len(reconstructed_obs[k].shape[2:])
                        )
                        for k in mlp_keys_dec
                    }
                )
                # Plan2Explore's exploration phase trains the reward and continue
                # heads on detached latents (reference p2e_dv3_exploration.py:160-163)
                head_latents = sg(latent_states) if detach_heads else latent_states
                pr = TwoHotEncodingDistribution(
                    world_model.reward_model.apply(wm_params["reward_model"], head_latents), dims=1
                )
                pc = Independent(
                    BernoulliSafeMode(
                        logits=world_model.continue_model.apply(wm_params["continue_model"], head_latents)
                    ),
                    1,
                )
                continue_targets = 1 - data["terminated"]
                pl = priors_logits.reshape(T, B, stochastic_size, discrete_size)
                psl = posteriors_logits.reshape(T, B, stochastic_size, discrete_size)
                rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
                    po,
                    batch_obs,
                    pr,
                    data["rewards"],
                    pl,
                    psl,
                    kl_dynamic,
                    kl_representation,
                    kl_free_nats,
                    kl_regularizer,
                    pc,
                    continue_targets,
                    continue_scale_factor,
                )
                aux = {
                    "posteriors": posteriors,
                    "recurrent_states": recurrent_states,
                    "posteriors_logits": psl,
                    "priors_logits": pl,
                    "kl": kl,
                    "state_loss": state_loss,
                    "reward_loss": reward_loss,
                    "observation_loss": observation_loss,
                    "continue_loss": continue_loss,
                }
            return rec_loss, aux

        return jax.value_and_grad(wm_loss_fn, has_aux=True)(wm_params)

    return wm_grad


def make_train_fn(runtime, world_model, actor, critic, txs, cfg, is_continuous, actions_dim):
    """Build the single jitted DV3 gradient step."""
    wm_tx, actor_tx, critic_tx = txs
    stochastic_size = int(cfg.algo.world_model.stochastic_size)
    discrete_size = int(cfg.algo.world_model.discrete_size)
    stoch_state_size = stochastic_size * discrete_size
    recurrent_state_size = int(cfg.algo.world_model.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    ent_coef = float(cfg.algo.actor.ent_coef)
    moments_cfg = cfg.algo.actor.moments
    # as the dynamic scan (make_wm_grad_fn): "dots" remat kills the ~40 stacked
    # (H, T*B, 512) residual buffers of the imagination scan
    img_unroll = scan_unroll_setting(cfg, "img")

    rssm = world_model.rssm
    wm_grad = make_wm_grad_fn(runtime, world_model, cfg)

    def train(params, opt_states, moments_state, data, key):
        # one jax.named_scope per phase, names fixed and disjoint: they are
        # HLO metadata only, and what a profile (and chipbench/scope_reduce.py)
        # splits the update's device time by; backward ops inherit the name
        T, B = data["rewards"].shape[:2]
        k_dyn, k_img, k_actor = jax.random.split(key, 3)

        # ---------------------------------------------------- world model
        (rec_loss, wm_aux), wm_grads = wm_grad(params["world_model"], data, k_dyn)
        with jax.named_scope("wm_optim"):
            updates, new_wm_opt = wm_tx.update(wm_grads, opt_states["world_model"], params["world_model"])
            new_wm_params = optax.apply_updates(params["world_model"], updates)

        # ---------------------------------------------------- imagination
        # starts from the (detached) posteriors; rollout uses the UPDATED
        # world model (reference updates torch modules in place before
        # imagining)
        # B-MAJOR flatten (T,B,..)->(B,T,..)->(B*T,..): merging with the
        # sharded batch axis MAJOR keeps each device's rows contiguous, so
        # the mesh sharding survives into imagination/actor/critic — a
        # T-major flatten interleaves the shards and GSPMD silently
        # all-gathers, replicating 80%+ of the step's FLOPs on every
        # device.  Downstream ops reduce over the merged axis, so the
        # order change is semantics-free.
        with jax.named_scope("bh_imagine"):
            imagined_prior0 = sg(wm_aux["posteriors"]).swapaxes(0, 1).reshape(T * B, stoch_state_size)
            recurrent_state0 = (
                sg(wm_aux["recurrent_states"]).swapaxes(0, 1).reshape(T * B, recurrent_state_size)
            )
            true_continue = (1 - data["terminated"]).swapaxes(0, 1).reshape(1, T * B, 1)

            # imagination RNG, hoisted out of the scan body like the dynamic
            # scan's: one batched gumbel draw for every step's prior sample,
            # pre-split keys for the actor heads
            k_img_n, k_img_a = jax.random.split(k_img)
            img_noise = jax.random.gumbel(
                k_img_n, (horizon, T * B, stochastic_size, discrete_size), jnp.float32
            )
            act_keys = jax.random.split(k_img_a, horizon + 1)

        traj_dtype = runtime.compute_dtype

        def actor_loss_fn(actor_params):
            with jax.named_scope("bh_imagine"):
                latent0 = jnp.concatenate([imagined_prior0, recurrent_state0], -1).astype(traj_dtype)
                acts0, _ = actor.apply(actor_params, sg(latent0), False, act_keys[0])
                action0 = jnp.concatenate(acts0, -1)

                # the scope again inside the body: what autodiff hoists out of
                # the loop (the weights' bf16 casts) keeps only the body's own path
                @jax.named_scope("bh_imagine")
                def img_step(carry, inp):
                    prior, rec, action = carry
                    n_t, k_act = inp
                    imagined_prior, rec = rssm.apply(
                        new_wm_params["rssm"], prior, rec, action, None, noise=n_t,
                        method=RSSM.imagination,
                    )
                    imagined_prior = imagined_prior.reshape(-1, stoch_state_size)
                    latent = jnp.concatenate([imagined_prior, rec], -1)
                    acts, _ = actor.apply(actor_params, sg(latent), False, k_act)
                    action = jnp.concatenate(acts, -1)
                    # stack the trajectory in the compute dtype: every consumer
                    # (critic/reward/continue/actor heads) immediately converts
                    # to bf16 anyway, and the (H, T*B, L) stacks are the step's
                    # biggest activation traffic (reference trains these heads
                    # under torch.autocast bf16, so precision semantics match)
                    return (imagined_prior, rec, action), (latent.astype(traj_dtype), action)

                # remat: the imagination while-loop is HBM-bound on the ~40
                # stacked (H, T*B, 512) residual buffers autodiff saves for the
                # backward pass — recomputing the body instead keeps only the
                # carry + outputs and cuts the loop's memory traffic several-fold
                (_, _, _), (latents, actions_seq) = jax.lax.scan(
                    scan_remat(img_step), (imagined_prior0, recurrent_state0, action0),
                    (img_noise, act_keys[1:]),
                    unroll=img_unroll,
                )
                imagined_trajectories = jnp.concatenate([latent0[None], latents], 0)  # (H+1, TB, L)
                imagined_actions = jnp.concatenate([action0[None], actions_seq], 0)

            with jax.named_scope("bh_actor"):
                v_logits = critic.apply(params["critic"], imagined_trajectories)
                r_logits = world_model.reward_model.apply(
                    new_wm_params["reward_model"], imagined_trajectories
                )
                c_logits = world_model.continue_model.apply(
                    new_wm_params["continue_model"], imagined_trajectories
                )
                predicted_values = TwoHotEncodingDistribution(v_logits, dims=1).mean
                predicted_rewards = TwoHotEncodingDistribution(r_logits, dims=1).mean
                continues = Independent(BernoulliSafeMode(logits=c_logits), 1).mode
                continues = jnp.concatenate([true_continue.squeeze(0)[None], continues[1:]], 0)

                lambda_vals = compute_lambda_values(
                    predicted_rewards[1:], predicted_values[1:], continues[1:] * gamma, lmbda
                )
                discount = sg(jnp.cumprod(continues * gamma, 0) / gamma)

                # policies recomputed on the detached trajectories (reference
                # dreamer_v3.py:272-304)
                _, policies = actor.apply(actor_params, sg(imagined_trajectories), False, k_actor)

                baseline = predicted_values[:-1]
                new_moments, offset, invscale = update_moments(
                    moments_state,
                    lambda_vals,
                    float(moments_cfg.decay),
                    float(moments_cfg.max),
                    float(moments_cfg.percentile.low),
                    float(moments_cfg.percentile.high),
                )
                normed_lambda_values = (lambda_vals - offset) / invscale
                normed_baseline = (baseline - offset) / invscale
                advantage = normed_lambda_values - normed_baseline
                if is_continuous:
                    objective = advantage
                else:
                    splits = np.cumsum(actions_dim)[:-1].tolist()
                    sub_actions = jnp.split(imagined_actions, splits, -1)
                    logps = jnp.stack(
                        [p.log_prob(sg(a))[:-1][..., None] for p, a in zip(policies, sub_actions)],
                        -1,
                    ).sum(-1)
                    objective = logps * sg(advantage)
                try:
                    entropy = ent_coef * jnp.stack([p.entropy() for p in policies], -1).sum(-1)
                except NotImplementedError:
                    # must span the full trajectory (H+1 rows): the loss slices
                    # [:-1], while `objective` is already one row shorter
                    entropy = jnp.zeros(imagined_trajectories.shape[:2])
                policy_loss = -jnp.mean(sg(discount[:-1]) * (objective + entropy[..., None][:-1]))
                aux = {
                    "imagined_trajectories": sg(imagined_trajectories),
                    "lambda_values": sg(lambda_vals),
                    "discount": discount,
                    "moments": new_moments,
                }
            return policy_loss, aux

        (policy_loss, actor_aux), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            params["actor"]
        )
        with jax.named_scope("actor_optim"):
            updates, new_actor_opt = actor_tx.update(actor_grads, opt_states["actor"], params["actor"])
            new_actor_params = optax.apply_updates(params["actor"], updates)

        # ---------------------------------------------------- critic
        traj = actor_aux["imagined_trajectories"][:-1]
        discount = actor_aux["discount"]
        lambda_vals = actor_aux["lambda_values"]

        def critic_loss_fn(critic_params):
            with jax.named_scope("bh_critic"):
                q_logits = critic.apply(critic_params, traj)
                tgt_logits = critic.apply(params["target_critic"], traj)
                qv = TwoHotEncodingDistribution(q_logits, dims=1)
                predicted_target_values = TwoHotEncodingDistribution(tgt_logits, dims=1).mean
                value_loss = -qv.log_prob(lambda_vals)
                value_loss = value_loss - qv.log_prob(sg(predicted_target_values))
                return jnp.mean(value_loss * discount[:-1].squeeze(-1))

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(params["critic"])
        with jax.named_scope("critic_optim"):
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
        return new_params, new_opt_states, actor_aux["moments"], metrics

    # training health sentinel hook (resilience/sentinel.py); params,
    # opt states AND the return-normalization moments are all predicated
    # on the verdict
    return guard_update(runtime, train, cfg, n_state=3, donate_argnums=(0, 1, 2))


@jax.jit
def _ema(source_params, target_params, tau):  # the program keeps the name a trace of the loop knows it by
    return optax.incremental_update(source_params, target_params, tau)


def target_update_tau(critic_cfg, gradient_steps: int):
    """``tau`` of the target critics' EMA update that is due before this
    gradient step (1, a hard copy, before the very first), else ``None``."""
    if gradient_steps % critic_cfg.per_rank_target_network_update_freq != 0:
        return None
    return 1.0 if gradient_steps == 0 else critic_cfg.tau


def dv3_optimizers(cfg, precision):
    """(world model, actor, critic) optimizers of a DreamerV3 update."""
    return tuple(
        _make_optimizer(cfg.algo[name].optimizer, cfg.algo[name].clip_gradients, precision)
        for name in ("world_model", "actor", "critic")
    )


class DV3Learner:
    """The learner's side of :func:`train_loop`: it owns ``params``,
    ``opt_states`` and the moments, and offers the loop the player's params,
    one gradient step, the sentinel's rollback and its part of a checkpoint.
    It holds no env, ring, timer or logger."""

    test_name = ""  # the label handed to ``test`` when the run ends

    def __init__(self, runtime, cfg, modules, txs, params, opt_states, moments, is_continuous, actions_dim):
        self.world_model, self.actor, _ = modules
        self.params, self.opt_states, self.moments = params, opt_states, moments
        self.gradient_steps = 0
        self._critic_cfg = cfg.algo.critic
        # resolved in this module's globals at call time: the benchmark's spy
        # and chip_smoke.py wrap ``make_train_fn`` by assignment
        self._train_fn = make_train_fn(runtime, *modules, txs, cfg, is_continuous, actions_dim)
        self.health = self._train_fn.health

    @classmethod
    def from_state(cls, runtime, cfg, state, observation_space, actions_dim, is_continuous):
        *modules, params = build_agent(
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
        # bf16-true: bf16 parameter storage (the EMA target keeps f32 — its
        # small per-step updates would drown in bf16 rounding); the optimizers
        # below hold the f32 master copy (optim.master_weights)
        params = runtime.replicate(runtime.to_param_dtype(params, exclude=("target_critic",)))
        txs = dv3_optimizers(cfg, runtime.precision)
        if state is not None:
            opt_states = restore_opt_states(state["opt_states"], params, runtime.precision)
            moments = jax.tree_util.tree_map(jnp.asarray, state["moments"])
        else:
            opt_states = runtime.replicate(
                {name: tx.init(params[name]) for name, tx in zip(("world_model", "actor", "critic"), txs)}
            )
            moments = runtime.replicate(init_moments())
        return cls(runtime, cfg, modules, txs, params, opt_states, moments, is_continuous, actions_dim)

    def player_params(self, test: bool = False):
        """What the player acts with; ``test`` asks for the closing test's."""
        return {"world_model": self.params["world_model"], "actor": self.params["actor"]}

    def step(self, batch, key):
        """One gradient step on ``batch``, the target critic's EMA update
        included where it is due; returns the metrics."""
        tau = target_update_tau(self._critic_cfg, self.gradient_steps)
        if tau is not None:
            self.params["target_critic"] = _ema(self.params["critic"], self.params["target_critic"], tau)
        self.params, self.opt_states, self.moments, metrics = self._train_fn(
            self.params, self.opt_states, self.moments, batch, key
        )
        self.gradient_steps += 1
        return metrics

    def restore(self, rolled):
        """Adopt the checkpoint state the sentinel rolled back to."""
        self.params = restore_like(self.params, {k: rolled[k] for k in self.params})
        self.opt_states = restore_like(self.opt_states, rolled["opt_states"])
        self.moments = restore_like(self.moments, rolled["moments"])

    def checkpoint_state(self):
        """The agent's part of a checkpoint (and what a rollback loads); the
        loop adds its own."""
        return {**self.params, "opt_states": self.opt_states, "moments": self.moments}


def resume_states(cfg):
    """``(state, rb_state)`` of ``checkpoint.resume_from``: the checkpoint a run
    resumes from, and the same if its ring is to be restored from it."""
    state = load_checkpoint(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None
    return state, state if state and cfg.buffer.checkpoint else None


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    runtime.seed_everything(cfg.seed)
    state, rb_state = resume_states(cfg)
    train_loop(runtime, cfg, partial(DV3Learner.from_state, runtime, cfg, state), state, rb_state)


def train_loop(runtime, cfg, build_learner, state=None, rb_state=None, random_prefill: bool = True):
    """The collect -> replay -> train loop of the DreamerV3 family.

    ``build_learner(observation_space, actions_dim, is_continuous)`` gives the
    learner (see :class:`DV3Learner`) once the envs exist; ``state`` is the
    checkpoint whose counters the loop resumes from, ``rb_state`` the one its
    ring (and replay priorities) are restored from; without
    ``random_prefill`` the player acts from the first step on.  Nothing here
    asks which algorithm runs."""
    import gymnasium as gym
    from gymnasium.vector import AsyncVectorEnv, AutoresetMode, SyncVectorEnv

    world_size = runtime.world_size

    cfg.env.frame_stack = -1
    if 2 ** int(np.log2(cfg.env.screen_size)) != cfg.env.screen_size:
        raise ValueError(f"The screen size must be a power of 2, got: {cfg.env.screen_size}")

    logger = get_logger(runtime, cfg)
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name)
    runtime.print(f"Log dir: {log_dir}")
    observability = setup_observability(runtime, cfg, log_dir, logger=logger)
    if logger:
        logger.log_hyperparams(cfg)

    total_envs = cfg.env.num_envs * world_size
    thunks = [
        partial(
            RestartOnException,
            make_env(
                cfg, cfg.seed + i, 0, log_dir if runtime.is_global_zero else None, "train", vector_env_idx=i
            ),
        )
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

    if (
        len(set(cfg.algo.cnn_keys.encoder).intersection(set(cfg.algo.cnn_keys.decoder))) == 0
        and len(set(cfg.algo.mlp_keys.encoder).intersection(set(cfg.algo.mlp_keys.decoder))) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjointed")
    if len(set(cfg.algo.cnn_keys.decoder) - set(cfg.algo.cnn_keys.encoder)) > 0:
        raise RuntimeError("The CNN keys of the decoder must be contained in the encoder ones")
    if len(set(cfg.algo.mlp_keys.decoder) - set(cfg.algo.mlp_keys.encoder)) > 0:
        raise RuntimeError("The MLP keys of the decoder must be contained in the encoder ones")
    if cfg.metric.log_level > 0:
        runtime.print("Encoder CNN keys:", cfg.algo.cnn_keys.encoder)
        runtime.print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
        runtime.print("Decoder CNN keys:", cfg.algo.cnn_keys.decoder)
        runtime.print("Decoder MLP keys:", cfg.algo.mlp_keys.decoder)
    obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder

    learner = build_learner(observation_space, actions_dim, is_continuous)
    player = build_player(
        runtime, learner.world_model, learner.actor, learner.player_params(), actions_dim, total_envs, cfg
    )

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(dict(cfg.metric.aggregator))

    buffer_size = cfg.buffer.size // total_envs if not cfg.dry_run else 2
    rb = EnvIndependentReplayBuffer(
        max(buffer_size, 2),
        n_envs=total_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{runtime.global_rank}"),
        buffer_cls=SequentialReplayBuffer,
    )
    if rb_state:
        rb = restore_buffer(rb_state["rb"], memmap=cfg.buffer.memmap)

    # HBM-resident replay window + on-device sampling (data/device_buffer.py):
    # the host feed samples and re-uploads ~12.6 MB per gradient step — the
    # cache cuts that to one on-device gather, leaving only new frames
    # (n_envs x ~12 KB/step) to upload
    device_cache = maybe_create_for(cfg, runtime, rb, rb_state)

    train_step = 0
    train_metrics = None
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
    # a rollback loads the agent's keys; the ring and the loop's counters stay live
    health = learner.health.bind(ckpt_mgr=ckpt_mgr, select=tuple(learner.checkpoint_state()))
    if health.enabled:
        observability.health_stats = health.stats

    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed)[0]
    for k in obs_keys:
        step_data[k] = obs[k][np.newaxis]
    step_data["rewards"] = np.zeros((1, total_envs, 1))
    step_data["truncated"] = np.zeros((1, total_envs, 1))
    step_data["terminated"] = np.zeros((1, total_envs, 1))
    step_data["is_first"] = np.ones_like(step_data["terminated"])
    player.init_states()

    metric_fetch_gate = MetricFetchGate(cfg.metric.get("fetch_every", 1))
    heartbeat_t = time.perf_counter()
    for iter_num in range(start_iter, total_iters + 1):
        observability.on_iteration(policy_step)
        policy_step += policy_steps_per_iter

        with timer("Time/env_interaction_time", SumMetric, sync_on_compute=False):
            if random_prefill and iter_num <= learning_starts and cfg.checkpoint.resume_from is None:
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
                # the fetch is where the host waits for the player's program
                with timer("Time/player_step"):
                    prepared = prepare_obs(obs, cnn_keys=cfg.algo.cnn_keys.encoder, num_envs=total_envs)
                    mask = {k: v for k, v in prepared.items() if k.startswith("mask")} or None
                    action_list = player.get_actions(prepared, runtime.next_key(), mask=mask)
                    actions, real_actions = fetch_actions(
                        action_list, actions_dim, is_continuous, total_envs
                    )

            step_data["actions"] = np.asarray(actions).reshape(1, total_envs, -1)
            with timer("Time/replay_add"):
                rb.add(step_data, validate_args=cfg.buffer.validate_args)
                if device_cache is not None:
                    device_cache.add(step_data)

            with timer("Time/env_step"):
                next_obs, rewards, terminated, truncated, infos = envs.step(
                    np.asarray(real_actions).reshape(envs.action_space.shape)
                )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)

        step_data["is_first"] = np.zeros_like(step_data["terminated"])
        if "restart_on_exception" in infos:
            for i, agent_roe in enumerate(infos["restart_on_exception"]):
                if agent_roe and not dones[i]:
                    last_inserted_idx = (rb.buffer[i]._pos - 1) % rb.buffer[i].buffer_size
                    rb.buffer[i]["terminated"][last_inserted_idx] = np.zeros_like(
                        rb.buffer[i]["terminated"][last_inserted_idx]
                    )
                    rb.buffer[i]["truncated"][last_inserted_idx] = np.ones_like(
                        rb.buffer[i]["truncated"][last_inserted_idx]
                    )
                    rb.buffer[i]["is_first"][last_inserted_idx] = np.zeros_like(
                        rb.buffer[i]["is_first"][last_inserted_idx]
                    )
                    step_data["is_first"][:, i] = np.ones_like(step_data["is_first"][:, i])

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
            step_data[k] = next_obs[k][np.newaxis]
        obs = next_obs

        rewards = rewards.reshape((1, total_envs, -1))
        step_data["terminated"] = terminated.reshape((1, total_envs, -1)).astype(np.float32)
        step_data["truncated"] = truncated.reshape((1, total_envs, -1)).astype(np.float32)
        step_data["rewards"] = clip_rewards_fn(rewards)

        dones_idxes = dones.nonzero()[0].tolist()
        reset_envs = len(dones_idxes)
        if reset_envs > 0:
            reset_data = {}
            for k in obs_keys:
                reset_data[k] = (real_next_obs[k][dones_idxes])[np.newaxis]
            reset_data["terminated"] = step_data["terminated"][:, dones_idxes]
            reset_data["truncated"] = step_data["truncated"][:, dones_idxes]
            reset_data["actions"] = np.zeros((1, reset_envs, int(np.sum(actions_dim))))
            reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
            reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
            with timer("Time/replay_add"):
                rb.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)
                if device_cache is not None:
                    device_cache.add(reset_data, dones_idxes)

            step_data["rewards"][:, dones_idxes] = np.zeros_like(reset_data["rewards"])
            step_data["terminated"][:, dones_idxes] = np.zeros_like(step_data["terminated"][:, dones_idxes])
            step_data["truncated"][:, dones_idxes] = np.zeros_like(step_data["truncated"][:, dones_idxes])
            step_data["is_first"][:, dones_idxes] = np.ones_like(step_data["is_first"][:, dones_idxes])
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
                ) as feed:
                    with timer("Time/train_time", SumMetric, sync_on_compute=cfg.metric.sync_on_compute):
                        for batch in feed:
                            train_metrics = learner.step(batch, runtime.next_key())
                    train_step += world_size
                rolled = health.tick()
                if rolled is not None:
                    learner.restore(rolled)
                # The update donated the tree the player was acting with, so
                # the player is handed the new one here, directly after the
                # dispatch (and after a rollback put restored arrays in its
                # place) and before the next policy step can read the old one.
                # On the training device this is a rebinding of the learner's
                # own arrays; on the host CPU it is one device-to-host copy,
                # which also waits for the update that produced them.
                with timer("Time/params_refresh"):
                    player.params = learner.player_params()
                # metric.fetch_every amortizes the per-iteration device
                # sync of the losses dict on high-latency links (1 =
                # reference cadence; the aggregator still averages over the
                # log window)
                if aggregator and not aggregator.disabled and metric_fetch_gate():
                    with timer("Time/loss_fetch"), trace_scope("block_until_ready"):
                        fetched_metrics = device_get_metrics(train_metrics)
                    for k, v in fetched_metrics.items():
                        aggregator.update(k, v)

        # ------------------------------------------------------ logging
        if cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters
        ):
            # on_log reads the sums before this span closes, and timer.reset()
            # below drops the registry it was opened under: its time lands in
            # the next interval's record
            with timer("Time/log"):
                observability.on_log(policy_step, train_step)
                if logger:
                    if aggregator and not aggregator.disabled:
                        logger.log_metrics(aggregator.compute(), policy_step)
                        aggregator.reset()
                    logger.log_metrics(
                        {"Params/replay_ratio": learner.gradient_steps * world_size / policy_step},
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
                # throughput heartbeat on stdout: long runs are otherwise dark
                # between episode-end reward lines
                heartbeat_now = time.perf_counter()
                split = ""
                if logger and not timer.disabled:  # timer_metrics exists iff both hold
                    split = (
                        f", env_s={timer_metrics.get('Time/env_interaction_time', 0):.1f}"
                        f", train_s={timer_metrics.get('Time/train_time', 0):.1f}"
                    )
                runtime.print(
                    f"Rank-0: heartbeat policy_step={policy_step}, "
                    f"sps={(policy_step - last_log) / max(heartbeat_now - heartbeat_t, 1e-9):.2f}, "
                    f"gradient_steps={learner.gradient_steps}" + split
                )
                heartbeat_t = heartbeat_now
                last_log = policy_step
                last_train = train_step

        # ------------------------------------------------------ checkpoint
        def _ckpt_state():
            ckpt_state = {
                **learner.checkpoint_state(),
                "ratio": ratio.state_dict(),
                "iter_num": iter_num * world_size,
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": ckpt_mgr.last_checkpoint,
            }
            if cfg.buffer.checkpoint:
                ckpt_state["rb"] = rb
            if device_cache is not None and getattr(device_cache, "prioritized", False):
                # sequence-start priorities (decayed on sample) are not
                # derivable from the host buffer — ride the snapshot
                ckpt_state["replay_priority"] = device_cache.priority_state()
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
        player.params = learner.player_params(test=True)
        test_rew = test(player, runtime, cfg, log_dir, learner.test_name, greedy=False)
        if logger:
            logger.log_metrics({"Test/cumulative_reward": test_rew}, policy_step)
    if logger:
        logger.finalize()
