"""P2E-DV3 exploration phase (reference
sheeprl/algos/p2e_dv3/p2e_dv3_exploration.py train:41, main:522).

One jitted gradient step composed of:
1. world-model update (DV3 losses; reward/continue heads read DETACHED
   latents — p2e_dv3_exploration.py:160-163);
2. disagreement-ensemble update: each member regresses the next stochastic
   state from (z_t, h_t, a_t) (ensemble axis vmapped, single optimizer);
3. exploration behavior: imagination with the exploration actor; each
   exploration critic contributes a Moments-normalized advantage weighted
   by its configured weight; intrinsic critics get ensemble-variance
   rewards, task critics the reward model;
4. zero-shot task behavior: standard DV3 actor/critic update on the same
   replayed posteriors.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.dreamer_v3.agent import RSSM
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import (
    _make_optimizer,
    _ema,
    make_wm_grad_fn,
    resume_states,
    target_update_tau,
    train_loop,
)
from sheeprl_tpu.algos.dreamer_v3.utils import compute_lambda_values, init_moments, update_moments
from sheeprl_tpu.algos.p2e_dv3.agent import build_agent
from sheeprl_tpu.optim import restore_opt_states
from sheeprl_tpu.resilience.sentinel import guard_update, restore_like
from sheeprl_tpu.utils.distribution import (
    BernoulliSafeMode,
    Independent,
    OneHotCategorical,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm

sg = jax.lax.stop_gradient


def make_train_fn(
    runtime, world_model, actor, critic, ensemble, critics_cfg, txs, cfg, is_continuous, actions_dim
):
    """Build the single jitted P2E-DV3 exploration gradient step."""
    wm_tx, ens_tx, actor_task_tx, critic_task_tx, actor_expl_tx, critics_expl_txs = txs
    stochastic_size = int(cfg.algo.world_model.stochastic_size)
    discrete_size = int(cfg.algo.world_model.discrete_size)
    stoch_state_size = stochastic_size * discrete_size
    recurrent_state_size = int(cfg.algo.world_model.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    ent_coef = float(cfg.algo.actor.ent_coef)
    moments_cfg = cfg.algo.actor.moments
    intrinsic_reward_multiplier = float(cfg.algo.intrinsic_reward_multiplier)
    critic_names = tuple(critics_cfg.keys())
    weights_sum = sum(c["weight"] for c in critics_cfg.values())

    rssm = world_model.rssm
    wm_grad = make_wm_grad_fn(runtime, world_model, cfg, detach_heads=True)

    def _update_moments(state, x):
        return update_moments(
            state,
            x,
            float(moments_cfg.decay),
            float(moments_cfg.max),
            float(moments_cfg.percentile.low),
            float(moments_cfg.percentile.high),
        )

    def _imagine(actor_params, wm_params, imagined_prior0, recurrent_state0, key):
        """(H+1, TB, L) trajectories + (H+1, TB, A) actions, actions sampled
        from the given actor at every imagined state."""
        with jax.named_scope("bh_imagine"):
            keys = jax.random.split(key, horizon + 1)
            latent0 = jnp.concatenate([imagined_prior0, recurrent_state0], -1)
            acts0, _ = actor.apply(actor_params, sg(latent0), False, keys[0])
            action0 = jnp.concatenate(acts0, -1)

            @jax.named_scope("bh_imagine")  # again inside the scan body, as dreamer_v3's
            def img_step(carry, kk):
                prior, rec, action = carry
                k_im, k_act = jax.random.split(kk)
                imagined_prior, rec = rssm.apply(
                    wm_params["rssm"], prior, rec, action, k_im, method=RSSM.imagination
                )
                imagined_prior = imagined_prior.reshape(-1, stoch_state_size)
                latent = jnp.concatenate([imagined_prior, rec], -1)
                acts, _ = actor.apply(actor_params, sg(latent), False, k_act)
                action = jnp.concatenate(acts, -1)
                return (imagined_prior, rec, action), (latent, action)

            _, (latents, actions_seq) = jax.lax.scan(
                img_step, (imagined_prior0, recurrent_state0, action0), keys[1:]
            )
            traj = jnp.concatenate([latent0[None], latents], 0)
            acts = jnp.concatenate([action0[None], actions_seq], 0)
        return traj, acts

    def _policy_objective(actor_params, traj, imagined_actions, advantage, key):
        _, policies = actor.apply(actor_params, sg(traj), False, key)
        if is_continuous:
            objective = advantage
        else:
            splits = np.cumsum(actions_dim)[:-1].tolist()
            sub_actions = jnp.split(imagined_actions, splits, -1)
            logps = jnp.stack(
                [p.log_prob(sg(a))[:-1][..., None] for p, a in zip(policies, sub_actions)], -1
            ).sum(-1)
            objective = logps * sg(advantage)
        try:
            entropy = ent_coef * jnp.stack([p.entropy() for p in policies], -1).sum(-1)
        except NotImplementedError:
            # must span the full trajectory (H+1 rows): the caller slices
            # [:-1], while `objective` is already one row shorter
            entropy = jnp.zeros(traj.shape[:2])
        return objective, entropy

    def _critic_update(critic_params, target_params, tx, opt_state, traj, lambda_vals, discount):
        @jax.named_scope("bh_critic")
        def loss_fn(cp):
            qv = TwoHotEncodingDistribution(critic.apply(cp, traj[:-1]), dims=1)
            target_values = TwoHotEncodingDistribution(
                critic.apply(target_params, traj[:-1]), dims=1
            ).mean
            value_loss = -qv.log_prob(lambda_vals) - qv.log_prob(sg(target_values))
            return jnp.mean(value_loss * discount[:-1].squeeze(-1))

        loss, grads = jax.value_and_grad(loss_fn)(critic_params)
        with jax.named_scope("critic_optim"):
            updates, new_opt = tx.update(grads, opt_state, critic_params)
            new_params = optax.apply_updates(critic_params, updates)
        return new_params, new_opt, loss, optax.global_norm(grads)

    def train(params, opt_states, moments_task, moments_expl, data, key):
        T, B = data["rewards"].shape[:2]
        k_dyn, k_img_e, k_pol_e, k_img_t, k_pol_t = jax.random.split(key, 5)

        # ---------------------------------------------------- world model
        # dreamer_v3's loss; the reward/continue heads read DETACHED latents
        (rec_loss, wm_aux), wm_grads = wm_grad(params["world_model"], data, k_dyn)
        with jax.named_scope("wm_optim"):
            updates, new_wm_opt = wm_tx.update(wm_grads, opt_states["world_model"], params["world_model"])
            new_wm_params = optax.apply_updates(params["world_model"], updates)

        posts_flat = sg(wm_aux["posteriors"]).reshape(T, B, stoch_state_size)
        rec_states = sg(wm_aux["recurrent_states"])

        # ---------------------------------------------------- ensembles
        ens_in = jnp.concatenate([posts_flat, rec_states, data["actions"]], -1)

        def ens_loss_fn(ens_params):
            out = jax.vmap(lambda p: ensemble.apply(p, ens_in))(ens_params)[:, :-1]
            target = posts_flat[1:]
            # MSEDistribution(out, 1).log_prob summed over the last dim
            return jnp.sum(jax.vmap(lambda o: ((o - target) ** 2).sum(-1).mean())(out))

        ens_loss, ens_grads = jax.value_and_grad(ens_loss_fn)(params["ensembles"])
        updates, new_ens_opt = ens_tx.update(ens_grads, opt_states["ensembles"], params["ensembles"])
        new_ens_params = optax.apply_updates(params["ensembles"], updates)

        # B-MAJOR flatten (T,B,..)->(B,T,..)->(B*T,..): keeps the mesh's
        # batch sharding through the merge (a T-major flatten interleaves
        # the shards and GSPMD replicates the imagination phase on every
        # device); downstream ops reduce over the merged axis, so the
        # order change is semantics-free
        imagined_prior0 = posts_flat.swapaxes(0, 1).reshape(T * B, stoch_state_size)
        recurrent_state0 = rec_states.swapaxes(0, 1).reshape(T * B, recurrent_state_size)
        true_continue = (1 - data["terminated"]).swapaxes(0, 1).reshape(T * B, 1)

        # ------------------------------------- exploration behavior
        def actor_expl_loss_fn(actor_params):
            traj, imagined_actions = _imagine(
                actor_params, new_wm_params, imagined_prior0, recurrent_state0, k_img_e
            )
            with jax.named_scope("bh_actor"):
                continues = Independent(
                    BernoulliSafeMode(
                        logits=world_model.continue_model.apply(new_wm_params["continue_model"], traj)
                    ),
                    1,
                ).mode
                continues = jnp.concatenate([true_continue[None], continues[1:]], 0)

                advantages = []
                new_moments = {}
                per_critic = {}
                for name in critic_names:
                    ccfg = critics_cfg[name]
                    predicted_values = TwoHotEncodingDistribution(
                        critic.apply(params["critics_exploration"][name]["module"], traj), dims=1
                    ).mean
                    if ccfg["reward_type"] == "intrinsic":
                        ens_traj_in = jnp.concatenate([sg(traj), sg(imagined_actions)], -1)
                        preds = jax.vmap(lambda p: ensemble.apply(p, ens_traj_in))(new_ens_params)
                        # torch's Tensor.var is unbiased (ddof=1), reference :285
                        reward = preds.var(0, ddof=1).mean(-1, keepdims=True) * intrinsic_reward_multiplier
                    else:
                        reward = TwoHotEncodingDistribution(
                            world_model.reward_model.apply(new_wm_params["reward_model"], traj), dims=1
                        ).mean
                    lambda_vals = compute_lambda_values(
                        reward[1:], predicted_values[1:], continues[1:] * gamma, lmbda
                    )
                    nm, offset, invscale = _update_moments(moments_expl[name], lambda_vals)
                    new_moments[name] = nm
                    normed_lambda = (lambda_vals - offset) / invscale
                    normed_baseline = (predicted_values[:-1] - offset) / invscale
                    advantages.append((normed_lambda - normed_baseline) * ccfg["weight"] / weights_sum)
                    per_critic[name] = {
                        "lambda_values": sg(lambda_vals),
                        "predicted_values_mean": sg(predicted_values).mean(),
                        "reward_mean": sg(reward).mean() if ccfg["reward_type"] == "intrinsic" else None,
                    }
                advantage = jnp.stack(advantages, 0).sum(0)
                discount = sg(jnp.cumprod(continues * gamma, 0) / gamma)

                objective, entropy = _policy_objective(
                    actor_params, traj, imagined_actions, advantage, k_pol_e
                )
                policy_loss = -jnp.mean(sg(discount[:-1]) * (objective + entropy[..., None][:-1]))
                aux = {
                    "traj": sg(traj),
                    "discount": discount,
                    "per_critic": per_critic,
                    "moments": new_moments,
                }
            return policy_loss, aux

        (policy_loss_expl, expl_aux), actor_expl_grads = jax.value_and_grad(
            actor_expl_loss_fn, has_aux=True
        )(params["actor_exploration"])
        with jax.named_scope("actor_optim"):
            updates, new_actor_expl_opt = actor_expl_tx.update(
                actor_expl_grads, opt_states["actor_exploration"], params["actor_exploration"]
            )
            new_actor_expl = optax.apply_updates(params["actor_exploration"], updates)

        # per-critic exploration value updates
        new_critics_expl = {}
        new_critics_expl_opt = {}
        expl_value_losses = {}
        expl_critic_grads = {}
        for name in critic_names:
            new_module, new_opt, v_loss, g_norm = _critic_update(
                params["critics_exploration"][name]["module"],
                params["critics_exploration"][name]["target_module"],
                critics_expl_txs[name],
                opt_states["critics_exploration"][name],
                expl_aux["traj"],
                expl_aux["per_critic"][name]["lambda_values"],
                expl_aux["discount"],
            )
            new_critics_expl[name] = {
                "module": new_module,
                "target_module": params["critics_exploration"][name]["target_module"],
            }
            new_critics_expl_opt[name] = new_opt
            expl_value_losses[name] = v_loss
            expl_critic_grads[name] = g_norm

        # ------------------------------------- zero-shot task behavior
        def actor_task_loss_fn(actor_params):
            traj, imagined_actions = _imagine(
                actor_params, new_wm_params, imagined_prior0, recurrent_state0, k_img_t
            )
            with jax.named_scope("bh_actor"):
                predicted_values = TwoHotEncodingDistribution(
                    critic.apply(params["critic_task"], traj), dims=1
                ).mean
                predicted_rewards = TwoHotEncodingDistribution(
                    world_model.reward_model.apply(new_wm_params["reward_model"], traj), dims=1
                ).mean
                continues = Independent(
                    BernoulliSafeMode(
                        logits=world_model.continue_model.apply(new_wm_params["continue_model"], traj)
                    ),
                    1,
                ).mode
                continues = jnp.concatenate([true_continue[None], continues[1:]], 0)
                lambda_vals = compute_lambda_values(
                    predicted_rewards[1:], predicted_values[1:], continues[1:] * gamma, lmbda
                )
                nm, offset, invscale = _update_moments(moments_task, lambda_vals)
                normed_lambda = (lambda_vals - offset) / invscale
                normed_baseline = (predicted_values[:-1] - offset) / invscale
                advantage = normed_lambda - normed_baseline
                discount = sg(jnp.cumprod(continues * gamma, 0) / gamma)
                objective, entropy = _policy_objective(
                    actor_params, traj, imagined_actions, advantage, k_pol_t
                )
                policy_loss = -jnp.mean(sg(discount[:-1]) * (objective + entropy[..., None][:-1]))
                aux = {
                    "traj": sg(traj),
                    "discount": discount,
                    "lambda_values": sg(lambda_vals),
                    "moments": nm,
                }
            return policy_loss, aux

        (policy_loss_task, task_aux), actor_task_grads = jax.value_and_grad(
            actor_task_loss_fn, has_aux=True
        )(params["actor_task"])
        with jax.named_scope("actor_optim"):
            updates, new_actor_task_opt = actor_task_tx.update(
                actor_task_grads, opt_states["actor_task"], params["actor_task"]
            )
            new_actor_task = optax.apply_updates(params["actor_task"], updates)

        new_critic_task, new_critic_task_opt, value_loss_task, critic_task_grads = _critic_update(
            params["critic_task"],
            params["target_critic_task"],
            critic_task_tx,
            opt_states["critic_task"],
            task_aux["traj"],
            task_aux["lambda_values"],
            task_aux["discount"],
        )

        new_params = {
            "world_model": new_wm_params,
            "actor_task": new_actor_task,
            "critic_task": new_critic_task,
            "target_critic_task": params["target_critic_task"],
            "actor_exploration": new_actor_expl,
            "critics_exploration": new_critics_expl,
            "ensembles": new_ens_params,
        }
        new_opt_states = {
            "world_model": new_wm_opt,
            "ensembles": new_ens_opt,
            "actor_task": new_actor_task_opt,
            "critic_task": new_critic_task_opt,
            "actor_exploration": new_actor_expl_opt,
            "critics_exploration": new_critics_expl_opt,
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
            "Loss/ensemble_loss": ens_loss,
            "Loss/policy_loss_exploration": policy_loss_expl,
            "Loss/policy_loss_task": policy_loss_task,
            "Loss/value_loss_task": value_loss_task,
            "Grads/world_model": optax.global_norm(wm_grads),
            "Grads/ensemble": optax.global_norm(ens_grads),
            "Grads/actor_exploration": optax.global_norm(actor_expl_grads),
            "Grads/actor_task": optax.global_norm(actor_task_grads),
            "Grads/critic_task": critic_task_grads,
        }
        for name in critic_names:
            metrics[f"Loss/value_loss_exploration_{name}"] = expl_value_losses[name]
            metrics[f"Grads/critic_exploration_{name}"] = expl_critic_grads[name]
            metrics[f"Values_exploration/predicted_values_{name}"] = expl_aux["per_critic"][name][
                "predicted_values_mean"
            ]
            metrics[f"Values_exploration/lambda_values_{name}"] = expl_aux["per_critic"][name][
                "lambda_values"
            ].mean()
            if critics_cfg[name]["reward_type"] == "intrinsic":
                metrics[f"Rewards/intrinsic_{name}"] = expl_aux["per_critic"][name]["reward_mean"]
        return new_params, new_opt_states, task_aux["moments"], expl_aux["moments"], metrics

    # training health sentinel hook (resilience/sentinel.py); both
    # moments states are predicated on the verdict alongside params/opt
    return guard_update(runtime, train, cfg, n_state=4, donate_argnums=(0, 1, 2, 3))


def expand_exploration_metric_keys(cfg, critics_cfg) -> None:
    """Instantiate per-critic aggregator entries from the generic keys
    (reference p2e_dv3_exploration.py:695-707)."""
    generic = [
        "Loss/value_loss_exploration",
        "Values_exploration/predicted_values",
        "Values_exploration/lambda_values",
        "Grads/critic_exploration",
        "Rewards/intrinsic",
    ]
    metrics = cfg.metric.aggregator.metrics
    for g in generic:
        if g in metrics:
            for name, ccfg in critics_cfg.items():
                if g == "Rewards/intrinsic" and ccfg["reward_type"] != "intrinsic":
                    continue
                metrics[f"{g}_{name}"] = metrics[g]
            metrics.pop(g, None)


class ExplorationLearner:
    """Plan2Explore's exploration learner for ``dreamer_v3.train_loop`` (the
    contract is ``dreamer_v3.DV3Learner``'s): the player acts with the
    exploration actor and the run is tested zero-shot with the task actor;
    every exploration critic has an EMA target beside the task critic's."""

    test_name = "zero-shot"

    def __init__(self, runtime, cfg, state, observation_space, actions_dim, is_continuous):
        self.world_model, self.actor, critic, ensemble, critics_cfg, params = build_agent(
            runtime,
            actions_dim,
            is_continuous,
            cfg,
            observation_space,
            state["world_model"] if state else None,
            state["ensembles"] if state else None,
            state["actor_task"] if state else None,
            state["critic_task"] if state else None,
            state["target_critic_task"] if state else None,
            state["actor_exploration"] if state else None,
            state["critics_exploration"] if state else None,
        )
        # the trainable exploration critics get bf16 storage like everything
        # else; only their nested EMA target_module subtrees stay f32
        params = runtime.replicate(
            runtime.to_param_dtype(params, exclude=("target_critic_task", "target_module"))
        )

        def tx(part):
            return _make_optimizer(cfg.algo[part].optimizer, cfg.algo[part].clip_gradients, runtime.precision)

        wm_tx, ens_tx, actor_task_tx, critic_task_tx = tx("world_model"), tx("ensembles"), tx("actor"), tx("critic")
        actor_expl_tx, critics_expl_txs = tx("actor"), {name: tx("critic") for name in critics_cfg}
        if state is not None:
            params_for_opt = {
                **params,
                "critics_exploration": {n: p["module"] for n, p in params["critics_exploration"].items()},
            }
            opt_states = restore_opt_states(state["opt_states"], params_for_opt, runtime.precision)
            moments_task = jax.tree_util.tree_map(jnp.asarray, state["moments_task"])
            moments_expl = jax.tree_util.tree_map(jnp.asarray, state["moments_exploration"])
        else:
            opt_states = runtime.replicate(
                {
                    "world_model": wm_tx.init(params["world_model"]),
                    "ensembles": ens_tx.init(params["ensembles"]),
                    "actor_task": actor_task_tx.init(params["actor_task"]),
                    "critic_task": critic_task_tx.init(params["critic_task"]),
                    "actor_exploration": actor_expl_tx.init(params["actor_exploration"]),
                    "critics_exploration": {
                        name: critics_expl_txs[name].init(params["critics_exploration"][name]["module"])
                        for name in critics_cfg
                    },
                }
            )
            moments_task = runtime.replicate(init_moments())
            moments_expl = runtime.replicate({name: init_moments() for name in critics_cfg})
        self.params, self.opt_states = params, opt_states
        self.moments_task, self.moments_exploration = moments_task, moments_expl
        self.gradient_steps = 0
        self._critic_cfg = cfg.algo.critic
        if not MetricAggregator.disabled:
            expand_exploration_metric_keys(cfg, critics_cfg)
        self._train_fn = make_train_fn(
            runtime,
            self.world_model,
            self.actor,
            critic,
            ensemble,
            critics_cfg,
            (wm_tx, ens_tx, actor_task_tx, critic_task_tx, actor_expl_tx, critics_expl_txs),
            cfg,
            is_continuous,
            actions_dim,
        )
        self.health = self._train_fn.health

    def player_params(self, test: bool = False):
        actor = "actor_task" if test else "actor_exploration"
        return {"world_model": self.params["world_model"], "actor": self.params[actor]}

    def step(self, batch, key):
        tau = target_update_tau(self._critic_cfg, self.gradient_steps)
        if tau is not None:
            params = self.params
            params["target_critic_task"] = _ema(params["critic_task"], params["target_critic_task"], tau)
            for critic in params["critics_exploration"].values():
                critic["target_module"] = _ema(critic["module"], critic["target_module"], tau)
        self.params, self.opt_states, self.moments_task, self.moments_exploration, metrics = self._train_fn(
            self.params, self.opt_states, self.moments_task, self.moments_exploration, batch, key
        )
        self.gradient_steps += 1
        return metrics

    def restore(self, rolled):
        self.params = restore_like(self.params, {k: rolled[k] for k in self.params})
        self.opt_states = restore_like(self.opt_states, rolled["opt_states"])
        self.moments_task = restore_like(self.moments_task, rolled["moments_task"])
        self.moments_exploration = restore_like(self.moments_exploration, rolled["moments_exploration"])

    def checkpoint_state(self):
        return {
            **self.params,
            "opt_states": self.opt_states,
            "moments_task": self.moments_task,
            "moments_exploration": self.moments_exploration,
        }


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    runtime.seed_everything(cfg.seed)
    state, rb_state = resume_states(cfg)
    cfg.algo.player.actor_type = "exploration"
    train_loop(runtime, cfg, partial(ExplorationLearner, runtime, cfg, state), state, rb_state)
