"""PPO (coupled) — TPU-native main loop.

Counterpart of reference sheeprl/algos/ppo/ppo.py (train:30, main:106).
TPU-first design decisions (vs the reference's per-minibatch python loop +
DDP backward):

- the ENTIRE update — next-value bootstrap, GAE, advantage normalization,
  ``update_epochs`` x minibatches of clipped-surrogate steps — is ONE jitted
  function (``make_update_fn``) with ``lax.scan`` over epochs and
  minibatches. One dispatch per iteration; XLA fuses the whole schedule;
- data parallelism is the mesh ``data`` axis: the rollout batch is sharded
  over envs, params replicated; XLA inserts the gradient all-reduce that
  DDP did (SURVEY.md §2.7);
- ``cfg.env.num_envs`` is per data-parallel worker (reference semantics):
  the host runs ``num_envs * world_size`` vectorized envs;
- annealed lr/clip/ent coefficients are traced scalars (no recompiles);
  lr rides ``optax.inject_hyperparams``;
- truncation bootstrapping (reference ppo.py:301-321) computes V(final_obs)
  on a fixed-shape batch (all envs, substituted rows) to avoid recompiles.
"""

from __future__ import annotations

import copy
import os
import warnings
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.ppo.agent import build_agent, evaluate_actions, get_values, PPOPlayer, sample_actions
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.lm_policy import language_model_policy
from sheeprl_tpu.algos.ppo.utils import normalize_obs, prepare_obs, test
from sheeprl_tpu.algos.ppo.vtrace import vtrace
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.config.compose import _locate
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.obs import setup_observability, trace_scope
from sheeprl_tpu.ops.block_sparse_attention import take_engaged
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
    print_config,
    save_configs,
)
from sheeprl_tpu.optim import restore_opt_states
from jax import shard_map


def build_ppo_optimizer(
    optim_cfg: Dict[str, Any], max_grad_norm: float, precision: str = "32-true"
) -> optax.GradientTransformation:
    """optax optimizer with injectable learning_rate (for annealing inside
    jit) and optional global-norm clipping."""
    from sheeprl_tpu.optim import finalize_optimizer, normalize_optim_kwargs, resolve_weight_decay

    cfg = dict(optim_cfg)
    base_fn = _locate(cfg.pop("_target_"))
    kwargs = normalize_optim_kwargs(cfg)
    wd = resolve_weight_decay(kwargs, base_fn)
    tx = optax.inject_hyperparams(base_fn)(**kwargs)
    return finalize_optimizer(tx, wd, max_grad_norm, precision)


def rank_local_perm(key, n_total, n_envs, world_size, mb_size, num_minibatches):
    """Epoch permutation for ``buffer.share_data=False`` on the GSPMD
    fallback path (``strategy=fsdp``, where params stay ZeRO-sharded and
    the shard_map DDP core does not apply): rank w owns envs
    [w*B_local, (w+1)*B_local) of the (T, B) rollout; each rank's (t, b)
    cells are permuted among themselves and the ranks striped across every
    minibatch, so a minibatch row never leaves its rank — the SPMD
    equivalent of DDP's per-rank DataLoader (reference ppo.py:383-390 with
    share_data left False). The primary multi-device path implements the
    same semantics directly in shard_map (``_update_shard_map``)."""
    b_local = n_envs // world_size
    n_local = n_total // world_size  # = T * b_local per rank
    pr = mb_size // world_size
    local = jax.vmap(lambda k: jax.random.permutation(k, n_local))(
        jax.random.split(key, world_size)
    )  # (W, n_local) of rank-linear indices l = t*b_local + b
    n_used_local = num_minibatches * pr
    if n_used_local > n_local:  # pad by wrapping as many times as needed
        local = jnp.tile(local, (1, -(-n_used_local // n_local)))[:, :n_used_local]
    t, b = local // b_local, local % b_local
    flat_idx = t * n_envs + jnp.arange(world_size)[:, None] * b_local + b
    striped = flat_idx.reshape(world_size, num_minibatches, pr)
    return striped.transpose(1, 0, 2).reshape(-1)


def make_update_fn(
    runtime,
    module,
    tx: optax.GradientTransformation,
    cfg: Dict[str, Any],
    obs_keys: Sequence[str],
):
    """Build the single jitted PPO update (GAE + epochs x minibatches).

    ``buffer.share_data`` (reference ppo.py:40-50, 383-390) controls the
    epoch shuffle: True gathers the whole rollout and permutes GLOBALLY —
    under SPMD that is simply a global permutation of the flattened batch,
    XLA inserting the cross-device all-to-all the reference got from
    fabric.all_gather + DistributedSampler. False (the reference default)
    keeps minibatches rank-local: each device shard is permuted within
    itself and minibatches are rank-striped, so no rollout data ever
    crosses devices — exactly DDP semantics.

    A language-model policy (``algo.policy=sdar_moe`` or ``mla_moe``:
    ``lm_policy.py``) takes the episode update instead:
    ``make_episode_update_fn``."""
    if language_model_policy(cfg) is not None:
        return make_episode_update_fn(runtime, module, tx, cfg)
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    update_epochs = int(cfg.algo.update_epochs)
    share_data = bool(cfg.buffer.get("share_data", False))
    world_size = int(runtime.world_size)
    mb_size = int(cfg.algo.per_rank_batch_size) * runtime.world_size
    gamma = float(cfg.algo.gamma)
    gae_lambda = float(cfg.algo.gae_lambda)
    vf_coef = float(cfg.algo.vf_coef)
    clip_vloss = bool(cfg.algo.clip_vloss)
    reduction = str(cfg.algo.loss_reduction)
    normalize_adv = bool(cfg.algo.normalize_advantages)
    # V-trace off-policy correction (vtrace.py): replaces GAE with
    # rho/c-clipped IS-weighted targets so per-shard policy lag in the
    # decoupled fan-in is corrected instead of assumed-zero.  Off by
    # default; with on-policy data the estimator is exactly GAE.
    vt_cfg = cfg.algo.get("vtrace", None) or {}
    use_vtrace = bool(vt_cfg.get("enabled", False))
    vt_rho_clip = float(vt_cfg.get("rho_clip", 1.0))
    vt_c_clip = float(vt_cfg.get("c_clip", 1.0))

    def _gae_and_flatten(params, data, next_obs):
        """Value targets on device (GAE, or V-trace when enabled), then
        flatten (T, E, ...) -> (T*E, ...).  A ``mask`` key in ``data``
        (the mask-padded fan-in's env-validity columns) rides through the
        flatten untouched — the minibatch losses consume it as weights."""
        norm_next_obs = normalize_obs(
            {k: next_obs[k].astype(jnp.float32) for k in obs_keys}, cnn_keys, obs_keys
        )
        next_values = get_values(module, params, norm_next_obs)
        if use_vtrace:
            # target-policy logprobs of the rollout actions under the
            # CURRENT params: one extra forward pass over the rollout,
            # the price of correcting per-shard staleness
            t_len, n_env = data["rewards"].shape[:2]
            flat_obs = normalize_obs(
                {
                    k: data[k].reshape(t_len * n_env, *data[k].shape[2:]).astype(jnp.float32)
                    for k in obs_keys
                },
                cnn_keys,
                obs_keys,
            )
            flat_actions = data["actions"].reshape(t_len * n_env, *data["actions"].shape[2:])
            tgt_logprobs, _, _ = evaluate_actions(module, params, flat_obs, flat_actions)
            log_rhos = tgt_logprobs.reshape(data["logprobs"].shape).astype(jnp.float32) - data[
                "logprobs"
            ].astype(jnp.float32)
            returns, advantages = vtrace(
                data["rewards"],
                data["values"],
                data["dones"],
                next_values,
                log_rhos,
                gamma,
                gae_lambda,
                vt_rho_clip,
                vt_c_clip,
            )
        else:
            returns, advantages = gae(
                data["rewards"], data["values"], data["dones"], next_values, gamma, gae_lambda
            )
        data = {**data, "returns": returns, "advantages": advantages}
        n_total = data["rewards"].shape[0] * data["rewards"].shape[1]
        flat = {k: v.reshape(n_total, *v.shape[2:]) for k, v in data.items()}
        return flat, n_total

    def _update_shard_map(params, opt_state, data, next_obs, key, clip_coef, ent_coef):
        """Multi-device update as an explicit DDP program (shard_map over
        the "data" axis).

        GSPMD cannot keep the epoch shuffle sharded: ``x[perm]`` with a
        data-dependent permutation over the flattened rollout forces an
        all-gather and replicates the whole update on every device (zero
        DP speedup — measured 8x redundant FLOPs on an 8-device mesh).
        shard_map makes the locality explicit instead: each rank GAEs and
        shuffles only its own env columns, computes per-rank minibatch
        gradients, and a ``pmean`` reproduces DDP's gradient all-reduce.
        share_data=True all-gathers the rollout first and applies ONE
        global permutation (same key on every rank), each rank computing
        its stripe of every global minibatch — the reference's
        fabric.all_gather + DistributedSampler (reference ppo.py:383-390).
        Advantage normalization is per-rank-minibatch, exactly the
        reference's DDP semantics (the single-device path normalizes the
        global minibatch, which coincides when world_size == 1)."""
        from jax.sharding import PartitionSpec as SMP

        from sheeprl_tpu.parallel.sharding import BATCH_AXES

        per_rank_mb = mb_size // world_size
        data_specs = jax.tree_util.tree_map(lambda _: SMP(None, BATCH_AXES), data)
        obs_specs = jax.tree_util.tree_map(lambda _: SMP(BATCH_AXES), next_obs)

        def body(params, opt_state, data, next_obs, key, clip_coef, ent_coef):
            # flattened (data, fsdp) shard index: the specs above split the
            # batch over BOTH mesh axes, so rank-local logic follows suit
            rank = runtime.layout.flat_rank()
            flat, n_local = _gae_and_flatten(params, data, next_obs)
            if share_data:
                flat = jax.tree_util.tree_map(
                    lambda x: jax.lax.all_gather(x, BATCH_AXES, axis=0, tiled=True), flat
                )
                n_rows = n_local * world_size
                num_minibatches = max(1, -(-n_rows // mb_size))
            else:
                n_rows = n_local
                num_minibatches = max(1, -(-n_local // per_rank_mb))

            def loss_fn(p, mb):
                obs = {k: mb[k].astype(jnp.float32) for k in obs_keys}
                obs = normalize_obs(obs, cnn_keys, obs_keys)
                new_logprobs, entropy, new_values = evaluate_actions(module, p, obs, mb["actions"])
                w = mb.get("mask")  # mask-padded fan-in: dead columns weigh 0
                adv = mb["advantages"]
                if normalize_adv:
                    adv = normalize_tensor(adv, mask=w > 0 if w is not None else None)
                pg = policy_loss(new_logprobs, mb["logprobs"], adv, clip_coef, reduction, weights=w)
                vl = value_loss(
                    new_values, mb["values"], mb["returns"], clip_coef, clip_vloss, reduction, weights=w
                )
                ent = entropy_loss(entropy, reduction, weights=w)
                total = pg + vf_coef * vl + ent_coef * ent
                return total, jnp.stack([pg, vl, ent])

            grad_fn = jax.grad(loss_fn, has_aux=True)

            def mb_step(carry, mb):
                params, opt_state = carry
                grads, losses = grad_fn(params, mb)
                # DDP gradient all-reduce (+ averaged losses for logging)
                grads = jax.lax.pmean(grads, BATCH_AXES)
                losses = jnp.concatenate(
                    [jax.lax.pmean(losses, BATCH_AXES), optax.global_norm(grads)[None]]
                )
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), losses

            def epoch_step(carry, ekey):
                params, opt_state = carry
                if share_data:
                    n_used = num_minibatches * mb_size
                    perm = jax.random.permutation(ekey, n_rows)  # same key -> same global perm
                    if n_used > n_rows:
                        perm = jnp.tile(perm, -(-n_used // n_rows))[:n_used]
                    my = jnp.take(perm.reshape(num_minibatches, world_size, per_rank_mb), rank, axis=1)
                else:
                    n_used = num_minibatches * per_rank_mb
                    perm = jax.random.permutation(jax.random.fold_in(ekey, rank), n_rows)
                    if n_used > n_rows:
                        perm = jnp.tile(perm, -(-n_used // n_rows))[:n_used]
                    my = perm.reshape(num_minibatches, per_rank_mb)
                shuffled = jax.tree_util.tree_map(
                    lambda x: x[my.reshape(-1)].reshape(num_minibatches, per_rank_mb, *x.shape[1:]),
                    flat,
                )
                (params, opt_state), losses = jax.lax.scan(mb_step, (params, opt_state), shuffled)
                return (params, opt_state), losses.mean(0)

            keys = jax.random.split(key, update_epochs)
            (params, opt_state), losses = jax.lax.scan(epoch_step, (params, opt_state), keys)
            mean_losses = losses.mean(0)
            metrics = {
                "Loss/policy_loss": mean_losses[0],
                "Loss/value_loss": mean_losses[1],
                "Loss/entropy_loss": mean_losses[2],
                "Grads/agent": mean_losses[3],
            }
            return params, opt_state, metrics

        return shard_map(
            body,
            mesh=runtime.mesh,
            in_specs=(SMP(), SMP(), data_specs, obs_specs, SMP(), SMP(), SMP()),
            out_specs=(SMP(), SMP(), SMP()),
            check_vma=False,
        )(params, opt_state, data, next_obs, key, clip_coef, ent_coef)

    def update(params, opt_state, data, next_obs, key, clip_coef, ent_coef, lr):
        # inject the (possibly annealed) learning rate
        opt_state = _set_lr(opt_state, lr)
        if runtime.ddp_gate(data["rewards"].shape[1], "PPO"):
            # explicit DDP mapping: GSPMD cannot keep the epoch-shuffle
            # gather sharded (a data-dependent x[perm] over the flattened
            # rollout replicates the WHOLE update on every device), so the
            # multi-device path runs the shuffle+minibatch core in
            # shard_map with rank-local permutations and an explicit
            # pmean of the gradients
            return _update_shard_map(params, opt_state, data, next_obs, key, clip_coef, ent_coef)
        flat, n_total = _gae_and_flatten(params, data, next_obs)
        num_minibatches = max(1, -(-n_total // mb_size))
        n_used = num_minibatches * mb_size

        def loss_fn(p, mb):
            obs = {k: mb[k].astype(jnp.float32) for k in obs_keys}
            obs = normalize_obs(obs, cnn_keys, obs_keys)
            new_logprobs, entropy, new_values = evaluate_actions(module, p, obs, mb["actions"])
            w = mb.get("mask")  # mask-padded fan-in: dead columns weigh 0
            adv = mb["advantages"]
            if normalize_adv:
                adv = normalize_tensor(adv, mask=w > 0 if w is not None else None)
            pg = policy_loss(new_logprobs, mb["logprobs"], adv, clip_coef, reduction, weights=w)
            vl = value_loss(
                new_values, mb["values"], mb["returns"], clip_coef, clip_vloss, reduction, weights=w
            )
            ent = entropy_loss(entropy, reduction, weights=w)
            total = pg + vf_coef * vl + ent_coef * ent
            return total, jnp.stack([pg, vl, ent])

        grad_fn = jax.grad(loss_fn, has_aux=True)

        def mb_step(carry, mb):
            params, opt_state = carry
            grads, losses = grad_fn(params, mb)
            # pre-clip global grad norm rides the metrics for telemetry and
            # the training sentinel's z-score monitor
            losses = jnp.concatenate([losses, optax.global_norm(grads)[None]])
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), losses

        n_envs = data["rewards"].shape[1]

        def _epoch_perm(ekey):
            if share_data or world_size == 1 or n_envs % world_size != 0:
                perm = jax.random.permutation(ekey, n_total)
                if n_used > n_total:  # pad by wrapping (fixed shapes for scan)
                    perm = jnp.tile(perm, -(-n_used // n_total))[:n_used]
                return perm
            return rank_local_perm(ekey, n_total, n_envs, world_size, mb_size, num_minibatches)

        def epoch_step(carry, ekey):
            params, opt_state = carry
            perm = _epoch_perm(ekey)
            shuffled = jax.tree_util.tree_map(
                lambda x: x[perm].reshape(num_minibatches, mb_size, *x.shape[1:]), flat
            )
            (params, opt_state), losses = jax.lax.scan(mb_step, (params, opt_state), shuffled)
            return (params, opt_state), losses.mean(0)

        keys = jax.random.split(key, update_epochs)
        (params, opt_state), losses = jax.lax.scan(epoch_step, (params, opt_state), keys)
        mean_losses = losses.mean(0)
        metrics = {
            "Loss/policy_loss": mean_losses[0],
            "Loss/value_loss": mean_losses[1],
            "Loss/entropy_loss": mean_losses[2],
            "Grads/agent": mean_losses[3],
        }
        return params, opt_state, metrics

    # training health sentinel (resilience/sentinel.py): the shared hook
    # every update builder routes through — off (default) returns the
    # plain jitted step untouched
    return guard_update(runtime, update, cfg, n_state=2, donate_argnums=(0, 1))


def make_episode_update_fn(runtime, policy, tx: optax.GradientTransformation, cfg: Dict[str, Any]):
    """The PPO update of a language-model policy (``lm_policy.py``: the
    block-diffusion ``SdarPolicy``, the causal ``CausalLmPolicy``): the unit of
    a minibatch is a whole episode, because one forward pass over an episode
    (its packed denoising trajectory, or its tokens under the causal mask)
    yields the log-probabilities and values of all its steps.  GAE, the
    clipped losses and the optimizer are the ones ``make_update_fn`` uses; the
    epoch shuffle permutes episodes.  Where the policy's ``evaluate_episodes``
    hands back an ``aux_loss`` among its counters (the causal policy's
    multi-token-prediction cross-entropy), the loss gains ``policy.aux_coef x
    aux_loss`` as a fourth term, and nothing otherwise.

    ``data``: ``prompt`` (1, E, P) and ``actions`` (T, E, 2) integers,
    ``logprobs`` / ``values`` / ``rewards`` / ``dones`` (T, E, 1), one whole
    episode per env (the kind's fused collector).  Returns ``(params,
    opt_state, metrics, probe)``: ``metrics`` are scalars (losses, gradient
    norm, the expert layers' counters, the auxiliary loss's counters);
    ``probe`` holds what each minibatch step produced (its episodes,
    log-probabilities, values, losses, the gradient's norm whole and leaf by
    leaf, the norm of every leaf's change ``new - old`` in float32, routing
    choice, load per expert, whether the sorted buffer was the short one with
    the compact token side, and the tokens that hold more choices than it reads),
    stacked over the call's steps, for whoever compares the update with the
    plain reference."""
    if runtime.world_size > 1:
        raise ValueError("the language-model policy updates on one device; set fabric.devices=1")
    update_epochs = int(cfg.algo.update_epochs)
    mb_eps = int(cfg.algo.per_rank_batch_size)
    gamma, gae_lambda = float(cfg.algo.gamma), float(cfg.algo.gae_lambda)
    vf_coef, clip_vloss = float(cfg.algo.vf_coef), bool(cfg.algo.clip_vloss)
    reduction, normalize_adv = str(cfg.algo.loss_reduction), bool(cfg.algo.normalize_advantages)
    aux_coef = float(getattr(policy, "aux_coef", 0.0))

    def loss_fn(p, mb, clip_coef, ent_coef):
        logp, entropy, values, aux = policy.evaluate_episodes(p, mb["prompt"], mb["actions"])
        with jax.named_scope("ppo_loss"):
            adv = normalize_tensor(mb["advantages"]) if normalize_adv else mb["advantages"]
            pg = policy_loss(logp, mb["logprobs"], adv, clip_coef, reduction)
            vl = value_loss(values, mb["values"], mb["returns"], clip_coef, clip_vloss, reduction)
            ent = entropy_loss(entropy, reduction)
            total = pg + vf_coef * vl + ent_coef * ent
            terms = [pg, vl, ent]
            if "aux_loss" in aux:  # the policy's own auxiliary loss: a fourth term
                total = total + aux_coef * aux["aux_loss"]
                terms.append(aux["aux_loss"])
        return total, (jnp.stack(terms), logp, values, aux)

    grad_fn = jax.grad(loss_fn, has_aux=True)

    def update(params, opt_state, data, next_obs, key, clip_coef, ent_coef, lr):
        del next_obs  # every rollout ends with its episodes: nothing to bootstrap from
        opt_state = _set_lr(opt_state, lr)
        n_eps = data["rewards"].shape[1]
        if n_eps % mb_eps:
            raise ValueError(f"{n_eps} episodes a rollout do not divide into minibatches of {mb_eps}")
        with jax.named_scope("ppo_loss"):
            returns, advantages = gae(
                data["rewards"], data["values"], data["dones"], jnp.zeros_like(data["values"][0]), gamma, gae_lambda
            )
        per_step = {"logprobs": data["logprobs"], "values": data["values"], "returns": returns, "advantages": advantages}
        # episode-major: (E, T) per-step scalars, (E, T, 2) actions, (E, P) prompts
        episodes = {k: jnp.swapaxes(v[..., 0], 0, 1) for k, v in per_step.items()}
        episodes.update(actions=jnp.swapaxes(data["actions"], 0, 1), prompt=data["prompt"][0])

        def mb_step(carry, ids):
            params, opt_state = carry
            mb = {k: v[ids] for k, v in episodes.items()}
            grads, (losses, logp, values, aux) = grad_fn(params, mb, clip_coef, ent_coef)
            with jax.named_scope("ppo_optim"):
                updates, opt_state = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                leaf_norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))  # noqa: E731
                # what the step did to the state, read off the parameters the next step starts from
                moved = jax.tree_util.tree_map(
                    lambda new, old: leaf_norm(new.astype(jnp.float32) - old.astype(jnp.float32)), new_params, params
                )
            probe = {"episodes": ids, "logprobs": logp, "values": values, "losses": losses,
                     "grad_norm": optax.global_norm(grads), "grad_leaf_norms": jax.tree_util.tree_map(leaf_norm, grads),
                     "moved_leaf_norms": moved, "load": aux["load"], "dropped": aux["dropped"].sum(),
                     "top_i": aux["top_i"], "entropy": aux["entropy"].mean(), "short": aux["short"],
                     "overflow": aux["overflow"].max(),
                     "aux_counters": aux.get("aux_counters", {})}
            return (new_params, opt_state), probe

        def epoch_step(carry, ekey):
            ids = jax.random.permutation(ekey, n_eps).reshape(n_eps // mb_eps, mb_eps)
            return jax.lax.scan(mb_step, carry, ids)

        (params, opt_state), probe = jax.lax.scan(epoch_step, (params, opt_state), jax.random.split(key, update_epochs))
        probe = jax.tree_util.tree_map(lambda x: x.reshape(-1, *x.shape[2:]), probe)  # (epochs * minibatches, ...)
        losses = probe["losses"].mean(0)
        load = probe["load"].sum(0).astype(jnp.float32)  # (layers, experts held) over the call
        steps, _, positions, top_k = probe["top_i"].shape
        assignments = steps * positions * top_k  # a layer's over the call, to held and absent experts alike
        metrics = {
            "Loss/policy_loss": losses[0],
            "Loss/value_loss": losses[1],
            "Loss/entropy_loss": losses[2],
            "Grads/agent": probe["grad_norm"].mean(),
            "MoE/load_max_over_mean": (load.max(-1) / jnp.maximum(load.mean(-1), 1.0)).max(),
            "MoE/held_share": (load.sum(-1) / assignments).mean(),
            "MoE/dropped": probe["dropped"].sum().astype(jnp.float32),
            # of the call's layer passes: those that took the short buffer with the compact token side
            "MoE/short_buffer_share": probe["short"].astype(jnp.float32).mean(),
            # the longest list a layer pass of the call would have needed (models/sdar_moe.py compact_slots)
            "MoE/overflow_tokens": probe["overflow"].max().astype(jnp.float32),
            "MoE/router_entropy": probe["entropy"].mean(),
            **{f"MoE/load_l{i}_e{e}": load[i, e] for i in range(load.shape[0]) for e in range(load.shape[1])},
            **{name: v.mean() for name, v in probe["aux_counters"].items()},
        }
        return params, opt_state, metrics, probe

    return guard_update(runtime, update, cfg, n_state=2, donate_argnums=(0, 1))


def _set_lr(opt_state, lr):
    """Override learning_rate inside an InjectHyperparamsState (possibly
    nested in an optax.chain tuple or a bf16-true MasterWeightsState)."""
    from sheeprl_tpu.optim import MasterWeightsState

    if isinstance(opt_state, MasterWeightsState):
        return opt_state._replace(inner=_set_lr(opt_state.inner, lr))
    if hasattr(opt_state, "hyperparams") and "learning_rate" in opt_state.hyperparams:
        hp = dict(opt_state.hyperparams)
        hp["learning_rate"] = jnp.asarray(lr, dtype=jnp.asarray(hp["learning_rate"]).dtype)
        return opt_state._replace(hyperparams=hp)
    if type(opt_state) is tuple:  # optax.chain state (not a NamedTuple state)
        return tuple(_set_lr(s, lr) for s in opt_state)
    return opt_state


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    if "minedojo" in str(cfg.env.wrapper.get("_target_", "")).lower():
        raise ValueError(
            "MineDojo is not currently supported by the PPO agent (no action-mask handling); "
            "use one of the Dreamer agents."
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
    total_envs = cfg.env.num_envs * world_size
    # env backend dispatch (howto/jax-envs.md): host = the gymnasium
    # vector stack (bit-exact pre-backend behavior), jax = device-resident
    # envs + the fused collect path below
    env_backend = resolve_env_backend(cfg)
    envs = make_train_envs(cfg, runtime, log_dir)
    observation_space = envs.single_observation_space

    import gymnasium as gym

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
    lm_kind = language_model_policy(cfg)
    lm_policy = lm_kind is not None
    if lm_policy and (env_backend != "jax" or cfg.algo.run_test):
        raise ValueError(
            f"algo.policy={lm_kind.name} collects through the fused device collector only and has no test "
            "episode: set algo.env_backend=jax, env=jax_tokens and algo.run_test=False"
        )
    module, params = build_agent(
        runtime,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        state["agent"] if state else None,
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

    # the language-model policy acts inside the fused collector only: no host-side player
    player = None if lm_policy else PPOPlayer(module, params, _prep, device=runtime.player_device(params))

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
        cfg.algo.per_rank_batch_size = state["batch_size"] // world_size

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_iter != 0:
        warnings.warn(
            f"metric.log_every ({cfg.metric.log_every}) is not a multiple of "
            f"policy_steps_per_iter ({policy_steps_per_iter}); metrics log at the next multiple."
        )

    ckpt_mgr = CheckpointManager(
        runtime, cfg, log_dir, observability=observability, last_checkpoint=last_checkpoint
    )
    update_fn = make_update_fn(runtime, module, tx, cfg, obs_keys)
    # training health: anomalous updates are skipped inside the jitted
    # step; a tripped skip budget rolls params/optimizer back to the last
    # good checkpoint (howto/resilience.md "Training health & rollback")
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
        # fused collect (envs/jax/collect.py): policy + env + append as
        # one lax.scan per rollout; the payload is born on device
        from sheeprl_tpu.envs.jax.collect import FusedOnPolicyCollector

        collector = (lm_kind.collector_class if lm_policy else FusedOnPolicyCollector)(
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
            with timer("Time/pack"), trace_scope("host_to_device"):
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
            clip_rewards_fn=clip_rewards_fn,
            policy_step=policy_step,
        )
        adopt_params_fn = lambda p: setattr(player, "params", p)

        def _pack(payload):
            # shard the rollout over the mesh's env axis so each device
            # receives only its own columns (the shard_map update consumes
            # exactly this layout; 1-device meshes place trivially); on the
            # overlapped path this runs on the collector thread, so the
            # host->device upload of rollout t+1 overlaps train step t
            local_data = {
                k: v.astype(jnp.float32) if v.dtype not in (jnp.uint8,) else np.array(v)
                for k, v in payload.data.items()
            }
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
    # the language-model policies' counters ride the telemetry record, a section a prefix; how their blocked
    # attention's kernels engaged (tiles, fused or split backward) rides the first record after they were built
    counter_sections = {"MoE/": "moe", "MTP/": "mtp"}
    policy_counters: Dict[str, Dict[str, float]] = {}

    for iter_num, payload in pipeline:
        observability.on_iteration(policy_step)
        payload.apply_events(aggregator, runtime, cfg.metric.log_level)
        policy_step = payload.policy_step_end

        # ------------------------------------------------- device update
        with timer("Time/train_time", SumMetric, sync_on_compute=cfg.metric.sync_on_compute):
            # (the episode update also returns what each minibatch step produced: unused here)
            params, opt_state, train_metrics, *_ = update_fn(
                params,
                opt_state,
                payload.data,
                payload.next_obs,
                runtime.next_key(),
                jnp.float32(current_clip),
                jnp.float32(current_ent),
                jnp.float32(current_lr),
            )
        # the host's wait for the update, under its own name: ``publish`` keeps its barrier and finds the
        # parameters ready.  Where a rollout's events were not fetched (``metric.fetch_every`` > 1) the
        # rollout has not been waited for either, and its wait lands here too
        with timer("Time/update_wait"), trace_scope("block_until_ready"):
            jax.block_until_ready(params)
        with timer("Time/publish"):
            pipeline.publish(iter_num, params)
            rolled = health.tick()
        train_step += world_size
        if rolled is not None:
            params = restore_like(params, rolled["agent"])
            opt_state = restore_like(opt_state, rolled["optimizer"])

        if aggregator and not aggregator.disabled and metric_fetch_gate():
            # materializing metrics blocks on the update; only pay that
            # sync when metrics are on, at the metric.fetch_every cadence
            with timer("Time/loss_fetch"), trace_scope("block_until_ready"):
                fetched_metrics = device_get_metrics(train_metrics)
            for k, v in fetched_metrics.items():
                aggregator.update(k, v)
            for prefix, section in counter_sections.items():
                found = {k[len(prefix):]: float(v) for k, v in fetched_metrics.items() if k.startswith(prefix)}
                if found:
                    policy_counters[section] = found

        # ------------------------------------------------- logging
        if cfg.metric.log_level > 0 and logger:
            # where an interval ends, on_log reads the sums before this span closes and
            # timer.reset() drops the registry it was opened under: that region's time
            # lands in the next interval's record (utils/timer.py)
            with timer("Time/log"):
                logger.log_metrics({"Info/learning_rate": current_lr}, policy_step)
                logger.log_metrics({"Info/clip_coef": current_clip, "Info/ent_coef": current_ent}, policy_step)
                if policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters:
                    sections: Dict[str, Any] = dict(policy_counters)
                    attention_kernels = take_engaged()
                    if attention_kernels:
                        sections["attention"] = attention_kernels
                    observability.on_log(policy_step, train_step, extra=sections or None)
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
            current_lr = polynomial_decay(
                iter_num, initial=lr0, final=0.0, max_decay_steps=total_iters, power=1.0
            )
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
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": ckpt_mgr.last_checkpoint,
            },
        )
        if ckpt_mgr.preempted:
            runtime.print(f"Preemption signal: emergency checkpoint written, stopping at iter {iter_num}")
            break

    pipeline.close()  # before envs.close(): the collector may be mid-step
    if player is not None:
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
