"""SAC agent (flax) — counterpart of reference sheeprl/algos/sac/agent.py
(SACActor:57, SACCritic:20, SACAgent:145, SACPlayer:270, build_agent:317).

TPU-first design:
- the N critics are ONE module with **stacked (vmapped) params**: a single
  batched MLP evaluation on the MXU instead of a python loop over critic
  modules;
- the target critics are an EMA params pytree updated with
  ``optax.incremental_update`` (reference qfs_target_ema);
- log_alpha is just a scalar leaf in the train state; under the sharded
  batch its gradient mean IS the cross-replica all-reduce the reference
  does explicitly (sac.py:72)."""

from __future__ import annotations

from math import prod
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models.models import MLP
from sheeprl_tpu.utils.utils import place_player_params

LOG_STD_MIN = -5.0
LOG_STD_MAX = 2.0


class SACActor(nn.Module):
    hidden_size: int = 256
    action_dim: int = 1
    action_low: Any = -1.0
    action_high: Any = 1.0

    @nn.compact
    def __call__(self, obs: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """-> (mean, log_std) of the pre-tanh Normal."""
        x = MLP(hidden_sizes=(self.hidden_size, self.hidden_size), activation="relu")(obs)
        mean = nn.Dense(self.action_dim)(x)
        log_std = nn.Dense(self.action_dim)(x)
        return mean, log_std

    @property
    def action_scale(self) -> jax.Array:
        return jnp.asarray((np.asarray(self.action_high) - np.asarray(self.action_low)) / 2.0, jnp.float32)

    @property
    def action_bias(self) -> jax.Array:
        return jnp.asarray((np.asarray(self.action_high) + np.asarray(self.action_low)) / 2.0, jnp.float32)


def actor_action_and_log_prob(
    actor: SACActor, params: Any, obs: jax.Array, key: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """tanh-squashed rsample rescaled to env bounds + its log-prob
    (Eq. 26 of arXiv:1812.05905; reference agent.py:109-143)."""
    mean, log_std = actor.apply(params, obs)
    std = jnp.exp(jnp.clip(log_std, LOG_STD_MIN, LOG_STD_MAX))
    x_t = mean + std * jax.random.normal(key, mean.shape, dtype=mean.dtype)
    y_t = jnp.tanh(x_t)
    scale, bias = actor.action_scale, actor.action_bias
    action = y_t * scale + bias
    log_prob = (
        -((x_t - mean) ** 2) / (2 * std**2) - jnp.log(std) - 0.5 * jnp.log(2 * jnp.pi)
        - jnp.log(scale * (1 - y_t**2) + 1e-6)
    ).sum(-1, keepdims=True)
    return action, log_prob


def actor_greedy_action(actor: SACActor, params: Any, obs: jax.Array) -> jax.Array:
    mean, _ = actor.apply(params, obs)
    return jnp.tanh(mean) * actor.action_scale + actor.action_bias


class SACCritic(nn.Module):
    """Q(s, a) MLP head; params are stacked over the critic ensemble."""

    hidden_size: int = 256
    num_critics: int = 1

    @nn.compact
    def __call__(self, obs: jax.Array, action: jax.Array) -> jax.Array:
        x = jnp.concatenate([obs, action], -1)
        return MLP(
            hidden_sizes=(self.hidden_size, self.hidden_size),
            output_dim=self.num_critics,
            activation="relu",
        )(x)


def critic_ensemble_init(critic: SACCritic, n: int, key: jax.Array, obs: jax.Array, act: jax.Array):
    """Stacked params for n critics: leaves have a leading (n,) axis."""
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: critic.init(k, obs, act))(keys)


def critic_ensemble_apply(critic: SACCritic, stacked_params: Any, obs: jax.Array, act: jax.Array) -> jax.Array:
    """(B, n) q-values — one vmapped evaluation of the whole ensemble."""
    q = jax.vmap(lambda p: critic.apply(p, obs, act))(stacked_params)  # (n, B, 1)
    return jnp.moveaxis(q.squeeze(-1), 0, -1)


class SACTrainState(NamedTuple):
    actor_params: Any
    critic_params: Any  # stacked (n, ...) leaves
    target_critic_params: Any
    log_alpha: jax.Array
    actor_opt: Any
    critic_opt: Any
    alpha_opt: Any


class SACPlayer:
    """Env-interaction policy bound to a (mutable) actor-params reference,
    optionally pinned to the host CPU backend (reference SACPlayer:270)."""

    def __init__(self, actor: SACActor, params: Any, prepare_obs_fn, device=None):
        self.actor = actor
        self.device = device
        self._params = jax.device_put(params, device) if device is not None else params
        self._prepare_obs = prepare_obs_fn
        self._sample = jax.jit(lambda p, o, k: actor_action_and_log_prob(actor, p, o, k)[0])
        self._greedy = jax.jit(lambda p, o: actor_greedy_action(actor, p, o))

    @property
    def params(self) -> Any:
        return self._params

    @params.setter
    def params(self, value: Any) -> None:
        self._params = place_player_params(value, self.device)

    def get_actions(self, obs: Dict[str, Any], key: Optional[jax.Array] = None, greedy: bool = False):
        prepared = self._prepare_obs(obs)
        if self.device is not None:
            prepared = jax.device_put(prepared, self.device)
            if key is not None:
                key = jax.device_put(key, self.device)
        if greedy:
            return self._greedy(self._params, prepared)
        return self._sample(self._params, prepared, key)


def build_agent(
    runtime,
    cfg: Dict[str, Any],
    obs_space,
    action_space,
    agent_state: Optional[Dict[str, Any]] = None,
) -> Tuple[SACActor, SACCritic, Dict[str, Any], float]:
    """-> (actor module, critic module, params dict, target_entropy)."""
    act_dim = int(prod(action_space.shape))
    obs_dim = int(sum(prod(obs_space[k].shape) for k in cfg.algo.mlp_keys.encoder))
    actor = SACActor(
        hidden_size=int(cfg.algo.actor.hidden_size),
        action_dim=act_dim,
        action_low=np.asarray(action_space.low),
        action_high=np.asarray(action_space.high),
    )
    critic = SACCritic(hidden_size=int(cfg.algo.critic.hidden_size), num_critics=1)
    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
    else:
        dummy_obs = jnp.zeros((1, obs_dim), jnp.float32)
        dummy_act = jnp.zeros((1, act_dim), jnp.float32)
        actor_params = actor.init(runtime.next_key(), dummy_obs)
        critic_params = critic_ensemble_init(
            critic, int(cfg.algo.critic.n), runtime.next_key(), dummy_obs, dummy_act
        )
        params = {
            "actor": actor_params,
            "critic": critic_params,
            "target_critic": jax.tree_util.tree_map(jnp.copy, critic_params),
            "log_alpha": jnp.log(jnp.asarray([float(cfg.algo.alpha.alpha)], jnp.float32)),
        }
    target_entropy = -float(act_dim)
    return actor, critic, params, target_entropy
