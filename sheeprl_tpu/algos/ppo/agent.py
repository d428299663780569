"""PPO agent (flax) — counterpart of reference sheeprl/algos/ppo/agent.py
(PPOAgent:91, PPOPlayer:242, build_agent:325).

Functional design: one linen module produces (actor_outputs, values); the
reference's agent/player weight-tying trick (ppo/agent.py:362-369) is
trivial here — the player is the same module applied with the same params
pytree under a jitted inference function, so env interaction never pays
mesh collectives and always sees fresh weights."""

from __future__ import annotations

from math import prod
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from sheeprl_tpu.models.models import MLP, CNN, MultiEncoder
from sheeprl_tpu.utils.distribution import Independent, Normal, OneHotCategorical
from sheeprl_tpu.utils.utils import place_player_params

Dtype = Any


class CNNEncoder(nn.Module):
    """NatureCNN-style conv stack over NHWC uint8-normalized images
    (reference ppo/agent.py CNNEncoder: NatureCNN with features_dim)."""

    features_dim: int
    keys: Sequence[str]
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)
        kw = dict(dtype=self.dtype, padding="VALID")
        x = nn.relu(nn.Conv(32, (8, 8), strides=(4, 4), **kw)(x))
        x = nn.relu(nn.Conv(64, (4, 4), strides=(2, 2), **kw)(x))
        x = nn.relu(nn.Conv(64, (3, 3), strides=(1, 1), **kw)(x))
        x = x.reshape(x.shape[:-3] + (-1,))
        x = nn.relu(nn.Dense(self.features_dim, dtype=self.dtype)(x))
        return x


class MLPEncoder(nn.Module):
    features_dim: int
    keys: Sequence[str]
    dense_units: int = 64
    mlp_layers: int = 2
    dense_act: str = "tanh"
    layer_norm: bool = False
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)
        x = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            output_dim=self.features_dim,
            activation=self.dense_act,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )(x)
        return x


class PPOAgentModule(nn.Module):
    """MultiEncoder -> (actor backbone -> per-subaction heads, critic)."""

    actions_dim: Sequence[int]
    is_continuous: bool
    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    encoder_cfg: Dict[str, Any]
    actor_cfg: Dict[str, Any]
    critic_cfg: Dict[str, Any]
    distribution: str = "auto"
    dtype: Dtype = jnp.float32

    def setup(self) -> None:
        enc = self.encoder_cfg
        cnn_encoder = (
            CNNEncoder(features_dim=enc["cnn_features_dim"], keys=tuple(self.cnn_keys), dtype=self.dtype)
            if len(self.cnn_keys) > 0
            else None
        )
        mlp_encoder = (
            MLPEncoder(
                features_dim=enc["mlp_features_dim"],
                keys=tuple(self.mlp_keys),
                dense_units=enc["dense_units"],
                mlp_layers=enc["mlp_layers"],
                dense_act=enc["dense_act"],
                layer_norm=enc["layer_norm"],
                dtype=self.dtype,
            )
            if len(self.mlp_keys) > 0
            else None
        )
        self.feature_extractor = MultiEncoder(
            cnn_encoder=cnn_encoder,
            mlp_encoder=mlp_encoder,
            cnn_keys=tuple(self.cnn_keys),
            mlp_keys=tuple(self.mlp_keys),
        )
        self.critic = MLP(
            hidden_sizes=(self.critic_cfg["dense_units"],) * self.critic_cfg["mlp_layers"],
            output_dim=1,
            activation=self.critic_cfg["dense_act"],
            layer_norm=self.critic_cfg["layer_norm"],
            dtype=self.dtype,
        )
        self.actor_backbone = MLP(
            hidden_sizes=(self.actor_cfg["dense_units"],) * self.actor_cfg["mlp_layers"],
            output_dim=None,
            activation=self.actor_cfg["dense_act"],
            layer_norm=self.actor_cfg["layer_norm"],
            dtype=self.dtype,
        )
        if self.is_continuous:
            self.actor_heads = (nn.Dense(sum(self.actions_dim) * 2, dtype=self.dtype),)
        else:
            self.actor_heads = tuple(nn.Dense(d, dtype=self.dtype) for d in self.actions_dim)

    def __call__(self, obs: Dict[str, jax.Array]) -> Tuple[List[jax.Array], jax.Array]:
        feat = self.feature_extractor(obs)
        values = self.critic(feat)
        a = self.actor_backbone(feat)
        actor_outs = [head(a) for head in self.actor_heads]
        return actor_outs, values


# --------------------------------------------------------------------------- #
# pure fns over (params, obs): policy evaluation / sampling
# --------------------------------------------------------------------------- #
def evaluate_actions(
    module: PPOAgentModule,
    params: Any,
    obs: Dict[str, jax.Array],
    actions: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(new_logprobs, entropy, values) for given flat actions
    (one-hots concatenated for discrete, raw for continuous)."""
    actor_outs, values = module.apply(params, obs)
    if module.is_continuous:
        mean, log_std = jnp.split(actor_outs[0], 2, axis=-1)
        dist = Independent(Normal(mean, jnp.exp(log_std)), 1)
        logprob = dist.log_prob(actions)[..., None]
        entropy = dist.entropy()[..., None]
        return logprob, entropy, values
    import numpy as np

    splits = np.cumsum(module.actions_dim)[:-1].tolist()
    sub_actions = jnp.split(actions, splits, axis=-1)
    logprobs, entropies = [], []
    for logits, act in zip(actor_outs, sub_actions):
        d = OneHotCategorical(logits=logits)
        logprobs.append(d.log_prob(act))
        entropies.append(d.entropy())
    logprob = jnp.stack(logprobs, -1).sum(-1, keepdims=True)
    entropy = jnp.stack(entropies, -1).sum(-1, keepdims=True)
    return logprob, entropy, values


def sample_actions(
    module: PPOAgentModule,
    params: Any,
    obs: Dict[str, jax.Array],
    key: jax.Array,
    greedy: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(flat_actions, real_actions, logprobs, values). ``real_actions`` are
    env-facing (indices for discrete, raw for continuous)."""
    actor_outs, values = module.apply(params, obs)
    if module.is_continuous:
        mean, log_std = jnp.split(actor_outs[0], 2, axis=-1)
        dist = Independent(Normal(mean, jnp.exp(log_std)), 1)
        act = dist.mean if greedy else dist.rsample(key)
        logprob = dist.log_prob(act)[..., None]
        return act, act, logprob, values
    keys = jax.random.split(key, len(actor_outs))
    sub_actions, sub_real, logprobs = [], [], []
    for k, logits in zip(keys, actor_outs):
        d = OneHotCategorical(logits=logits)
        a = d.mode if greedy else d.sample(k)
        sub_actions.append(a)
        sub_real.append(jnp.argmax(a, -1))
        logprobs.append(d.log_prob(a))
    flat = jnp.concatenate(sub_actions, -1)
    real = jnp.stack(sub_real, -1)
    logprob = jnp.stack(logprobs, -1).sum(-1, keepdims=True)
    return flat, real, logprob, values


def get_values(module: PPOAgentModule, params: Any, obs: Dict[str, jax.Array]) -> jax.Array:
    _, values = module.apply(params, obs)
    return values


class PPOPlayer:
    """Host-side convenience wrapper: jitted greedy/sampling policies bound
    to a mutable params reference (reference PPOPlayer:242).

    ``device`` pins the player to a specific device — beside a chip the
    env hot loop runs the (tiny) policy on the host CPU backend so each env
    step avoids a device dispatch and fetch; params sync once per
    rollout (the BASELINE north star's "CPU actors feed TPU learners")."""

    def __init__(self, module: PPOAgentModule, params: Any, prepare_obs_fn, device=None):
        self.module = module
        self.device = device
        self._params = jax.device_put(params, device) if device is not None else params
        self._prepare_obs = prepare_obs_fn
        self._sample = jax.jit(
            lambda p, o, k, greedy: sample_actions(module, p, o, k, greedy), static_argnums=(3,)
        )
        self._values = jax.jit(lambda p, o: get_values(module, p, o))

    @property
    def params(self) -> Any:
        return self._params

    @params.setter
    def params(self, value: Any) -> None:
        self._params = place_player_params(value, self.device)

    def _obs(self, obs: Dict[str, Any]) -> Dict[str, jax.Array]:
        prepared = self._prepare_obs(obs)
        if self.device is not None:
            prepared = jax.device_put(prepared, self.device)
        return prepared

    def get_actions(self, obs: Dict[str, Any], key: jax.Array, greedy: bool = False):
        if self.device is not None:
            key = jax.device_put(key, self.device)
        return self._sample(self._params, self._obs(obs), key, greedy)

    def get_values(self, obs: Dict[str, Any]) -> jax.Array:
        return self._values(self._params, self._obs(obs))


def build_agent(
    runtime,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Dict[str, Any],
    obs_space,
    agent_state: Optional[Any] = None,
) -> Tuple[PPOAgentModule, Any]:
    """Create module + init params (optionally from a checkpoint state).
    An ``algo.policy`` that names a language-model policy (``lm_policy.py``)
    builds that instead."""
    from sheeprl_tpu.algos.ppo.lm_policy import language_model_policy

    lm_kind = language_model_policy(cfg)
    if lm_kind is not None:
        return lm_kind.build(runtime, cfg, agent_state)
    distribution = cfg.distribution.get("type", "auto").lower()
    if distribution not in ("auto", "normal", "tanh_normal", "discrete"):
        raise ValueError(f"Unknown distribution: {distribution}")
    if distribution == "discrete" and is_continuous:
        raise ValueError("Discrete distribution chosen but the action space is continuous")
    if distribution not in ("discrete", "auto") and not is_continuous:
        raise ValueError("Continuous distribution chosen but the action space is discrete")
    module = PPOAgentModule(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        cnn_keys=tuple(cfg.algo.cnn_keys.encoder),
        mlp_keys=tuple(cfg.algo.mlp_keys.encoder),
        encoder_cfg=dict(cfg.algo.encoder),
        actor_cfg=dict(cfg.algo.actor),
        critic_cfg=dict(cfg.algo.critic),
        distribution=distribution,
        dtype=runtime.compute_dtype,
    )
    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
    else:
        dummy_obs = {}
        for k in tuple(cfg.algo.cnn_keys.encoder):
            shape = obs_space[k].shape
            dummy_obs[k] = jnp.zeros((1, *shape), dtype=jnp.float32)
        for k in tuple(cfg.algo.mlp_keys.encoder):
            shape = obs_space[k].shape
            dummy_obs[k] = jnp.zeros((1, *shape), dtype=jnp.float32)
        params = module.init(runtime.next_key(), dummy_obs)
    return module, params
