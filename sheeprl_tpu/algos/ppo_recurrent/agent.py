"""Recurrent PPO agent (flax) — counterpart of reference
sheeprl/algos/ppo_recurrent/agent.py (RecurrentModel:19, RecurrentPPOAgent:83,
RecurrentPPOPlayer:265, build_agent:412).

TPU-first deltas vs the reference:

- the LSTM is a ``nn.scan``-lifted cell over the time axis (one fused XLA
  while-loop) instead of cuDNN ``nn.LSTM`` + pack_padded_sequence;
- episode boundaries are handled by *masked in-scan state resets* driven by
  an ``is_first`` flag rather than by dynamically splitting episodes and
  padding (reference ppo_recurrent.py:424-444) — shapes stay static so the
  whole update compiles once.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.ppo.agent import CNNEncoder, MLPEncoder
from sheeprl_tpu.models.models import MLP, MultiEncoder
from sheeprl_tpu.utils.distribution import Independent, Normal, OneHotCategorical
from sheeprl_tpu.utils.utils import place_player_params

Dtype = Any


class _ResetLSTMCell(nn.Module):
    """LSTM cell whose carry is zeroed where ``is_first`` is set, scanned
    over time. Equivalent to the reference's episode splitting: hidden
    state never crosses an episode boundary."""

    hidden_size: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, carry, inp):
        x, is_first = inp
        c, h = carry
        keep = (1.0 - is_first).astype(c.dtype)
        c = c * keep
        h = h * keep
        (c, h), out = nn.OptimizedLSTMCell(self.hidden_size, dtype=self.dtype)((c, h), x)
        return (c, h), out


class RecurrentModel(nn.Module):
    """pre-MLP -> scanned LSTM -> post-MLP (reference RecurrentModel:19)."""

    hidden_size: int
    pre_rnn_mlp: Dict[str, Any]
    post_rnn_mlp: Dict[str, Any]
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(
        self, x: jax.Array, is_first: jax.Array, hx: jax.Array, cx: jax.Array
    ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
        # x: (T, B, D), is_first: (T, B, 1), hx/cx: (B, H)
        if self.pre_rnn_mlp.get("apply", False):
            x = MLP(
                hidden_sizes=(),
                output_dim=self.pre_rnn_mlp["dense_units"],
                activation=self.pre_rnn_mlp.get("activation", "relu"),
                layer_norm=self.pre_rnn_mlp.get("layer_norm", False),
                dtype=self.dtype,
            )(x)
        scan = nn.scan(
            _ResetLSTMCell,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=0,
            out_axes=0,
        )(self.hidden_size, dtype=self.dtype)
        (cx, hx), out = scan((cx, hx), (x, is_first))
        if self.post_rnn_mlp.get("apply", False):
            out = MLP(
                hidden_sizes=(),
                output_dim=self.post_rnn_mlp["dense_units"],
                activation=self.post_rnn_mlp.get("activation", "relu"),
                layer_norm=self.post_rnn_mlp.get("layer_norm", False),
                dtype=self.dtype,
            )(out)
        return out, (hx, cx)


class RecurrentPPOAgentModule(nn.Module):
    """MultiEncoder(obs) ++ prev_actions -> RecurrentModel -> actor heads
    + critic (reference RecurrentPPOAgent:83)."""

    actions_dim: Sequence[int]
    is_continuous: bool
    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    encoder_cfg: Dict[str, Any]
    rnn_cfg: Dict[str, Any]
    actor_cfg: Dict[str, Any]
    critic_cfg: Dict[str, Any]
    dtype: Dtype = jnp.float32

    @property
    def rnn_hidden_size(self) -> int:
        return int(self.rnn_cfg["lstm"]["hidden_size"])

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
        self.rnn = RecurrentModel(
            hidden_size=self.rnn_hidden_size,
            pre_rnn_mlp=dict(self.rnn_cfg["pre_rnn_mlp"]),
            post_rnn_mlp=dict(self.rnn_cfg["post_rnn_mlp"]),
            dtype=self.dtype,
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

    def __call__(
        self,
        obs: Dict[str, jax.Array],
        prev_actions: jax.Array,
        is_first: jax.Array,
        hx: jax.Array,
        cx: jax.Array,
    ) -> Tuple[List[jax.Array], jax.Array, Tuple[jax.Array, jax.Array]]:
        """obs values: (T, B, ...); prev_actions: (T, B, sum(actions_dim));
        is_first: (T, B, 1); hx/cx: (B, H)."""
        feat = self.feature_extractor(obs)
        x = jnp.concatenate([feat, prev_actions.astype(feat.dtype)], axis=-1)
        out, (hx, cx) = self.rnn(x, is_first, hx, cx)
        values = self.critic(out)
        a = self.actor_backbone(out)
        actor_outs = [head(a) for head in self.actor_heads]
        return actor_outs, values, (hx, cx)


# --------------------------------------------------------------------------- #
# pure fns
# --------------------------------------------------------------------------- #
def _dist_stats(module, actor_outs, actions):
    if module.is_continuous:
        mean, log_std = jnp.split(actor_outs[0], 2, axis=-1)
        dist = Independent(Normal(mean, jnp.exp(log_std)), 1)
        logprob = dist.log_prob(actions)[..., None]
        entropy = dist.entropy()[..., None]
        return logprob, entropy
    splits = np.cumsum(module.actions_dim)[:-1].tolist()
    sub_actions = jnp.split(actions, splits, axis=-1)
    logprobs, entropies = [], []
    for logits, act in zip(actor_outs, sub_actions):
        d = OneHotCategorical(logits=logits)
        logprobs.append(d.log_prob(act))
        entropies.append(d.entropy())
    logprob = jnp.stack(logprobs, -1).sum(-1, keepdims=True)
    entropy = jnp.stack(entropies, -1).sum(-1, keepdims=True)
    return logprob, entropy


def evaluate_actions(
    module: RecurrentPPOAgentModule,
    params: Any,
    obs: Dict[str, jax.Array],
    prev_actions: jax.Array,
    is_first: jax.Array,
    hx: jax.Array,
    cx: jax.Array,
    actions: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(new_logprobs, entropy, values) over a (T, B, ...) sequence batch."""
    actor_outs, values, _ = module.apply(params, obs, prev_actions, is_first, hx, cx)
    logprob, entropy = _dist_stats(module, actor_outs, actions)
    return logprob, entropy, values


def sample_actions(
    module: RecurrentPPOAgentModule,
    params: Any,
    obs: Dict[str, jax.Array],
    prev_actions: jax.Array,
    hx: jax.Array,
    cx: jax.Array,
    key: jax.Array,
    greedy: bool = False,
):
    """Single env step (T=1). Returns (flat, real, logprobs, values, (hx, cx))."""
    is_first = jnp.zeros(prev_actions.shape[:-1] + (1,), dtype=jnp.float32)
    actor_outs, values, states = module.apply(params, obs, prev_actions, is_first, hx, cx)
    if module.is_continuous:
        mean, log_std = jnp.split(actor_outs[0], 2, axis=-1)
        dist = Independent(Normal(mean, jnp.exp(log_std)), 1)
        act = dist.mean if greedy else dist.rsample(key)
        logprob = dist.log_prob(act)[..., None]
        return act, act, logprob, values, states
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
    return flat, real, logprob, values, states


def get_values(
    module: RecurrentPPOAgentModule,
    params: Any,
    obs: Dict[str, jax.Array],
    prev_actions: jax.Array,
    hx: jax.Array,
    cx: jax.Array,
) -> jax.Array:
    is_first = jnp.zeros(prev_actions.shape[:-1] + (1,), dtype=jnp.float32)
    _, values, _ = module.apply(params, obs, prev_actions, is_first, hx, cx)
    return values


class RecurrentPPOPlayer:
    """Stateful host-side wrapper carrying (hx, cx, prev_actions) across env
    steps (reference RecurrentPPOPlayer:265). State resets on done are applied
    by the caller via :meth:`reset_states`."""

    def __init__(self, module: RecurrentPPOAgentModule, params: Any, prepare_obs_fn, num_envs: int, device=None):
        self.module = module
        self.device = device
        self.num_envs = num_envs
        self._params = jax.device_put(params, device) if device is not None else params
        self._prepare_obs = prepare_obs_fn
        self._sample = jax.jit(
            lambda p, o, pa, hx, cx, k, greedy: sample_actions(module, p, o, pa, hx, cx, k, greedy),
            static_argnums=(6,),
        )
        self._values = jax.jit(lambda p, o, pa, hx, cx: get_values(module, p, o, pa, hx, cx))
        self.init_states()

    @property
    def params(self) -> Any:
        return self._params

    @params.setter
    def params(self, value: Any) -> None:
        self._params = place_player_params(value, self.device)

    def init_states(self) -> None:
        h = self.module.rnn_hidden_size
        self.hx = jnp.zeros((self.num_envs, h), dtype=jnp.float32)
        self.cx = jnp.zeros((self.num_envs, h), dtype=jnp.float32)
        self.prev_actions = jnp.zeros((1, self.num_envs, sum(self.module.actions_dim)), dtype=jnp.float32)

    def reset_states(self, dones: np.ndarray) -> None:
        """Zero per-env recurrent state + prev_actions where done."""
        keep = jnp.asarray(1.0 - dones.reshape(self.num_envs, 1), dtype=jnp.float32)
        self.hx = self.hx * keep
        self.cx = self.cx * keep
        self.prev_actions = self.prev_actions * keep[None]

    def _obs(self, obs: Dict[str, Any]) -> Dict[str, jax.Array]:
        prepared = self._prepare_obs(obs)
        if self.device is not None:
            prepared = jax.device_put(prepared, self.device)
        return prepared

    def get_actions(self, obs: Dict[str, Any], key: jax.Array, greedy: bool = False):
        if self.device is not None:
            key = jax.device_put(key, self.device)
        flat, real, logprobs, values, (hx, cx) = self._sample(
            self._params, self._obs(obs), self.prev_actions, self.hx, self.cx, key, greedy
        )
        self.hx, self.cx = hx, cx
        self.prev_actions = flat[None] if flat.ndim == 2 else flat
        return flat, real, logprobs, values

    def get_values(self, obs: Dict[str, Any]) -> jax.Array:
        return self._values(self._params, self._obs(obs), self.prev_actions, self.hx, self.cx)


def build_agent(
    runtime,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Dict[str, Any],
    obs_space,
    agent_state: Optional[Any] = None,
) -> Tuple[RecurrentPPOAgentModule, Any]:
    """Create module + init params (reference build_agent:412)."""
    module = RecurrentPPOAgentModule(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        cnn_keys=tuple(cfg.algo.cnn_keys.encoder),
        mlp_keys=tuple(cfg.algo.mlp_keys.encoder),
        encoder_cfg=dict(cfg.algo.encoder),
        rnn_cfg=dict(cfg.algo.rnn),
        actor_cfg=dict(cfg.algo.actor),
        critic_cfg=dict(cfg.algo.critic),
        dtype=runtime.compute_dtype,
    )
    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
    else:
        dummy_obs = {}
        for k in tuple(cfg.algo.cnn_keys.encoder) + tuple(cfg.algo.mlp_keys.encoder):
            shape = obs_space[k].shape
            dummy_obs[k] = jnp.zeros((1, 1, *shape), dtype=jnp.float32)
        hidden = int(cfg.algo.rnn.lstm.hidden_size)
        params = module.init(
            runtime.next_key(),
            dummy_obs,
            jnp.zeros((1, 1, sum(actions_dim)), dtype=jnp.float32),
            jnp.zeros((1, 1, 1), dtype=jnp.float32),
            jnp.zeros((1, hidden), dtype=jnp.float32),
            jnp.zeros((1, hidden), dtype=jnp.float32),
        )
    return module, params
