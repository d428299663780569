"""VPG agent: one flax module (shared torso, policy + value heads) and a
host-side player for the env hot loop.

The framework's contract (howto/register_new_algorithm.md): "the agent" is
a pair ``(module, params)`` — the module holds architecture, the param
pytree holds the numbers, and nothing is ever mutated in place."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models.models import MLP
from sheeprl_tpu.utils.utils import transfer_tree


class VPGAgentModule(nn.Module):
    n_actions: int
    dense_units: int = 64
    mlp_layers: int = 2

    @nn.compact
    def __call__(self, obs: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """obs (..., D) -> (logits (..., A), value (...,))."""
        h = MLP(hidden_sizes=(self.dense_units,) * self.mlp_layers)(obs)
        logits = nn.Dense(self.n_actions)(h)
        value = nn.Dense(1)(h)[..., 0]
        return logits, value


def prepare_obs(obs: Dict[str, Any], mlp_keys: Sequence[str], num_envs: int) -> jax.Array:
    """Concat the requested vector keys into a flat (num_envs, D) batch."""
    return jnp.concatenate(
        [jnp.asarray(obs[k], jnp.float32).reshape(num_envs, -1) for k in mlp_keys], axis=-1
    )


class VPGPlayer:
    """Env-loop policy wrapper: jitted sample/greedy action selection bound
    to a mutable params reference.  ``device`` comes from
    ``runtime.player_device(params)`` — beside a chip a tiny policy runs on the
    host CPU backend so each env step skips a device dispatch and fetch
    (see howto/scaling.md)."""

    def __init__(self, module: VPGAgentModule, params: Any, mlp_keys: Sequence[str],
                 num_envs: int, device=None):
        self.module = module
        self.mlp_keys = list(mlp_keys)
        self.num_envs = num_envs
        self.device = device
        self._params = jax.device_put(params, device) if device is not None else params

        def _act(p, obs, key, greedy):
            logits, value = module.apply(p, obs)
            actions = jnp.where(
                greedy, jnp.argmax(logits, -1), jax.random.categorical(key, logits)
            )
            logp = jnp.take_along_axis(jax.nn.log_softmax(logits), actions[:, None], 1)[:, 0]
            return actions, logp, value

        self._act = jax.jit(_act)
        self._values = jax.jit(lambda p, obs: module.apply(p, obs)[1])

    @property
    def params(self) -> Any:
        return self._params

    @params.setter
    def params(self, value: Any) -> None:
        # mesh-placed arrays cannot enter another backend's jit directly;
        # transfer_tree batches the whole pytree into ONE cross-backend
        # copy (leaf-by-leaf device_put pays the link latency per leaf —
        # see howto/scaling.md "player placement")
        self._params = transfer_tree(value, self.device)

    def get_actions(self, obs: Dict[str, Any], key: jax.Array, greedy: bool = False):
        prepared = prepare_obs(obs, self.mlp_keys, self.num_envs)
        if self.device is not None:
            prepared = jax.device_put(prepared, self.device)
            key = jax.device_put(key, self.device)
        return self._act(self._params, prepared, key, greedy)

    def get_values(self, obs: Dict[str, Any]) -> jax.Array:
        prepared = prepare_obs(obs, self.mlp_keys, self.num_envs)
        if self.device is not None:
            prepared = jax.device_put(prepared, self.device)
        return self._values(self._params, prepared)


def build_agent(
    runtime,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Dict[str, Any],
    obs_space,
    agent_state: Optional[Any] = None,
) -> Tuple[VPGAgentModule, Any]:
    if is_continuous or len(actions_dim) != 1:
        raise ValueError("vpg is a single-discrete-action-space tutorial algorithm")
    module = VPGAgentModule(
        n_actions=int(actions_dim[0]),
        dense_units=int(cfg.algo.dense_units),
        mlp_layers=int(cfg.algo.mlp_layers),
    )
    obs_dim = sum(int(np.prod(obs_space[k].shape)) for k in cfg.algo.mlp_keys.encoder)
    # init from the SEEDED runtime key (the same contract as the built-ins,
    # ppo/agent.py:280) so different seeds start from different weights; a
    # checkpoint, when given, overwrites the values right after
    params = module.init(runtime.next_key(), jnp.zeros((1, obs_dim)))
    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
    return module, params
