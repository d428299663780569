"""sheeprl_tpu.envs.jax — device-resident environments (ROADMAP item 2).

Three tiers, fastest last:

1. :func:`make_gym_env` / :class:`JaxToGymEnv` — the jax env families as
   ordinary host gym envs (``env_backend=host``): wrapper chain, video,
   Sync/Async vector envs all unchanged;
2. :class:`JaxVectorEnv` — all N envs stepped by ONE jitted program per
   ``step`` call behind the gymnasium vector API (``final_obs`` /
   ``final_info`` SAME_STEP semantics preserved);
3. the fused collect path (:mod:`sheeprl_tpu.envs.jax.collect`,
   ``algo.env_backend=jax``) — policy-step + env-step + buffer-append as
   one ``lax.scan`` per rollout, zero host round trips.

``howto/jax-envs.md`` documents the protocol, the auto-reset semantics
and when host envs are still required.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

from sheeprl_tpu.envs.jax.classic import CartPoleJax, PendulumJax
from sheeprl_tpu.envs.jax.core import (
    JaxEnv,
    initial_reset_key,
    step_keys,
    tree_select,
    vector_reset,
    vector_step,
)
from sheeprl_tpu.envs.jax.gridworld import GridWorldJax
from sheeprl_tpu.envs.jax.gym_adapter import JaxToGymEnv, make_gym_env
from sheeprl_tpu.envs.jax.tokens import TokenEnvJax
from sheeprl_tpu.envs.jax.vector import JaxVectorEnv

__all__ = [
    "JAX_ENV_REGISTRY",
    "CartPoleJax",
    "GridWorldJax",
    "JaxEnv",
    "JaxToGymEnv",
    "JaxVectorEnv",
    "PendulumJax",
    "TokenEnvJax",
    "initial_reset_key",
    "is_jax_env_id",
    "make_gym_env",
    "make_jax_env",
    "step_keys",
    "tree_select",
    "vector_reset",
    "vector_step",
]

#: id -> constructor; ids are the ``env.id`` values of the
#: ``configs/env/jax_*.yaml`` group entries
JAX_ENV_REGISTRY: Dict[str, Callable[..., JaxEnv]] = {
    "jax_cartpole": CartPoleJax,
    "jax_pendulum": PendulumJax,
    "jax_gridworld": GridWorldJax,
    "jax_tokens": TokenEnvJax,
}


def is_jax_env_id(env_id: Any) -> bool:
    return str(env_id) in JAX_ENV_REGISTRY


def make_jax_env(id: str, **kwargs: Any) -> JaxEnv:
    """Resolve a registered jax env id to a constructed :class:`JaxEnv`.

    ``kwargs`` pass through to the family constructor (``randomize``,
    ``size``, ``max_episode_steps``, ...), so env configs parameterize
    the families the same way host wrappers take factory kwargs.
    """
    if id not in JAX_ENV_REGISTRY:
        raise ValueError(
            f"Unknown jax env id {id!r}; registered: {', '.join(sorted(JAX_ENV_REGISTRY))}"
        )
    return JAX_ENV_REGISTRY[id](**kwargs)
