"""P2E-DV3 agent (flax) — counterpart of reference
sheeprl/algos/p2e_dv3/agent.py (build_agent:27).

Plan2Explore (arXiv:2005.05960) on the DreamerV3 skeleton: the DV3 world
model + TASK actor/critic plus an EXPLORATION actor, a dict of exploration
critics (each with a weight and a reward type, intrinsic or task), and an
ensemble of next-stochastic-state predictors whose disagreement (variance)
is the intrinsic reward.

Param layout::

    params = {
      "world_model", "actor_task", "critic_task", "target_critic_task",
      "actor_exploration",
      "critics_exploration": {k: {"module", "target_module"}},
      "ensembles",  # stacked over the ensemble axis (vmap)
    }
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v3.agent import (
    Actor,
    DreamerMLP,
    PlayerDV3,
    WorldModel,
    _ln_enabled,
    _ln_eps,
    build_player,
    uniform_out_init,
)
from sheeprl_tpu.algos.dreamer_v3.agent import build_agent as dv3_build_agent

Actor = Actor  # re-export: cfg.algo.actor.cls points here


def build_agent(
    runtime,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Dict[str, Any],
    obs_space,
    world_model_state: Optional[Any] = None,
    ensembles_state: Optional[Any] = None,
    actor_task_state: Optional[Any] = None,
    critic_task_state: Optional[Any] = None,
    target_critic_task_state: Optional[Any] = None,
    actor_exploration_state: Optional[Any] = None,
    critics_exploration_state: Optional[Any] = None,
) -> Tuple[WorldModel, Any, Any, Any, Dict[str, Any], Dict[str, Any]]:
    """-> (world_model, actor(Actor module), critic(DreamerMLP module),
    ensemble(DreamerMLP module), critics_exploration_cfg, params).

    The actor module is shared by the task and exploration policies (two
    param trees); same for all critics."""
    world_model_cfg = cfg.algo.world_model
    critic_cfg = cfg.algo.critic
    ens_cfg = cfg.algo.ensembles

    stochastic_size = world_model_cfg.stochastic_size * world_model_cfg.discrete_size
    latent_state_size = stochastic_size + world_model_cfg.recurrent_model.recurrent_state_size

    world_model, actor, critic, dv3_params = dv3_build_agent(
        runtime,
        actions_dim,
        is_continuous,
        cfg,
        obs_space,
        world_model_state,
        actor_task_state,
        critic_task_state,
        target_critic_task_state,
    )

    k = runtime.next_key
    dummy_latent = jnp.zeros((1, latent_state_size), jnp.float32)

    actor_exploration_params = (
        jax.tree_util.tree_map(jnp.asarray, actor_exploration_state)
        if actor_exploration_state is not None
        else actor.init({"params": k()}, dummy_latent, False, k())
    )

    # exploration critics: only entries with weight > 0 exist (reference
    # agent.py:120-154)
    critics_exploration_cfg: Dict[str, Dict[str, Any]] = {}
    critics_params: Dict[str, Dict[str, Any]] = {}
    intrinsic_critics = 0
    for name, v in cfg.algo.critics_exploration.items():
        if v["weight"] > 0:
            if v["reward_type"] == "intrinsic":
                intrinsic_critics += 1
            elif v["reward_type"] != "task":
                raise ValueError(
                    f"Exploration critic '{name}' has unknown reward_type '{v['reward_type']}'"
                )
            critics_exploration_cfg[name] = {"weight": v["weight"], "reward_type": v["reward_type"]}
            if critics_exploration_state is not None:
                critics_params[name] = jax.tree_util.tree_map(
                    jnp.asarray, critics_exploration_state[name]
                )
            else:
                module_params = critic.init(k(), dummy_latent)
                critics_params[name] = {
                    "module": module_params,
                    "target_module": jax.tree_util.tree_map(jnp.copy, module_params),
                }
    if intrinsic_critics == 0:
        raise RuntimeError("You must specify at least one intrinsic critic (`reward_type='intrinsic'`)")

    # disagreement ensemble: predicts the next stochastic state from
    # (stochastic, recurrent, action); n members with different seeds,
    # stacked for vmap (reference agent.py:176-205)
    ensemble = DreamerMLP(
        units=ens_cfg.dense_units,
        layers=ens_cfg.mlp_layers,
        output_dim=stochastic_size,
        layer_norm=_ln_enabled(ens_cfg.layer_norm),
        eps=_ln_eps(ens_cfg.layer_norm),
        act=ens_cfg.get("dense_act", "silu"),
        out_init=uniform_out_init(1.0),
    )
    ens_input_dim = int(np.sum(actions_dim)) + latent_state_size
    if ensembles_state is not None:
        ensembles_params = jax.tree_util.tree_map(jnp.asarray, ensembles_state)
    else:
        dummy_ens_in = jnp.zeros((1, ens_input_dim), jnp.float32)
        ensembles_params = jax.vmap(lambda kk: ensemble.init(kk, dummy_ens_in))(
            jax.random.split(k(), int(ens_cfg.n))
        )

    params = {
        "world_model": dv3_params["world_model"],
        "actor_task": dv3_params["actor"],
        "critic_task": dv3_params["critic"],
        "target_critic_task": dv3_params["target_critic"],
        "actor_exploration": actor_exploration_params,
        "critics_exploration": critics_params,
        "ensembles": ensembles_params,
    }
    return world_model, actor, critic, ensemble, critics_exploration_cfg, params


def make_player(
    runtime,
    world_model: WorldModel,
    actor,
    params: Dict[str, Any],
    actions_dim: Sequence[int],
    num_envs: int,
    cfg: Dict[str, Any],
    actor_type: str,
) -> PlayerDV3:
    """PlayerDV3 over the selected policy ('exploration' or 'task'); switch
    policies by re-assigning ``player.params`` (reference swaps the actor
    module and re-ties weights, p2e_dv3_finetuning.py:350-353)."""
    actor_params = params["actor_exploration"] if actor_type == "exploration" else params["actor_task"]
    player_params = {"world_model": params["world_model"], "actor": actor_params}
    return build_player(runtime, world_model, actor, player_params, actions_dim, num_envs, cfg, actor_type)
