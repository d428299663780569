"""P2E-DV2 agent (flax) — counterpart of reference
sheeprl/algos/p2e_dv2/agent.py (build_agent:26).

Plan2Explore (arXiv:2005.05960) on the DreamerV2 skeleton: the DV2 world
model + TASK actor/critic/target-critic plus an EXPLORATION
actor/critic/target-critic and an ensemble of one-step predictors of the
next *flattened stochastic state* whose disagreement (variance) is the
intrinsic reward (reference p2e_dv2_exploration.py:251-263; unlike DV1,
whose ensemble predicts the next embedded observation).

Param layout::

    params = {
      "world_model",
      "actor_task", "critic_task", "target_critic_task",
      "actor_exploration", "critic_exploration", "target_critic_exploration",
      "ensembles",  # stacked over the ensemble axis (vmap)
    }
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v2.agent import (
    Actor,
    PlayerDV2,
    V2MLP,
    WorldModel,
    build_agent as dv2_build_agent,
)

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
    critic_exploration_state: Optional[Any] = None,
    target_critic_exploration_state: Optional[Any] = None,
) -> Tuple[WorldModel, Any, Any, Any, Dict[str, Any]]:
    """-> (world_model, actor(Actor module), critic(V2MLP module),
    ensemble(V2MLP module), params).

    The DV2 ``build_agent`` provides the world model and the EXPLORATION
    branch (reference agent.py:97-106 wires ``dv2_build_agent`` outputs to
    the exploration policy); the task branch re-initializes fresh copies of
    the same modules."""
    world_model_cfg = cfg.algo.world_model
    ens_cfg = cfg.algo.ensembles

    stochastic_size = int(world_model_cfg.stochastic_size)
    discrete_size = int(world_model_cfg.discrete_size)
    recurrent_state_size = int(world_model_cfg.recurrent_model.recurrent_state_size)
    latent_state_size = stochastic_size * discrete_size + recurrent_state_size

    world_model, actor, critic, dv2_params = dv2_build_agent(
        runtime,
        actions_dim,
        is_continuous,
        cfg,
        obs_space,
        world_model_state,
        actor_exploration_state,
        critic_exploration_state,
        target_critic_exploration_state,
    )

    k = runtime.next_key
    dummy_latent = jnp.zeros((1, latent_state_size), jnp.float32)

    actor_task_params = (
        jax.tree_util.tree_map(jnp.asarray, actor_task_state)
        if actor_task_state is not None
        else actor.init({"params": k()}, dummy_latent, False, k())
    )
    critic_task_params = (
        jax.tree_util.tree_map(jnp.asarray, critic_task_state)
        if critic_task_state is not None
        else critic.init(k(), dummy_latent)
    )
    target_critic_task_params = (
        jax.tree_util.tree_map(jnp.asarray, target_critic_task_state)
        if target_critic_task_state is not None
        else jax.tree_util.tree_map(jnp.copy, critic_task_params)
    )

    # disagreement ensemble: predicts the next flattened stochastic state
    # from (stochastic, recurrent, action); n members with different seeds,
    # stacked for vmap (reference agent.py:154-189)
    ensemble = V2MLP(
        units=ens_cfg.dense_units,
        layers=ens_cfg.mlp_layers,
        output_dim=stochastic_size * discrete_size,
        act=ens_cfg.get("dense_act", "elu"),
        layer_norm=bool(ens_cfg.get("layer_norm", False)),
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
        "world_model": dv2_params["world_model"],
        "actor_task": actor_task_params,
        "critic_task": critic_task_params,
        "target_critic_task": target_critic_task_params,
        "actor_exploration": dv2_params["actor"],
        "critic_exploration": dv2_params["critic"],
        "target_critic_exploration": dv2_params["target_critic"],
        "ensembles": ensembles_params,
    }
    return world_model, actor, critic, ensemble, params


def make_player(
    runtime,
    world_model: WorldModel,
    actor,
    params: Dict[str, Any],
    actions_dim: Sequence[int],
    num_envs: int,
    cfg: Dict[str, Any],
    actor_type: str,
) -> PlayerDV2:
    """PlayerDV2 over the selected policy ('exploration' or 'task'); switch
    policies by re-assigning ``player.params`` + ``player.actor_type``."""
    actor_params = params["actor_exploration"] if actor_type == "exploration" else params["actor_task"]
    player_params = {"world_model": params["world_model"], "actor": actor_params}
    return PlayerDV2(
        world_model,
        actor,
        player_params,
        actions_dim,
        num_envs,
        cfg.algo.world_model.stochastic_size,
        cfg.algo.world_model.recurrent_model.recurrent_state_size,
        discrete_size=cfg.algo.world_model.discrete_size,
        actor_type=actor_type,
        expl_amount=float(cfg.algo.actor.get("expl_amount", 0.0)),
        device=runtime.player_device(player_params),
    )
