"""P2E-DV1 agent (flax) — counterpart of reference
sheeprl/algos/p2e_dv1/agent.py (build_agent:26).

Plan2Explore (arXiv:2005.05960) on the DreamerV1 skeleton: the DV1 world
model + TASK actor/critic plus an EXPLORATION actor/critic (single critic,
no target networks — V1 has none) and an ensemble of one-step predictors of
the next *embedded observation* whose disagreement (variance) is the
intrinsic reward (reference p2e_dv1_exploration.py:207-219; unlike DV2/DV3,
whose ensembles predict the next stochastic state).

Param layout::

    params = {
      "world_model",
      "actor_task", "critic_task",
      "actor_exploration", "critic_exploration",
      "ensembles",  # stacked over the ensemble axis (vmap)
    }
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v1.agent import PlayerDV1, build_agent as dv1_build_agent
from sheeprl_tpu.algos.dreamer_v2.agent import Actor, V2MLP, WorldModel

Actor = Actor  # re-export: cfg.algo.actor.cls points here


def embedded_obs_dim(cfg: Dict[str, Any], obs_space) -> int:
    """Output width of the DV1 MultiEncoder (the ensemble's target width).

    Mirrors the size arithmetic in dreamer_v1.agent.build_agent: 4 VALID
    conv stages of kernel 4 stride 2 on a 64x64 input, 8x channels
    multiplier on the last stage, plus ``dense_units`` for the MLP half."""
    world_model_cfg = cfg.algo.world_model
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    cnn_dim = 0
    if len(cnn_keys) > 0:
        size = int(obs_space[cnn_keys[0]].shape[0])
        for _ in range(4):
            size = (size - 4) // 2 + 1
        cnn_dim = size * size * 8 * world_model_cfg.encoder.cnn_channels_multiplier
    mlp_dim = world_model_cfg.encoder.dense_units if len(mlp_keys) > 0 else 0
    return int(cnn_dim + mlp_dim)


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
    actor_exploration_state: Optional[Any] = None,
    critic_exploration_state: Optional[Any] = None,
) -> Tuple[WorldModel, Any, Any, Any, Dict[str, Any]]:
    """-> (world_model, actor(Actor module), critic(V2MLP module),
    ensemble(V2MLP module), params).

    One actor/critic module serves both the task and exploration policies
    (separate param trees), exactly as the reference instantiates two copies
    of the same classes."""
    world_model_cfg = cfg.algo.world_model
    ens_cfg = cfg.algo.ensembles

    stochastic_size = int(world_model_cfg.stochastic_size)
    recurrent_state_size = int(world_model_cfg.recurrent_model.recurrent_state_size)
    latent_state_size = stochastic_size + recurrent_state_size

    world_model, actor, critic, dv1_params = dv1_build_agent(
        runtime,
        actions_dim,
        is_continuous,
        cfg,
        obs_space,
        world_model_state,
        actor_task_state,
        critic_task_state,
    )

    k = runtime.next_key
    dummy_latent = jnp.zeros((1, latent_state_size), jnp.float32)

    actor_exploration_params = (
        jax.tree_util.tree_map(jnp.asarray, actor_exploration_state)
        if actor_exploration_state is not None
        else actor.init({"params": k()}, dummy_latent, False, k())
    )
    critic_exploration_params = (
        jax.tree_util.tree_map(jnp.asarray, critic_exploration_state)
        if critic_exploration_state is not None
        else critic.init(k(), dummy_latent)
    )

    # disagreement ensemble: predicts the next embedded observation from
    # (stochastic, recurrent, action); n members with different seeds,
    # stacked for vmap (reference agent.py:125-143)
    ensemble = V2MLP(
        units=ens_cfg.dense_units,
        layers=ens_cfg.mlp_layers,
        output_dim=embedded_obs_dim(cfg, obs_space),
        act=ens_cfg.get("dense_act", "elu"),
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
        "world_model": dv1_params["world_model"],
        "actor_task": dv1_params["actor"],
        "critic_task": dv1_params["critic"],
        "actor_exploration": actor_exploration_params,
        "critic_exploration": critic_exploration_params,
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
) -> PlayerDV1:
    """PlayerDV1 over the selected policy ('exploration' or 'task'); switch
    policies by re-assigning ``player.params`` + ``player.actor_type``."""
    actor_params = params["actor_exploration"] if actor_type == "exploration" else params["actor_task"]
    player_params = {"world_model": params["world_model"], "actor": actor_params}
    return PlayerDV1(
        world_model,
        actor,
        player_params,
        actions_dim,
        num_envs,
        cfg.algo.world_model.stochastic_size,
        cfg.algo.world_model.recurrent_model.recurrent_state_size,
        expl_amount=float(cfg.algo.actor.get("expl_amount", 0.0)),
        expl_decay=float(cfg.algo.actor.get("expl_decay", 0.0)),
        expl_min=float(cfg.algo.actor.get("expl_min", 0.0)),
        actor_type=actor_type,
        device=runtime.player_device(player_params),
    )
