"""P2E-DV3 finetuning phase (reference
sheeprl/algos/p2e_dv3/p2e_dv3_finetuning.py main:33).

Consumes the exploration run's checkpoint
(``checkpoint.exploration_ckpt_path``): restores the world model, both
actors and the task critic, pins all the model-shape hyperparameters to the
exploration config, optionally inherits the exploration replay buffer, then
trains the TASK behavior with the standard DreamerV3 gradient step. The
player collects with the exploration actor until learning starts, then
switches to the task actor (reference p2e_dv3_finetuning.py:350-353)."""

from __future__ import annotations

import pathlib
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import DV3Learner, dv3_optimizers, train_loop
from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
from sheeprl_tpu.algos.p2e_dv3.agent import build_agent
from sheeprl_tpu.config.compose import yaml_load
from sheeprl_tpu.optim import restore_opt_states
from sheeprl_tpu.resilience.sentinel import restore_like
from sheeprl_tpu.utils.callback import load_checkpoint
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.utils import dotdict

# the DV3 update's names for the task behaviour -> the names a P2E checkpoint holds them under
CKPT_NAMES = {
    "world_model": "world_model",
    "actor": "actor_task",
    "critic": "critic_task",
    "target_critic": "target_critic_task",
}


def _load_exploration_cfg(ckpt_path: str) -> dotdict:
    """The exploration run's resolved config lives two levels above the
    checkpoint file (<log_dir>/checkpoint/ckpt_*.ckpt)."""
    p = pathlib.Path(ckpt_path)
    cfg_path = p.parent.parent / "config.yaml"
    if not cfg_path.exists():
        raise RuntimeError(f"Cannot find the exploration config at: {cfg_path}")
    with open(cfg_path) as f:
        return dotdict(yaml_load(f.read()))


class FinetuningLearner(DV3Learner):
    """DreamerV3's learner on the TASK behaviour of a Plan2Explore agent,
    checkpointed under the exploration phase's names.  The player collects
    with ``algo.player.actor_type``'s actor until the first gradient step, then
    with the task actor (reference p2e_dv3_finetuning.py:350-353)."""

    test_name = "few-shot"

    @classmethod
    def from_state(cls, runtime, cfg, state, observation_space, actions_dim, is_continuous):
        world_model, actor, critic, _, _, p2e_params = build_agent(
            runtime,
            actions_dim,
            is_continuous,
            cfg,
            observation_space,
            state["world_model"],
            state.get("ensembles"),
            state["actor_task"],
            state["critic_task"],
            state["target_critic_task"],
            state["actor_exploration"],
            state.get("critics_exploration"),
        )
        p2e_params = runtime.replicate(runtime.to_param_dtype(p2e_params, exclude=("target_critic_task",)))
        # DV3-shaped view for the task training step; the pytrees are shared, not copied
        params = {name: p2e_params[saved] for name, saved in CKPT_NAMES.items()}
        txs = dv3_optimizers(cfg, runtime.precision)
        saved_opt = state.get("opt_states", {})
        opt_states = {
            name: (
                restore_opt_states(saved_opt[CKPT_NAMES[name]], params[name], runtime.precision)
                if CKPT_NAMES[name] in saved_opt
                else runtime.replicate(tx.init(params[name]))
            )
            for name, tx in zip(("world_model", "actor", "critic"), txs)
        }
        moments = (
            jax.tree_util.tree_map(jnp.asarray, state["moments_task"])
            if "moments_task" in state
            else runtime.replicate(init_moments())
        )
        learner = cls(
            runtime, cfg, (world_model, actor, critic), txs, params, opt_states, moments, is_continuous, actions_dim
        )
        learner.actor_exploration = p2e_params["actor_exploration"]
        learner.explores_first = str(cfg.algo.player.actor_type) == "exploration"
        return learner

    def player_params(self, test: bool = False):
        if self.explores_first and self.gradient_steps == 0 and not test:
            return {"world_model": self.params["world_model"], "actor": self.actor_exploration}
        return super().player_params()

    def restore(self, rolled):
        self.params = restore_like(self.params, {name: rolled[saved] for name, saved in CKPT_NAMES.items()})
        self.opt_states = restore_like(
            self.opt_states, {name: rolled["opt_states"][CKPT_NAMES[name]] for name in self.opt_states}
        )
        self.moments = restore_like(self.moments, rolled["moments_task"])

    def checkpoint_state(self):
        return {
            **{saved: self.params[name] for name, saved in CKPT_NAMES.items()},
            "actor_exploration": self.actor_exploration,
            "opt_states": {CKPT_NAMES[name]: opt for name, opt in self.opt_states.items()},
            "moments_task": self.moments,
        }


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    runtime.seed_everything(cfg.seed)

    ckpt_path = cfg.checkpoint.exploration_ckpt_path
    exploration_cfg = _load_exploration_cfg(ckpt_path)
    resume_from_checkpoint = bool(cfg.checkpoint.resume_from)
    state = load_checkpoint(cfg.checkpoint.resume_from if resume_from_checkpoint else ckpt_path)

    # the models must match the exploration phase exactly
    # (reference p2e_dv3_finetuning.py:59-86)
    for key in (
        "gamma", "lmbda", "horizon", "dense_units", "mlp_layers", "dense_act", "cnn_act",
        "unimix", "hafner_initialization", "world_model", "actor", "critic",
        "cnn_keys", "mlp_keys", "cnn_layer_norm", "mlp_layer_norm",
    ):
        if key in exploration_cfg.algo:
            cfg.algo[key] = exploration_cfg.algo[key]
    cfg.env.clip_rewards = exploration_cfg.env.clip_rewards
    load_ring = bool(cfg.buffer.get("load_from_exploration", False))
    if load_ring and exploration_cfg.buffer.checkpoint:
        cfg.env.num_envs = exploration_cfg.env.num_envs

    # a fresh finetuning run starts its counters at zero whatever the
    # exploration checkpoint holds, acts with the player from the first step
    # on, and takes over the exploration ring only if asked to
    train_loop(
        runtime,
        cfg,
        partial(FinetuningLearner.from_state, runtime, cfg, state),
        state if resume_from_checkpoint else None,
        state if (resume_from_checkpoint or load_ring) and "rb" in state else None,
        random_prefill=False,
    )
