"""The causal language-model policy kinds of PPO: a sparse-expert model that
acts by appending one token per environment step (``envs/jax/tokens.py`` at
block length 1).  Three kinds share this policy and differ in their model
(``lm_policy.py`` names each kind's model class and the ``algo`` section of
its keys): ``algo.policy=mla_moe``, latent attention (``models/mla_moe.py``,
keys under ``algo.mla``), ``algo.policy=afmoe``, sliding-window and global
attention mixed (``models/afmoe.py``, keys under ``algo.afmoe``), and
``algo.policy=qwen3_next``, linear attention with a recurrent state and full
attention mixed (``models/qwen3_next.py``, keys under ``algo.qwen3_next``).

``evaluate_episodes`` scores a whole episode in one causal pass over its
``P + R`` tokens: response token ``i`` was drawn from the distribution at
position ``P + i - 1``, so that pass yields the log-probabilities, entropies
and values of all ``R`` steps.  Latent attention's multi-token-prediction
module rides the same pass and comes back as the policy's auxiliary loss,
which ``ppo.make_episode_update_fn`` adds at ``aux_coef`` (``algo.mtp_coef``).
Collection decodes through the model's own cache (``FusedCausalCollector``).
``howto/language_model_policy.md`` has the layout, the config keys and the cut.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


class CausalLmPolicy:
    """The model (a flax module with ``hidden`` / ``step`` / ``logits`` / ``evaluate``: ``MlaMoE``,
    ``Afmoe``, ``Qwen3Next``), the episode's lengths and the functions PPO calls on them."""

    is_continuous = False

    def __init__(self, model: Any, prompt_len: int, response_len: int, aux_coef: float = 0.0):
        self.model, self.cfg = model, model.cfg
        self.prompt_len, self.response_len = int(prompt_len), int(response_len)
        self.aux_coef = float(aux_coef)
        self.actions_dim = (1, model.cfg.vocab_size)

    @property
    def steps_per_episode(self) -> int:
        return self.response_len

    def init(self, key: jax.Array):
        tokens = jnp.zeros((1, self.prompt_len + self.response_len), jnp.int32)
        return self.model.init(key, tokens, self.prompt_len)

    def evaluate_episodes(self, params: Any, prompt: jax.Array, actions: jax.Array):
        """``prompt`` (B, P) and ``actions`` (B, T, 2) = (0, token) of whole
        episodes -> (log-probabilities, entropies, values), each (B, T), and
        the routed layers' counters stacked over the routed blocks, with the
        MTP module's cross-entropy as ``aux_loss`` and its counters where the
        model has one."""
        tokens = jnp.concatenate([prompt, actions[..., 1]], axis=1).astype(jnp.int32)
        (logp, entropy, values), aux, mtp = self.model.apply(params, tokens, self.prompt_len)
        if mtp is not None:
            aux = {**aux, "aux_loss": mtp["loss"],
                   "aux_counters": {"MTP/loss": mtp["loss"], "MTP/top1_match": mtp["top1_match"]}}
        return logp, entropy, values, aux


def build_causal_lm_agent(runtime, cfg: Dict[str, Any], agent_state: Optional[Any] = None) -> Tuple[CausalLmPolicy, Any]:
    """The policy of the kind ``algo.policy`` names, its model built from the kind's ``algo`` section."""
    from sheeprl_tpu.algos.ppo.lm_policy import language_model_policy

    wrapper = cfg.env.wrapper
    if int(wrapper.block_length) != 1:
        raise ValueError("a causal policy appends one token an env step: set env.wrapper.block_length=1")
    kind = language_model_policy(cfg)
    model_cls, keys = kind.model_class, cfg.algo[kind.section]
    model_cfg = model_cls.config_class.from_mapping({**dict(keys), "vocab_size": int(wrapper.vocab_size)})
    model = model_cls(model_cfg, runtime.compute_dtype, remat=bool(keys.get("remat", True)))
    policy = CausalLmPolicy(model, int(wrapper.prompt_len), int(wrapper.response_len),
                            aux_coef=float(cfg.algo.get("mtp_coef", 0.0)))
    if int(cfg.algo.rollout_steps) != policy.steps_per_episode:
        raise ValueError(
            f"algo.rollout_steps ({cfg.algo.rollout_steps}) must equal env.wrapper.response_len "
            f"({policy.steps_per_episode}): a rollout is one whole episode per env, one env step per token"
        )
    if agent_state is not None:
        return policy, jax.tree_util.tree_map(jnp.asarray, agent_state)
    return policy, jax.jit(policy.init)(runtime.next_key())
