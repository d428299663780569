"""The block-diffusion language-model policy kind of PPO (``algo.policy=sdar_moe``;
``lm_policy.py`` holds the kinds): an
SDAR-MoE model (``models/sdar_moe.py``) that acts by denoising one token of
the block in progress per environment step (``envs/jax/tokens.py``).

Where the MLP/CNN policy evaluates a flat batch of independent ``(obs,
action)`` cells, this one evaluates whole episodes: ``evaluate_episodes``
packs each episode's denoising trajectory (``EpisodeLayout``) and one forward
pass under the block-diffusion mask yields the log-probabilities and values of
all its steps.  ``howto/language_model_policy.md`` has the layout, the config
keys and the cut.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.models.sdar_moe import EpisodeLayout, SdarConfig, SdarMoE

class SdarPolicy:
    """The model, the episode's layout and the functions PPO calls on them."""

    is_continuous = False

    def __init__(self, model_cfg: SdarConfig, prompt_len: int, response_len: int, dtype: Any, remat: bool = True):
        self.cfg = model_cfg
        self.model = SdarMoE(model_cfg, dtype, remat=remat)
        self.layout = EpisodeLayout(prompt_len, response_len, model_cfg.block_length, model_cfg.denoise_steps)
        self.prefill_layout = EpisodeLayout(prompt_len, 0, model_cfg.block_length, model_cfg.denoise_steps)
        self.actions_dim = (model_cfg.block_length, model_cfg.vocab_size)

    @property
    def steps_per_episode(self) -> int:
        return self.layout.response_len

    def init(self, key: jax.Array):
        tokens = jnp.zeros((1, self.layout.length), jnp.int32)
        return self.model.init(key, tokens, self.layout)

    def evaluate_episodes(self, params: Any, prompt: jax.Array, actions: jax.Array):
        """``prompt`` (B, P) and ``actions`` (B, T, 2) of whole episodes ->
        (log-probabilities, entropies, values), each (B, T), and the expert
        layer's counters stacked over layers."""
        tokens, at = self.layout.pack(prompt, actions, self.cfg.mask_id)
        hidden, aux = self.model.apply(params, tokens, self.layout, method=SdarMoE.hidden)
        hidden_at = jnp.take_along_axis(hidden, at[..., None], axis=1)
        logp_all, values = self.model.apply(params, hidden_at, method=SdarMoE.score)
        with jax.named_scope("sdar_head"):
            logp = jnp.take_along_axis(logp_all, actions[..., 1:2], axis=-1)[..., 0]
            entropy = -(jnp.exp(logp_all) * logp_all).sum(-1)
        return logp, entropy, values, aux


def build_sdar_agent(runtime, cfg: Dict[str, Any], agent_state: Optional[Any] = None) -> Tuple[SdarPolicy, Any]:
    wrapper = cfg.env.wrapper
    model_cfg = SdarConfig.from_mapping({**dict(cfg.algo.sdar), "vocab_size": int(wrapper.vocab_size),
                                         "mask_id": int(wrapper.mask_id), "block_length": int(wrapper.block_length)})
    policy = SdarPolicy(model_cfg, int(wrapper.prompt_len), int(wrapper.response_len), runtime.compute_dtype,
                        remat=bool(cfg.algo.sdar.get("remat", True)))
    if int(cfg.algo.rollout_steps) != policy.steps_per_episode:
        raise ValueError(
            f"algo.rollout_steps ({cfg.algo.rollout_steps}) must equal env.wrapper.response_len "
            f"({policy.steps_per_episode}): a rollout is one whole episode per env, one env step per denoising step"
        )
    if agent_state is not None:
        return policy, jax.tree_util.tree_map(jnp.asarray, agent_state)
    return policy, jax.jit(policy.init)(runtime.next_key())
