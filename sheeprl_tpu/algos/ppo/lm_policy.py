"""Which language-model policy ``algo.policy`` names: the one place that knows
the kinds.  A kind is a builder of the policy (what ``build_agent`` returns in
place of the MLP/CNN module) and the fused collector that acts with it, and for
the causal kinds the model class and the ``algo`` section of its keys; the
update (``ppo.make_episode_update_fn``, minibatches of whole episodes) and the
loop (``ppo.main``) are the same for all of them.  Four kinds:

- ``sdar_moe``: block diffusion (``models/sdar_moe.py``); its cache holds every
  layer's grouped keys and values of the finished blocks;
- ``mla_moe``: causal, latent attention (``models/mla_moe.py``); its cache holds
  every block's latent and shared rotary key, never per-head keys or values;
- ``afmoe``: causal, sliding-window (``sliding_window`` positions) and global
  attention mixed (``models/afmoe.py``); its cache holds every layer's grouped
  keys and values, and a sliding layer reads only the window's slice of it;
- ``qwen3_next``: causal, Gated DeltaNet linear attention and gated full
  attention mixed 3:1 (``models/qwen3_next.py``); its cache holds a linear
  layer's fixed-size recurrent state beside a full layer's keys and values.

``howto/language_model_policy.md`` has the kinds side by side."""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Dict, Optional


@dataclasses.dataclass(frozen=True)
class LmPolicyKind:
    name: str
    builder: str  # "module:function(runtime, cfg, agent_state) -> (policy, params)"
    collector: str  # the class in envs/jax/collect.py that collects with it
    model: str = ""  # "module:Class" of a causal kind's model (its ``config_class`` reads the keys)
    section: str = ""  # the ``algo`` section that holds those keys

    def build(self, runtime, cfg: Dict[str, Any], agent_state: Optional[Any] = None):
        module, function = self.builder.split(":")
        return getattr(importlib.import_module(module), function)(runtime, cfg, agent_state)

    @property
    def model_class(self):
        module, name = self.model.split(":")
        return getattr(importlib.import_module(module), name)

    @property
    def collector_class(self):
        from sheeprl_tpu.envs.jax import collect

        return getattr(collect, self.collector)


KINDS = {
    kind.name: kind
    for kind in (
        # SDAR-MoE: generates by diffusion over blocks, an env step reveals one token of the block in progress
        LmPolicyKind("sdar_moe", "sheeprl_tpu.algos.ppo.sdar_policy:build_sdar_agent", "FusedDiffusionCollector"),
        # causal, latent attention: an env step appends one token, decoded against the latent cache
        LmPolicyKind("mla_moe", "sheeprl_tpu.algos.ppo.causal_lm_policy:build_causal_lm_agent", "FusedCausalCollector",
                     "sheeprl_tpu.models.mla_moe:MlaMoE", "mla"),
        # causal, windowed and global attention mixed: an env step appends one token, decoded against every
        # layer's keys and values (a sliding layer reads the window's slice)
        LmPolicyKind("afmoe", "sheeprl_tpu.algos.ppo.causal_lm_policy:build_causal_lm_agent", "FusedCausalCollector",
                     "sheeprl_tpu.models.afmoe:Afmoe", "afmoe"),
        # causal, linear attention with a recurrent state and full attention mixed: an env step appends one token,
        # decoded against each linear layer's state and each full layer's keys and values
        LmPolicyKind("qwen3_next", "sheeprl_tpu.algos.ppo.causal_lm_policy:build_causal_lm_agent", "FusedCausalCollector",
                     "sheeprl_tpu.models.qwen3_next:Qwen3Next", "qwen3_next"),
    )
}


def language_model_policy(cfg: Dict[str, Any]) -> Optional[LmPolicyKind]:
    """The kind ``algo.policy`` names, or None for the MLP/CNN policy (``mlp``, the default)."""
    return KINDS.get(str(cfg.algo.get("policy", "mlp")))
