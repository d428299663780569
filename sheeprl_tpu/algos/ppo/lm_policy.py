"""Which language-model policy ``algo.policy`` names: the one place that knows
the kinds.  A kind is a builder of the policy (what ``build_agent`` returns in
place of the MLP/CNN module) and the fused collector that acts with it; the
update (``ppo.make_episode_update_fn``, minibatches of whole episodes) and the
loop (``ppo.main``) are the same for all of them.
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

    def build(self, runtime, cfg: Dict[str, Any], agent_state: Optional[Any] = None):
        module, function = self.builder.split(":")
        return getattr(importlib.import_module(module), function)(runtime, cfg, agent_state)

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
        LmPolicyKind("mla_moe", "sheeprl_tpu.algos.ppo.causal_lm_policy:build_causal_lm_agent", "FusedCausalCollector"),
    )
}


def language_model_policy(cfg: Dict[str, Any]) -> Optional[LmPolicyKind]:
    """The kind ``algo.policy`` names, or None for the MLP/CNN policy (``mlp``, the default)."""
    return KINDS.get(str(cfg.algo.get("policy", "mlp")))
