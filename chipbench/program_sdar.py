"""The benchmark's door into the program for the language-model policy
(``program.py`` is DreamerV3's).  Importing this module imports the program's
model module, so a program without it fails here, at once, with an
``ImportError``, before a driver has built anything.

Everything is reached through what ``ppo.main`` itself calls —
``build_agent``, ``build_ppo_optimizer``, ``make_update_fn`` — and reads only
what the program exposes."""

from __future__ import annotations

from typing import Any, Dict

import sheeprl_tpu.models.sdar_moe as model  # noqa: F401  (the ImportError of a program without the model)
from sheeprl_tpu.models.sdar_moe import reference_params  # noqa: F401  (re-exported for the driver)


class LmUpdate:
    """The PPO update of the language-model policy with its parameters and
    optimizer, built as ``ppo.main`` builds them (same calls, same order),
    without envs or a collector."""

    def __init__(self, cfg):
        import jax

        import sheeprl_tpu.algos.ppo.ppo as ppo
        from sheeprl_tpu.algos.ppo.agent import build_agent
        from sheeprl_tpu.config import instantiate

        self.cfg = cfg
        self.runtime = runtime = instantiate(dict(cfg.fabric))
        runtime.launch()
        self._build_agent = lambda: build_agent(runtime, (), False, cfg, None)
        self.tx = ppo.build_ppo_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm, runtime.precision)
        self.policy, params = self.fresh_params()
        self.n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
        self.update_fn = ppo.make_update_fn(runtime, self.policy, self.tx, cfg, list(cfg.algo.mlp_keys.encoder))
        self._evaluate = jax.jit(lambda p, prompt, actions: self.policy.evaluate_episodes(p, prompt, actions)[:3])
        self._first = params

    def fresh_params(self):
        """(policy, the seed's initial parameters): the same values every call."""
        runtime = self.runtime
        runtime.seed_everything(self.cfg.seed)
        policy, params = self._build_agent()
        return policy, runtime.replicate(runtime.to_param_dtype(params))

    def initial_state(self):
        """(parameters, optimizer state) as ``ppo.main`` starts from them."""
        params = self._first if self._first is not None else self.fresh_params()[1]
        self._first = None
        return params, self.runtime.replicate(self.tx.init(params))

    @property
    def model_cfg(self) -> Dict[str, Any]:
        import dataclasses

        return dataclasses.asdict(self.policy.cfg)

    @property
    def hyper(self) -> Dict[str, Any]:
        a = self.cfg.algo
        return {"clip_coef": float(a.clip_coef), "clip_vloss": bool(a.clip_vloss), "vf_coef": float(a.vf_coef),
                "ent_coef": float(a.ent_coef), "gamma": float(a.gamma), "gae_lambda": float(a.gae_lambda),
                "normalize_advantages": bool(a.normalize_advantages), "max_grad_norm": float(a.max_grad_norm),
                "learning_rate": float(a.optimizer.get("learning_rate", a.optimizer.get("lr"))),
                "adam_eps": float(a.optimizer.eps), "adam_b1": float(a.optimizer.b1), "adam_b2": float(a.optimizer.b2)}

    def old_policy(self, params, prompt, actions):
        """Log-probabilities and values of whole episodes under ``params``,
        without gradients: what collection would have recorded."""
        logp, _, values = self._evaluate(params, prompt, actions)
        return logp, values

    def update(self, params, opt_state, data, key, learning_rate=None):
        """One update call: GAE, then one epoch of minibatch steps
        (``learning_rate``: the configuration's unless given)."""
        import jax.numpy as jnp

        h = self.hyper
        return self.update_fn(
            params, opt_state, data, {}, key, jnp.float32(h["clip_coef"]), jnp.float32(h["ent_coef"]),
            jnp.float32(h["learning_rate"] if learning_rate is None else learning_rate),
        )
