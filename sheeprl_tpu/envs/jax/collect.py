"""Fused collect: policy-step + env-step + buffer-append as ONE XLA program.

The real prize of device-resident envs (``algo.env_backend=jax``).  The
host collectors (``parallel/pipeline.py``) pay, per env step: a jitted
policy dispatch, an action fetch, a Python vector-env loop, and a numpy
buffer write — then one host->device upload per rollout.  Here the whole
rollout is a single ``lax.scan`` over ``algo.rollout_steps``:

- the policy samples actions from the CURRENT obs (same agent module the
  update trains — no separate player network, no weight transfer);
- ``core.vector_step`` advances all N envs with auto-reset folded in;
- truncation bootstrapping (reward += gamma * V(final_obs), exactly the
  host collectors' fixed-shape substitute-rows scheme) runs on device;
- the per-step records stack into the (T, N, ...) rollout layout the
  update functions already consume — the "buffer append" is the scan's
  output stacking, there is no buffer.

One dispatch per rollout, zero host round trips, one trace (fixed
shapes — the post-warmup compile counter stays flat, asserted in tests
and the bench ladder).

Episode returns/lengths accumulate on device inside the scan; the host
fetches them at the existing ``metric.fetch_every`` cadence (same
SUBSAMPLING semantics as the losses fetch: skipped rollouts' episode
events are dropped, not deferred — ``configs/metric/default.yaml``).

The collectors below expose the exact ``collect(iter_num, inline,
key_fn)`` contract of ``OnPolicyCollector`` / ``RecurrentCollector``, so
the loops drive them through the same ``PipelinedCollector`` scaffold
(always on its serial path: ``resolve_overlap_setting`` forces the
overlap OFF for this backend — there is no host work left to overlap).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.envs.jax.core import tree_select, vector_reset, vector_step
from sheeprl_tpu.envs.jax.vector import JaxVectorEnv
from sheeprl_tpu.parallel.pipeline import RolloutPayload
from sheeprl_tpu.utils.utils import MetricFetchGate

__all__ = ["FusedCausalCollector", "FusedDiffusionCollector", "FusedOnPolicyCollector", "FusedRecurrentCollector"]


class _FusedCollectorBase:
    """Shared scaffolding: params adoption, episode-event fetch cadence,
    policy-step accounting, telemetry counters."""

    def __init__(
        self,
        *,
        envs: JaxVectorEnv,
        module: Any,
        params: Any,
        cfg: Any,
        runtime: Any,
        obs_keys: Sequence[str],
        total_envs: int,
        world_size: int,
        aggregator: Any = None,
        policy_step: int = 0,
    ):
        self.envs = envs
        self.jax_env = envs.env
        self.module = module
        self.params = params
        self.cfg = cfg
        self.runtime = runtime
        self.obs_keys = list(obs_keys)
        self.total_envs = int(total_envs)
        self.world_size = int(world_size)
        self.aggregator = aggregator
        self.policy_step = int(policy_step)
        self.max_episode_steps = envs._max_steps
        self.rollout_steps = int(cfg.algo.rollout_steps)
        # device env state: seeded from the run seed, SAME key discipline
        # as JaxVectorEnv/JaxToGymEnv (core.py module docstring)
        self._env_base = jax.random.PRNGKey(int(cfg.seed))
        self._jinit = jax.jit(lambda base: self._initial_carry(base))
        # commit the initial carry to the mesh-replicated layout: rollout
        # outputs inherit the params' NamedSharding, so an uncommitted
        # first carry would make collect #2 a different arg-sharding
        # signature — one extra compile, breaking the flat-counter contract
        self._carry = jax.device_put(self._jinit(self._env_base), runtime.replicated)

        # a name a trace can be filtered by (``jit_collect_rollout``), whichever collector runs
        def collect_rollout(params, carry, key, env_base):
            return self._rollout_fn(params, carry, key, env_base)

        self._rollout = jax.jit(collect_rollout)
        # device->host episode-event fetch cadence (metric.fetch_every)
        self._event_gate = MetricFetchGate(cfg.metric.get("fetch_every", 1))
        self._log_events = int(cfg.metric.get("log_level", 1)) > 0
        # telemetry counters (obs/__init__.py "jaxenv" record section)
        self._n_rollouts = 0
        self._n_episodes = 0
        self._n_event_fetches = 0
        # update calls between the weights a rollout acts with and the newest (``params_age``): the loop makes
        # one update call an iteration and hands the result over through ``adopt``
        self._first_iter = None
        self._n_adopts = 0
        self._params_age = 0

    # subclasses implement
    def _initial_carry(self, base):
        raise NotImplementedError

    def _rollout_fn(self, params, carry, key, env_base):
        raise NotImplementedError

    def adopt(self, params: Any) -> None:
        """Params handoff target for ``PipelinedCollector``'s adopt hook —
        the fused program acts on whatever was last adopted (serial path:
        exactly the previous iteration's update, the host loops' order)."""
        self.params = params
        self._n_adopts += 1

    def _dispatch_rollout(self, iter_num: int, key_fn):
        """Dispatches one rollout under ``Time/env_interaction_time`` (the span
        holds the dispatch alone: the device works on after it) and counts it;
        returns what ``_rollout_fn`` returns."""
        from sheeprl_tpu.utils.metric import SumMetric
        from sheeprl_tpu.utils.timer import timer

        if self._first_iter is None:
            self._first_iter = iter_num
        self._params_age = max(0, iter_num - self._first_iter - self._n_adopts)
        with timer("Time/env_interaction_time", SumMetric, sync_on_compute=False):
            out = self._rollout(self.params, self._carry, key_fn(), self._env_base)
        self._n_rollouts += 1
        self.policy_step += self.rollout_steps * self.total_envs
        return out

    def _apply_events(self, events: Dict[str, Any], step_start: int) -> None:
        """Fetch + emit on-device episode events at the fetch cadence.  The
        fetch is where the host first waits for the rollout
        (``Time/collect_wait``; where the event gate is closed nothing is
        fetched here and the loop waits later, in ``ppo.main``'s
        ``Time/update_wait``); the host loop over the episodes that ended is
        ``Time/collect_events``."""
        if not self._log_events or self.aggregator is None:
            return
        if not self._event_gate():
            return
        from sheeprl_tpu.utils.timer import timer

        self._n_event_fetches += 1
        with timer("Time/collect_wait"):
            done = np.asarray(events["done"])  # (T, N)
            if not done.any():
                return
            ep_ret = np.asarray(events["ep_return"])
            ep_len = np.asarray(events["ep_length"])
        per_step = self.total_envs  # policy steps per scan step (global)
        with timer("Time/collect_events"):
            self._count_fetched(events)
            for t, i in zip(*np.nonzero(done)):
                self._n_episodes += 1
                ep_rew = float(ep_ret[t, i])
                if self.aggregator and "Rewards/rew_avg" in self.aggregator:
                    self.aggregator.update("Rewards/rew_avg", ep_rew)
                if self.aggregator and "Game/ep_len_avg" in self.aggregator:
                    self.aggregator.update("Game/ep_len_avg", float(ep_len[t, i]))
                self.runtime.print(
                    f"Rank-0: policy_step={step_start + (int(t) + 1) * per_step}, "
                    f"reward_env_{int(i)}={ep_rew}"
                )

    def _count_fetched(self, events: Dict[str, Any]) -> None:
        """What a collector counts on the device rides ``events`` and is fetched
        here, after the wait for the rollout has ended (nothing by default)."""

    def stats(self) -> Dict[str, Any]:
        """Telemetry provider (``jaxenv`` key in telemetry.jsonl); the counts
        are cumulative over the run."""
        return {
            "backend": "jax",
            "fused": True,
            "env": type(self.jax_env).__name__,
            "num_envs": self.total_envs,
            "rollout_steps": self.rollout_steps,
            "rollouts": self._n_rollouts,
            "env_steps": self._n_rollouts * self.rollout_steps * self.total_envs,
            "episodes_reported": self._n_episodes,
            "event_fetches": self._n_event_fetches,
            "params_age": self._params_age,
        }


class FusedOnPolicyCollector(_FusedCollectorBase):
    """Fused drop-in for the PPO/A2C ``OnPolicyCollector.collect``."""

    def _initial_carry(self, base):
        return vector_reset(self.jax_env, base, self.total_envs)

    def _rollout_fn(self, params, carry, key, env_base):
        from sheeprl_tpu.algos.ppo.agent import get_values, sample_actions
        from sheeprl_tpu.algos.ppo.utils import normalize_obs

        cfg = self.cfg
        env = self.jax_env
        obs_keys = tuple(self.obs_keys)
        cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
        gamma = float(cfg.algo.gamma)
        clip_rewards = bool(cfg.env.clip_rewards)
        max_steps = self.max_episode_steps
        discrete = not self.module.is_continuous

        def norm(obs):
            return normalize_obs({k: obs[k].astype(jnp.float32) for k in obs_keys}, cnn_keys, obs_keys)

        def step_fn(vstate, k_pol):
            obs = vstate["obs"]
            flat, real, logprobs, values = sample_actions(self.module, params, norm(obs), k_pol)
            act = real[..., 0] if discrete else flat
            new_vstate, out = vector_step(env, vstate, act, env_base, max_steps)
            rewards = out["reward"][:, None]
            if max_steps:
                # truncation bootstrap — the host collectors' fixed-shape
                # scheme: value the full env batch with terminal rows
                # substituted, add gamma * V only on truncated rows.  The
                # critic forward rides a lax.cond so the (common) steps
                # with no truncation skip it at runtime — the host path
                # likewise only values on actual truncations
                def _bootstrap():
                    real_next = tree_select(out["truncated"], out["final_obs"], out["obs"])
                    return get_values(self.module, params, norm(real_next))

                vals = jax.lax.cond(
                    out["truncated"].any(),
                    _bootstrap,
                    lambda: jnp.zeros((out["reward"].shape[0], 1), jnp.float32),
                )
                rewards = rewards + gamma * vals * out["truncated"][:, None].astype(jnp.float32)
            if clip_rewards:
                rewards = jnp.tanh(rewards)
            rec = {k: obs[k].astype(jnp.float32) for k in obs_keys}
            rec.update(
                dones=out["done"][:, None].astype(jnp.float32),
                values=values.astype(jnp.float32),
                actions=flat.astype(jnp.float32),
                logprobs=logprobs.astype(jnp.float32),
                rewards=rewards.astype(jnp.float32),
            )
            ev = {"done": out["done"], "ep_return": out["ep_return"], "ep_length": out["ep_length"]}
            return new_vstate, (rec, ev)

        keys = jax.random.split(jnp.asarray(key), self.rollout_steps)
        carry, (data, events) = jax.lax.scan(step_fn, carry, keys)
        return carry, data, events

    def collect(self, iter_num: int, inline: bool, key_fn) -> RolloutPayload:
        payload = RolloutPayload(iter_num)
        step_start = self.policy_step
        self._carry, data, events = self._dispatch_rollout(iter_num, key_fn)
        self._apply_events(events, step_start)
        payload.data = data
        payload.next_obs = {k: self._carry["obs"][k] for k in self.obs_keys}
        payload.policy_step_end = self.policy_step
        return payload


class FusedRecurrentCollector(_FusedCollectorBase):
    """Fused drop-in for ``RecurrentCollector.collect`` (recurrent PPO):
    the scan carry additionally threads (hx, cx, prev_actions), captures
    the PRE-action recurrent state per step (what the update conditions
    on) and zeroes it on done (``algo.reset_recurrent_state_on_done``),
    and the payload carries the bootstrap ``next_values`` extra."""

    def _initial_carry(self, base):
        h = self.module.rnn_hidden_size
        a = sum(self.module.actions_dim)
        return {
            "vstate": vector_reset(self.jax_env, base, self.total_envs),
            "hx": jnp.zeros((self.total_envs, h), jnp.float32),
            "cx": jnp.zeros((self.total_envs, h), jnp.float32),
            "prev_actions": jnp.zeros((1, self.total_envs, a), jnp.float32),
        }

    def _rollout_fn(self, params, carry, key, env_base):
        from sheeprl_tpu.algos.ppo.utils import normalize_obs
        from sheeprl_tpu.algos.ppo_recurrent.agent import get_values, sample_actions

        cfg = self.cfg
        env = self.jax_env
        obs_keys = tuple(self.obs_keys)
        cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
        gamma = float(cfg.algo.gamma)
        clip_rewards = bool(cfg.env.clip_rewards)
        reset_on_done = bool(cfg.algo.reset_recurrent_state_on_done)
        max_steps = self.max_episode_steps
        discrete = not self.module.is_continuous
        n = self.total_envs

        def norm(obs):
            # (T=1, B, ...) layout — what the recurrent module consumes
            # (host parity: ppo_recurrent.utils.prepare_obs)
            return normalize_obs(
                {k: obs[k][None].astype(jnp.float32) for k in obs_keys}, cnn_keys, obs_keys
            )

        def step_fn(c, k_pol):
            vstate = c["vstate"]
            obs = vstate["obs"]
            prev_hx, prev_cx, prev_actions = c["hx"], c["cx"], c["prev_actions"]
            flat, real, logprobs, values, (hx, cx) = sample_actions(
                self.module, params, norm(obs), prev_actions, prev_hx, prev_cx, k_pol
            )
            act = real.reshape(n, -1)[..., 0] if discrete else flat.reshape(n, -1)
            new_vstate, out = vector_step(env, vstate, act, env_base, max_steps)
            rewards = out["reward"][:, None]
            if max_steps:
                # host parity: the bootstrap values use the POST-action
                # recurrent state and the just-taken actions; the forward
                # rides a lax.cond — no-truncation steps skip it at runtime
                def _bootstrap():
                    real_next = tree_select(out["truncated"], out["final_obs"], out["obs"])
                    return get_values(self.module, params, norm(real_next), flat, hx, cx).reshape(n, -1)[
                        :, :1
                    ]

                vals = jax.lax.cond(
                    out["truncated"].any(),
                    _bootstrap,
                    lambda: jnp.zeros((n, 1), jnp.float32),
                )
                rewards = rewards + gamma * vals * out["truncated"][:, None].astype(jnp.float32)
            if clip_rewards:
                rewards = jnp.tanh(rewards)
            new_prev_actions = flat if flat.ndim == 3 else flat[None]
            if reset_on_done:
                keep = (1.0 - out["done"].astype(jnp.float32))[:, None]
                hx = hx * keep
                cx = cx * keep
                new_prev_actions = new_prev_actions * keep[None]
            rec = {k: obs[k].astype(jnp.float32) for k in obs_keys}
            rec.update(
                dones=out["done"][:, None].astype(jnp.float32),
                values=values.reshape(n, -1).astype(jnp.float32),
                actions=flat.reshape(n, -1).astype(jnp.float32),
                logprobs=logprobs.reshape(n, -1).astype(jnp.float32),
                rewards=rewards.astype(jnp.float32),
                prev_hx=prev_hx.astype(jnp.float32),
                prev_cx=prev_cx.astype(jnp.float32),
                prev_actions=prev_actions.reshape(n, -1).astype(jnp.float32),
            )
            ev = {"done": out["done"], "ep_return": out["ep_return"], "ep_length": out["ep_length"]}
            new_c = {"vstate": new_vstate, "hx": hx, "cx": cx, "prev_actions": new_prev_actions}
            return new_c, (rec, ev)

        keys = jax.random.split(jnp.asarray(key), self.rollout_steps)
        carry, (data, events) = jax.lax.scan(step_fn, carry, keys)
        next_values = get_values(
            self.module,
            params,
            norm(carry["vstate"]["obs"]),
            carry["prev_actions"],
            carry["hx"],
            carry["cx"],
        ).reshape(n, -1)
        return carry, data, events, next_values

    def collect(self, iter_num: int, inline: bool, key_fn) -> RolloutPayload:
        payload = RolloutPayload(iter_num)
        step_start = self.policy_step
        self._carry, data, events, next_values = self._dispatch_rollout(iter_num, key_fn)
        self._apply_events(events, step_start)
        payload.data = data
        payload.next_obs = {k: self._carry["vstate"]["obs"][k] for k in self.obs_keys}
        payload.extras["next_values"] = next_values
        payload.policy_step_end = self.policy_step
        return payload


def _reached(load: jax.Array) -> jax.Array:
    """The distinct held experts one cached pass's rows reached, summed over its routed layers, from
    their ``load`` (layers, held experts): int32."""
    return (load > 0).sum().astype(jnp.int32)


class _FusedEpisodeCollector(_FusedCollectorBase):
    """What the language-model policies' collectors share (``algos/ppo/lm_policy.py``
    names them): one rollout is one whole episode per env over
    ``envs/jax/tokens.py``, the carry between rollouts is the env state alone,
    and ``_rollout_fn`` returns ``(vstate, data, events)`` with the prompt
    among the data.

    The rollout program names its phases for a trace (``jax.named_scope``,
    metadata only; the model's own scopes lie beneath them): ``collect_prefill``
    (the prompt's pass and the cache's first fill), the cached passes
    (``collect_denoise`` and ``collect_commit``, or ``collect_decode``),
    ``collect_score`` (head and value), ``collect_sample`` (the position's and
    the token's choice, the recorded log-probability and value) and
    ``collect_env`` (``vector_step``).  ``stats()`` counts what the rollouts
    did, exactly and from shapes alone: forward ``passes`` of the model and the
    ``positions`` those ran over (what a trace's time per pass is taken over).

    What shapes cannot say is counted on the device, from the routed layers'
    own ``load`` (rows per held expert, which the dispatch needs anyway), over
    every cached pass (not the prefill) and every routed layer of the trunk:
    ``experts_reached``, the distinct held experts a pass's rows reached, i.e.
    the expert weights the pass had to read.  A rollout returns the sum beside
    its episode events as ``events["reach"]``; it is fetched with the events
    and dropped with them (``metric.fetch_every``): the total is over the
    ``event_fetches`` rollouts whose events were fetched."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self._experts_reached = 0

    def _initial_carry(self, base):
        return vector_reset(self.jax_env, base, self.total_envs)

    def _rollout_work(self) -> Dict[str, int]:
        """``passes`` and ``positions`` of ONE rollout."""
        raise NotImplementedError

    def _count_fetched(self, events: Dict[str, Any]) -> None:
        self._experts_reached += int(np.asarray(events["reach"]))

    def stats(self) -> Dict[str, Any]:
        return {**super().stats(), **{k: v * self._n_rollouts for k, v in self._rollout_work().items()},
                "experts_reached": self._experts_reached}

    def collect(self, iter_num: int, inline: bool, key_fn) -> RolloutPayload:
        payload = RolloutPayload(iter_num)
        step_start = self.policy_step
        self._carry, data, events = self._dispatch_rollout(iter_num, key_fn)
        self._apply_events(events, step_start)
        payload.data = data
        payload.next_obs = {k: self._carry["obs"][k] for k in self.obs_keys}
        payload.policy_step_end = self.policy_step
        return payload


class FusedCausalCollector(_FusedEpisodeCollector):
    """Fused collection for the causal language-model policies
    (``algos/ppo/causal_lm_policy.py``: the kinds ``mla_moe``, ``afmoe`` and
    ``qwen3_next``): one env step appends one token.  It calls the policy's own
    model class (``hidden`` with the prefill's cache entries, ``prefill_cache``,
    ``step``, ``logits``).

    One pass over the prompt under the model's masks (the full-sequence form of
    the attention) fills the cache; then the scan runs over response tokens,
    and its carry holds the env state, the cache and the hidden state at the
    newest position.  The cache is the model's: for latent attention
    (``MlaMoE``) per block the latent ``ckv`` and the shared rotary key of every
    position so far (576 numbers a position and block at the published sizes,
    never per-head keys or values); for ``Afmoe`` per layer the grouped keys and
    values (4 heads x 128 each, a position), of which a sliding layer's pass
    reads the fixed-width slice of the last ``sliding_window`` positions; for
    ``Qwen3Next`` two kinds side by side: a linear-attention layer's recurrent
    state and its convolution's last inputs, of fixed size, and a full layer's
    grouped keys and values.  The model class lays the cache out from the
    prefill's entries (``prefill_cache``: the entries a position each are padded
    to the episode's length, fixed-size ones pass as they are).  A step
    samples its token at temperature 1 from the full vocabulary slice, so that
    the recorded log-probability is exactly the model's, steps the env, and
    makes ONE cached pass over the token it has just appended.  Latent
    attention's multi-token-prediction module is not used to draft."""

    def _rollout_work(self) -> Dict[str, int]:
        n_env, p_len, r_len = self.total_envs, self.jax_env.prompt_len, self.jax_env.response_len
        return {"passes": 1 + r_len, "positions": n_env * (p_len + r_len)}

    def _rollout_fn(self, params, vstate, key, env_base):
        policy, env = self.module, self.jax_env
        model = policy.model
        model_cls = type(model)
        p_len, r_len = env.prompt_len, env.response_len

        with jax.named_scope("collect_prefill"):
            prompt = vstate["obs"]["tokens"][:, :p_len]
            u, _, entries = model.apply(params, prompt, True, method=model_cls.hidden)
            cache = model_cls.prefill_cache(entries, p_len + r_len)

        def step_fn(carry, xs):
            vstate, cache, last, reach = carry
            t, step_key = xs
            with jax.named_scope("collect_score"):
                logp_all, values = model.apply(params, last, method=model_cls.logits)
            with jax.named_scope("collect_sample"):
                x = jax.random.categorical(step_key, logp_all, axis=-1)
                action = jnp.stack([jnp.zeros_like(x), x], -1).astype(jnp.int32)
                logprob = jnp.take_along_axis(logp_all, x[:, None], axis=-1)
            with jax.named_scope("collect_env"):
                vstate, out = vector_step(env, vstate, action, env_base, None)
            # the appended token's pass: its cache entries join the cache, its hidden state scores the next step
            # (after the last token the env has reset, and what is written is never read)
            with jax.named_scope("collect_decode"):
                u, aux, cache = model.apply(params, x[:, None].astype(jnp.int32), cache, p_len + t, method=model_cls.step)
            rec = {
                "actions": action,
                "logprobs": logprob,
                "values": values[:, None],
                "rewards": out["reward"][:, None],
                "dones": out["done"][:, None].astype(jnp.float32),
                "ev": {"done": out["done"], "ep_return": out["ep_return"], "ep_length": out["ep_length"]},
            }
            return (vstate, cache, u[:, 0], reach + _reached(aux["load"])), rec

        keys = jax.random.split(jnp.asarray(key), r_len)
        carry = (vstate, cache, u[:, -1], jnp.int32(0))
        (vstate, _, _, reach), recs = jax.lax.scan(step_fn, carry, (jnp.arange(r_len), keys))
        events = {**recs.pop("ev"), "reach": reach}
        recs["prompt"] = prompt[None]
        return vstate, recs, events


class FusedDiffusionCollector(_FusedEpisodeCollector):
    """Fused collection for the block-diffusion language-model policy
    (``algos/ppo/sdar_policy.py``): one env step per denoising step.

    The scan runs over response blocks, and its carry holds the env state (the
    block in progress lives in its tokens) and, per layer, the keys and values
    of the clean positions so far.  A block costs five forward passes for its
    four tokens: four denoising passes over the block as it stands, each
    followed by an env step that writes the revealed token, then one pass over
    the finished block that appends its keys and values to the cache.

    Sampler: reveal the masked position whose top-1 probability is highest (a
    function of the state alone), and draw its token from the categorical at
    temperature 1, so that a step's recorded log-probability is exactly the
    model's (SDAR's own low-confidence sampler ranks positions by the *sampled*
    token's probability, which a policy-gradient ratio cannot reproduce)."""

    def _rollout_work(self) -> Dict[str, int]:
        n_env, p_len, r_len = self.total_envs, self.jax_env.prompt_len, self.jax_env.response_len
        block, steps = self.module.cfg.block_length, self.module.cfg.denoise_steps
        passes = self.module.layout.n_blocks * (steps + 1)  # a block's denoising passes and the one that commits it
        return {"passes": 1 + passes, "positions": n_env * (p_len + passes * block)}

    def _rollout_fn(self, params, vstate, key, env_base):
        from sheeprl_tpu.models.sdar_moe import SdarMoE

        policy, env = self.module, self.jax_env
        model, mcfg = policy.model, policy.cfg
        n_env, p_len = self.total_envs, env.prompt_len
        block, steps, n_blocks = mcfg.block_length, mcfg.denoise_steps, policy.layout.n_blocks
        s_max = policy.layout.n_clean

        # the prompt's keys and values: one clean pass under the block-causal mask
        with jax.named_scope("collect_prefill"):
            prompt = vstate["obs"]["tokens"][:, :p_len]
            _, _, kvs = model.apply(params, prompt, policy.prefill_layout, True, method=SdarMoE.hidden)
            cache = [
                tuple(jnp.zeros((n_env, s_max) + x.shape[2:], x.dtype).at[:, :p_len].set(x) for x in kv) for kv in kvs
            ]

        def current(vstate, length):
            return jax.lax.dynamic_slice_in_dim(vstate["obs"]["tokens"], length, block, axis=1)

        def block_fn(carry, xs):
            vstate, cache, reach = carry
            b, keys = xs
            length = p_len + b * block
            pos = length + jnp.arange(block)
            recs = []
            for j in range(steps):
                with jax.named_scope("collect_denoise"):
                    tokens = current(vstate, length)
                    hidden, aux, _ = model.apply(params, tokens, pos, cache, length, method=SdarMoE.block)
                    reach = reach + _reached(aux["load"])
                with jax.named_scope("collect_score"):
                    logp_all, values = model.apply(params, hidden, method=SdarMoE.score)
                with jax.named_scope("collect_sample"):
                    masked = tokens == mcfg.mask_id
                    u = jnp.argmax(jnp.where(masked, logp_all.max(-1), -jnp.inf), axis=-1)
                    logp_u = jnp.take_along_axis(logp_all, u[:, None, None], axis=1)[:, 0]
                    x = jax.random.categorical(keys[j], logp_u, axis=-1)
                    action = jnp.stack([u, x], -1).astype(jnp.int32)
                    logprob = jnp.take_along_axis(logp_u, x[:, None], axis=-1)
                    value = jnp.take_along_axis(values, u[:, None], axis=1)
                with jax.named_scope("collect_env"):
                    vstate, out = vector_step(env, vstate, action, env_base, None)
                recs.append({
                    "actions": action,
                    "logprobs": logprob,
                    "values": value,
                    "rewards": out["reward"][:, None],
                    "dones": out["done"][:, None].astype(jnp.float32),
                    "ev": {"done": out["done"], "ep_return": out["ep_return"], "ep_length": out["ep_length"]},
                })
            # the finished block's keys and values join the cache (after the last
            # block the env has reset, and what is written is never read)
            with jax.named_scope("collect_commit"):
                aux, kvs = model.apply(params, current(vstate, length), pos, cache, length, method=SdarMoE.commit)
                reach = reach + _reached(aux["load"])
                cache = [
                    tuple(jax.lax.dynamic_update_slice_in_dim(c, x, length, axis=1) for c, x in zip(kv_cache, kv))
                    for kv_cache, kv in zip(cache, kvs)
                ]
            return (vstate, cache, reach), jax.tree_util.tree_map(lambda *x: jnp.stack(x), *recs)

        keys = jax.random.split(jnp.asarray(key), n_blocks * steps).reshape(n_blocks, steps, -1)
        carry = (vstate, cache, jnp.int32(0))
        (vstate, _, reach), recs = jax.lax.scan(block_fn, carry, (jnp.arange(n_blocks), keys))
        recs = jax.tree_util.tree_map(lambda x: x.reshape(n_blocks * steps, *x.shape[2:]), recs)
        events = {**recs.pop("ev"), "reach": reach}
        recs["prompt"] = prompt[None]
        return vstate, recs, events
