"""DreamerV1 agent (flax) — counterpart of reference
sheeprl/algos/dreamer_v1/agent.py (RecurrentModel:31, RSSM:64,
PlayerDV1:219, build_agent:329).

V1 deltas from V2 (the encoder/decoder/actor modules are shared with the
DV2 agent, exactly as the reference imports them from dreamer_v2.agent):
- continuous Gaussian latents: representation/transition output
  (mean, std); std = softplus(std) + min_std (reference
  dreamer_v1/utils.py:80);
- plain GRU recurrent core (no LayerNorm trick);
- NO is_first gating in the dynamic step — sampled sequences may cross
  episode boundaries (reference dynamic:97 has no is_first input);
- epsilon-style exploration noise with an optional half-life decay on the
  exploration amount (reference Actor._get_expl_amount; the reference's
  literal formula ``amount * 0.5**step / decay`` collapses to ~0 after a
  few steps — the intended half-life form ``amount * 0.5**(step/decay)``
  is used here; with the default ``expl_decay=0`` both are identical
  constants).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v2.agent import (
    Actor,
    CNNDecoder,
    CNNEncoder,
    MLPDecoder,
    MLPEncoder,
    MultiDecoderV2,
    MultiEncoderV2,
    V2MLP,
    WorldModel,
    add_exploration_noise,
    xavier_init,
)
from sheeprl_tpu.models.models import resolve_activation
from sheeprl_tpu.utils.distribution import Normal
from sheeprl_tpu.utils.utils import place_player_params


def compute_stochastic_state(
    state_information: jax.Array,
    key: Optional[jax.Array],
    min_std: float = 0.1,
    sample: bool = True,
    noise: Optional[jax.Array] = None,
) -> Tuple[Tuple[jax.Array, jax.Array], jax.Array]:
    """(..., 2*stoch) -> ((mean, std), sampled state) (reference
    dreamer_v1/utils.py:80).

    ``noise`` is pre-drawn standard-normal noise of the mean's shape —
    the reparameterized sample becomes ``mean + std * noise``, letting
    the train scans hoist RNG out of their latency-bound bodies."""
    mean, std = jnp.split(state_information, 2, -1)
    std = jax.nn.softplus(std) + min_std
    if noise is not None and sample:
        return (mean, std), mean + std * noise
    dist = Normal(mean, std)
    state = dist.rsample(key) if sample else mean
    return (mean, std), state


class RecurrentModel(nn.Module):
    """Dense+act projection -> plain GRU cell (reference RecurrentModel:31
    wraps nn.GRU)."""

    recurrent_state_size: int
    act: Any = "elu"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, inp: jax.Array, recurrent_state: jax.Array) -> jax.Array:
        feat = nn.Dense(self.recurrent_state_size, kernel_init=xavier_init, dtype=self.dtype)(inp)
        feat = resolve_activation(self.act)(feat)
        # the GRU cell itself stays f32: flax's GRUCell computes the whole
        # convex update in its dtype, and a bf16 carry loses state updates
        # below 2^-8 every sequential step
        new_h, _ = nn.GRUCell(features=self.recurrent_state_size)(
            recurrent_state, feat.astype(jnp.float32)
        )
        return new_h


class RSSM(nn.Module):
    """Continuous-latent RSSM (reference RSSM:64)."""

    actions_dim: Sequence[int]
    embedded_obs_dim: int
    recurrent_state_size: int
    stochastic_size: int = 30
    representation_hidden_size: int = 200
    transition_hidden_size: int = 200
    min_std: float = 0.1
    act: Any = "elu"
    dtype: Any = jnp.float32

    def setup(self) -> None:
        self.recurrent_model = RecurrentModel(
            recurrent_state_size=self.recurrent_state_size, act=self.act, dtype=self.dtype
        )
        self.representation_model = V2MLP(
            self.representation_hidden_size, 1, 2 * self.stochastic_size, self.act, False,
            dtype=self.dtype,
        )
        self.transition_model = V2MLP(
            self.transition_hidden_size, 1, 2 * self.stochastic_size, self.act, False,
            dtype=self.dtype,
        )

    def recurrent_step(self, inp: jax.Array, recurrent_state: jax.Array) -> jax.Array:
        return self.recurrent_model(inp, recurrent_state)

    def _representation(self, recurrent_state: jax.Array, embedded_obs: jax.Array, key, noise=None):
        return compute_stochastic_state(
            self.representation_model(jnp.concatenate([recurrent_state, embedded_obs], -1)),
            key,
            self.min_std,
            noise=noise,
        )

    def _transition(self, recurrent_out: jax.Array, key, sample_state: bool = True, noise=None):
        return compute_stochastic_state(
            self.transition_model(recurrent_out), key, self.min_std, sample=sample_state, noise=noise
        )

    def representation_embed_proj(self, embedded_obs: jax.Array) -> jax.Array:
        """Embed-side half (plus bias) of the representation model's first
        Dense, batched over the whole sequence outside the train scan —
        keeps the (embed_dim, units) kernel-grad accumulator out of the
        backward while-loop (same hoist as dreamer_v3/dreamer_v2)."""
        p = self.representation_model.variables["params"]["DenseActLn_0"]["Dense_0"]
        k_e = p["kernel"][self.recurrent_state_size:].astype(self.dtype)
        return embedded_obs.astype(self.dtype) @ k_e + p["bias"].astype(self.dtype)

    def _representation_from_proj(self, emb_proj: jax.Array, recurrent_state: jax.Array, key, noise=None):
        from sheeprl_tpu.models.models import resolve_activation

        params = self.representation_model.variables["params"]
        p = params["DenseActLn_0"]["Dense_0"]
        k_h = p["kernel"][: self.recurrent_state_size].astype(self.dtype)
        x = recurrent_state.astype(self.dtype) @ k_h + emb_proj
        x = resolve_activation(self.act)(x.astype(self.dtype))  # V1: no LN
        head = params["Dense_0"]
        mean_std = x.astype(jnp.float32) @ head["kernel"] + head["bias"]
        return compute_stochastic_state(mean_std, key, self.min_std, noise=noise)

    def dynamic_posterior_from_proj(
        self,
        posterior: jax.Array,
        recurrent_state: jax.Array,
        action: jax.Array,
        emb_proj: jax.Array,
        key=None,
        noise=None,
    ):
        """:meth:`dynamic_posterior` with the embed-side product
        precomputed (see :meth:`representation_embed_proj`)."""
        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        posterior_mean_std, posterior = self._representation_from_proj(
            emb_proj, recurrent_state, key, noise=noise
        )
        return recurrent_state, posterior, posterior_mean_std

    def dynamic(
        self,
        posterior: jax.Array,
        recurrent_state: jax.Array,
        action: jax.Array,
        embedded_obs: jax.Array,
        key: jax.Array,
    ):
        """One dynamic step — no is_first resets in V1 (reference
        dynamic:97)."""
        k1, k2 = jax.random.split(key)
        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        prior_mean_std, prior = self._transition(recurrent_state, k1)
        posterior_mean_std, posterior = self._representation(recurrent_state, embedded_obs, k2)
        return recurrent_state, posterior, prior, posterior_mean_std, prior_mean_std

    def dynamic_posterior(
        self,
        posterior: jax.Array,
        recurrent_state: jax.Array,
        action: jax.Array,
        embedded_obs: jax.Array,
        key=None,
        noise=None,
    ):
        """Sequential-only slice of :meth:`dynamic` for the train scan —
        the transition model (prior) is a pure function of ``h_t`` and
        batches over the stacked recurrent states outside the scan; its
        mean/std for the KL are recomputed there (see dreamer_v3.agent)."""
        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        posterior_mean_std, posterior = self._representation(
            recurrent_state, embedded_obs, key, noise=noise
        )
        return recurrent_state, posterior, posterior_mean_std

    def imagination(self, stochastic_state: jax.Array, recurrent_state: jax.Array, actions: jax.Array, key, noise=None):
        recurrent_state = self.recurrent_model(
            jnp.concatenate([stochastic_state, actions], -1), recurrent_state
        )
        _, imagined_prior = self._transition(recurrent_state, key, noise=noise)
        return imagined_prior, recurrent_state


class PlayerDV1:
    """Stateful env-interaction wrapper with zeros init states and
    exploration-noise support (reference PlayerDV1:219)."""

    def __init__(
        self,
        world_model: WorldModel,
        actor: Actor,
        params: Dict[str, Any],
        actions_dim: Sequence[int],
        num_envs: int,
        stochastic_size: int,
        recurrent_state_size: int,
        expl_amount: float = 0.0,
        expl_decay: float = 0.0,
        expl_min: float = 0.0,
        actor_type: Optional[str] = None,
        device=None,
    ):
        self.wm = world_model
        self.actor_module = actor
        self.actions_dim = tuple(actions_dim)
        self.num_envs = num_envs
        self.stochastic_size = stochastic_size
        self.recurrent_state_size = recurrent_state_size
        self.expl_amount = expl_amount
        self.expl_decay = expl_decay
        self.expl_min = expl_min
        self.actor_type = actor_type
        self.device = device
        self.params = params

        def _step(params, obs, prev_actions, recurrent_state, stochastic_state, key, mask, expl_amount, greedy):
            embedded_obs = self.wm.encoder.apply(params["world_model"]["encoder"], obs)
            recurrent_state = self.wm.rssm.apply(
                params["world_model"]["rssm"],
                jnp.concatenate([stochastic_state, prev_actions], -1),
                recurrent_state,
                method=RSSM.recurrent_step,
            )
            k1, k2, k3 = jax.random.split(key, 3)
            _, stoch = self.wm.rssm.apply(
                params["world_model"]["rssm"], recurrent_state, embedded_obs, k1,
                method=RSSM._representation,
            )
            actions, _ = self.actor_module.apply(
                params["actor"],
                jnp.concatenate([stoch, recurrent_state], -1),
                greedy,
                k2,
                mask,
            )
            # greedy is static_argnums=8: this branch specializes the trace,
            # it does not concretize a tracer
            if not greedy:  # jaxlint: disable=retrace-branch
                # expl_amount is traced so the decay schedule does not
                # retrigger compilation; amount 0 is a no-op
                actions = add_exploration_noise(
                    actions, k3, expl_amount, self.actions_dim, self.actor_module.is_continuous
                )
            return actions, jnp.concatenate(actions, -1), recurrent_state, stoch

        self._step = jax.jit(_step, static_argnums=(8,))
        self.init_states()

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, value):
        self._params = place_player_params(value, self.device)

    def get_expl_amount(self, step: int) -> float:
        amount = self.expl_amount
        if self.expl_decay:
            amount = amount * 0.5 ** (float(step) / self.expl_decay)
        return max(amount, self.expl_min)

    def init_states(self, reset_envs: Optional[Sequence[int]] = None) -> None:
        if reset_envs is None or len(reset_envs) == 0:
            self.actions = jnp.zeros((1, self.num_envs, int(np.sum(self.actions_dim))))
            self.recurrent_state = jnp.zeros((1, self.num_envs, self.recurrent_state_size))
            self.stochastic_state = jnp.zeros((1, self.num_envs, self.stochastic_size))
        else:
            idx = np.asarray(reset_envs)
            self.actions = self.actions.at[:, idx].set(0.0)
            self.recurrent_state = self.recurrent_state.at[:, idx].set(0.0)
            self.stochastic_state = self.stochastic_state.at[:, idx].set(0.0)

    def get_actions(
        self,
        obs: Dict[str, jax.Array],
        key: jax.Array,
        greedy: bool = False,
        mask=None,
        step: int = 0,
    ) -> Sequence[jax.Array]:
        if self.device is not None:
            obs = jax.device_put(obs, self.device)
            key = jax.device_put(key, self.device)
        expl = jnp.asarray(0.0 if greedy else self.get_expl_amount(step), jnp.float32)
        actions, flat, self.recurrent_state, self.stochastic_state = self._step(
            self._params,
            obs,
            self.actions,
            self.recurrent_state,
            self.stochastic_state,
            key,
            mask,
            expl,
            greedy,
        )
        self.actions = flat
        return actions


def build_agent(
    runtime,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Dict[str, Any],
    obs_space,
    world_model_state: Optional[Any] = None,
    actor_state: Optional[Any] = None,
    critic_state: Optional[Any] = None,
):
    """-> (world_model, actor, critic, params); V1 has NO target critic
    (reference build_agent:329)."""
    world_model_cfg = cfg.algo.world_model
    actor_cfg = cfg.algo.actor
    critic_cfg = cfg.algo.critic

    recurrent_state_size = world_model_cfg.recurrent_model.recurrent_state_size
    stochastic_size = world_model_cfg.stochastic_size
    latent_state_size = stochastic_size + recurrent_state_size

    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    use_continues = bool(world_model_cfg.use_continues)
    cnn_act = world_model_cfg.encoder.get("cnn_act", "relu")
    dense_act = world_model_cfg.encoder.get("dense_act", "elu")
    compute_dtype = runtime.compute_dtype  # precision policy (same split as DV3)

    cnn_encoder = (
        CNNEncoder(
            keys=cnn_keys,
            channels_multiplier=world_model_cfg.encoder.cnn_channels_multiplier,
            layer_norm=False,
            act=cnn_act,
            dtype=compute_dtype,
        )
        if len(cnn_keys) > 0
        else None
    )
    mlp_encoder = (
        MLPEncoder(
            keys=mlp_keys,
            mlp_layers=world_model_cfg.encoder.mlp_layers,
            dense_units=world_model_cfg.encoder.dense_units,
            layer_norm=False,
            act=dense_act,
            dtype=compute_dtype,
        )
        if len(mlp_keys) > 0
        else None
    )
    encoder = MultiEncoderV2(cnn_encoder, mlp_encoder)

    if cnn_encoder is not None:
        size = int(obs_space[cnn_keys[0]].shape[0])
        if size != 64:
            raise ValueError(
                f"DreamerV1's conv encoder/decoder require env.screen_size=64, got: {size}"
            )
        for _ in range(4):
            size = (size - 4) // 2 + 1
        cnn_encoder_output_dim = size * size * 8 * world_model_cfg.encoder.cnn_channels_multiplier
    else:
        cnn_encoder_output_dim = 0
    mlp_encoder_output_dim = world_model_cfg.encoder.dense_units if mlp_encoder is not None else 0
    embedded_obs_dim = cnn_encoder_output_dim + mlp_encoder_output_dim

    rssm = RSSM(
        actions_dim=tuple(actions_dim),
        embedded_obs_dim=embedded_obs_dim,
        recurrent_state_size=recurrent_state_size,
        stochastic_size=stochastic_size,
        representation_hidden_size=world_model_cfg.representation_model.hidden_size,
        transition_hidden_size=world_model_cfg.transition_model.hidden_size,
        min_std=float(world_model_cfg.min_std),
        act=dense_act,
        dtype=compute_dtype,
    )

    cnn_decoder = (
        CNNDecoder(
            keys=tuple(cfg.algo.cnn_keys.decoder),
            output_channels=[int(obs_space[k].shape[-1]) for k in cfg.algo.cnn_keys.decoder],
            channels_multiplier=world_model_cfg.observation_model.cnn_channels_multiplier,
            cnn_encoder_output_dim=cnn_encoder_output_dim,
            layer_norm=False,
            act=cnn_act,
            dtype=compute_dtype,
        )
        if len(cfg.algo.cnn_keys.decoder) > 0
        else None
    )
    mlp_decoder = (
        MLPDecoder(
            keys=tuple(cfg.algo.mlp_keys.decoder),
            output_dims=[int(obs_space[k].shape[0]) for k in cfg.algo.mlp_keys.decoder],
            mlp_layers=world_model_cfg.observation_model.mlp_layers,
            dense_units=world_model_cfg.observation_model.dense_units,
            layer_norm=False,
            act=dense_act,
            dtype=compute_dtype,
        )
        if len(cfg.algo.mlp_keys.decoder) > 0
        else None
    )
    observation_model = MultiDecoderV2(cnn_decoder, mlp_decoder)

    reward_model = V2MLP(
        units=world_model_cfg.reward_model.dense_units,
        layers=world_model_cfg.reward_model.mlp_layers,
        output_dim=1,
        act=dense_act,
        dtype=compute_dtype,
    )
    continue_model = (
        V2MLP(
            units=world_model_cfg.discount_model.dense_units,
            layers=world_model_cfg.discount_model.mlp_layers,
            output_dim=1,
            act=dense_act,
            dtype=compute_dtype,
        )
        if use_continues
        else None
    )
    world_model = WorldModel(encoder, rssm, observation_model, reward_model, continue_model)

    actor = Actor(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        distribution=cfg.distribution.get("type", "auto"),
        init_std=actor_cfg.init_std,
        min_std=actor_cfg.min_std,
        dense_units=actor_cfg.dense_units,
        mlp_layers=actor_cfg.mlp_layers,
        layer_norm=False,
        act=actor_cfg.get("dense_act", "elu"),
        dtype=compute_dtype,
    )
    critic = V2MLP(
        units=critic_cfg.dense_units,
        layers=critic_cfg.mlp_layers,
        output_dim=1,
        act=critic_cfg.get("dense_act", "elu"),
        dtype=compute_dtype,
    )

    B = 1
    dummy_obs = {}
    for k in cnn_keys:
        dummy_obs[k] = jnp.zeros((B, *obs_space[k].shape), jnp.float32)
    for k in mlp_keys:
        dummy_obs[k] = jnp.zeros((B, *obs_space[k].shape), jnp.float32)
    dummy_embed = jnp.zeros((B, embedded_obs_dim), jnp.float32)
    dummy_latent = jnp.zeros((B, latent_state_size), jnp.float32)
    k = runtime.next_key

    if world_model_state is not None:
        wm_params = jax.tree_util.tree_map(jnp.asarray, world_model_state)
    else:
        rssm_params = rssm.init(
            {"params": k()},
            jnp.zeros((B, stochastic_size)),
            jnp.zeros((B, recurrent_state_size)),
            jnp.zeros((B, int(np.sum(actions_dim)))),
            dummy_embed,
            k(),
            method=RSSM.dynamic,
        )
        wm_params = {
            "encoder": encoder.init(k(), dummy_obs),
            "rssm": rssm_params,
            "observation_model": observation_model.init(k(), dummy_latent),
            "reward_model": reward_model.init(k(), dummy_latent),
        }
        if continue_model is not None:
            wm_params["continue_model"] = continue_model.init(k(), dummy_latent)
    actor_params = (
        jax.tree_util.tree_map(jnp.asarray, actor_state)
        if actor_state is not None
        else actor.init({"params": k()}, dummy_latent, False, k())
    )
    critic_params = (
        jax.tree_util.tree_map(jnp.asarray, critic_state)
        if critic_state is not None
        else critic.init(k(), dummy_latent)
    )
    params = {
        "world_model": wm_params,
        "actor": actor_params,
        "critic": critic_params,
    }
    return world_model, actor, critic, params
