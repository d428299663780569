"""DreamerV2 agent (flax) — counterpart of reference
sheeprl/algos/dreamer_v2/agent.py (CNNEncoder:31, MLPEncoder:85,
CNNDecoder:129, MLPDecoder:199, RecurrentModel:246, RSSM:301, Actor:416,
WorldModel:707, PlayerDV2:735, build_agent:836).

Differences from the DV3 agent that define the V2 behavior:
- ELU activations, LayerNorm mostly off (GRU keeps its LN);
- encoder convs are VALID-padded k=4 s=2 (64 -> 31 -> 14 -> 6 -> 2), the
  decoder inverts with VALID deconvs of kernels [5, 5, 6, 6] from a 1x1
  feature map;
- no unimix on latent/actor logits, no learnable initial recurrent state
  (zeros resets), no symlog/two-hot heads;
- continuous actor defaults to a TruncatedNormal on tanh(mean);
- Xavier-normal init with zero biases (reference dreamer_v2/utils.py:64).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models.models import (
    LayerNormGRUCell,
    batch_major_flatten,
    batch_major_unflatten,
    resolve_activation,
)
from sheeprl_tpu.utils.distribution import (
    Independent,
    Normal,
    OneHotCategorical,
    OneHotCategoricalStraightThrough,
    TanhNormal,
    TruncatedNormal,
)
from sheeprl_tpu.utils.utils import place_player_params

xavier_init = nn.initializers.xavier_normal()


class DenseActLn(nn.Module):
    """Dense -> (optional LayerNorm) -> activation, Xavier-normal init."""

    units: int
    act: Any = "elu"
    layer_norm: bool = False
    dtype: Any = jnp.float32  # compute dtype; params f32, LN statistics f32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = nn.Dense(self.units, kernel_init=xavier_init, dtype=self.dtype)(x)
        if self.layer_norm:
            x = nn.LayerNorm()(x)
        return resolve_activation(self.act)(x.astype(self.dtype))


class V2MLP(nn.Module):
    """Stack of DenseActLn blocks + optional linear output head."""

    units: int
    layers: int
    output_dim: Optional[int] = None
    act: Any = "elu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        for _ in range(self.layers):
            x = DenseActLn(self.units, self.act, self.layer_norm, dtype=self.dtype)(x)
        if self.output_dim is not None:
            # heads emit f32 for the downstream distributions
            x = nn.Dense(self.output_dim, kernel_init=xavier_init)(x.astype(jnp.float32))
        return x


class CNNEncoder(nn.Module):
    """4-stage VALID conv encoder, kernel 4 stride 2, channels
    [1, 2, 4, 8] * mult, NHWC (reference CNNEncoder:31 assumes 64x64)."""

    keys: Sequence[str]
    channels_multiplier: int
    layer_norm: bool = False
    act: Any = "elu"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)
        # sharding-critical: see batch_major_flatten
        x, lead = batch_major_flatten(x, 3)
        for i in range(4):
            x = nn.Conv(
                (2**i) * self.channels_multiplier,
                (4, 4),
                strides=(2, 2),
                padding="VALID",
                kernel_init=xavier_init,
                dtype=self.dtype,
            )(x)
            if self.layer_norm:
                x = nn.LayerNorm()(x)
            x = resolve_activation(self.act)(x.astype(self.dtype))
        return batch_major_unflatten(x.reshape(x.shape[0], -1), lead)


class MLPEncoder(nn.Module):
    keys: Sequence[str]
    mlp_layers: int = 4
    dense_units: int = 400
    layer_norm: bool = False
    act: Any = "elu"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], -1)
        return V2MLP(self.dense_units, self.mlp_layers, None, self.act, self.layer_norm, dtype=self.dtype)(x)


class MultiEncoderV2(nn.Module):
    cnn_encoder: Optional[nn.Module] = None
    mlp_encoder: Optional[nn.Module] = None

    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        feats = []
        if self.cnn_encoder is not None:
            feats.append(self.cnn_encoder(obs))
        if self.mlp_encoder is not None:
            feats.append(self.mlp_encoder(obs))
        return jnp.concatenate(feats, -1) if len(feats) > 1 else feats[0]


class CNNDecoder(nn.Module):
    """Linear latent -> (1, 1, cnn_encoder_output_dim) -> 4 VALID deconvs of
    kernels [5, 5, 6, 6] stride 2 back to 64x64 (reference CNNDecoder:129)."""

    keys: Sequence[str]
    output_channels: Sequence[int]
    channels_multiplier: int
    cnn_encoder_output_dim: int
    layer_norm: bool = False
    act: Any = "elu"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        x = nn.Dense(self.cnn_encoder_output_dim, kernel_init=xavier_init, dtype=self.dtype)(latent)
        # sharding-critical: see batch_major_flatten
        x, lead = batch_major_flatten(x, 1)
        x = x.reshape(-1, 1, 1, self.cnn_encoder_output_dim)
        chans = [4 * self.channels_multiplier, 2 * self.channels_multiplier, self.channels_multiplier]
        kernels = [5, 5, 6, 6]
        for i, ch in enumerate(chans):
            x = nn.ConvTranspose(
                ch, (kernels[i], kernels[i]), strides=(2, 2), padding="VALID", kernel_init=xavier_init,
                dtype=self.dtype,
            )(x)
            if self.layer_norm:
                x = nn.LayerNorm()(x)
            x = resolve_activation(self.act)(x.astype(self.dtype))
        x = x.astype(jnp.float32)  # final deconv emits f32 for the dists
        x = nn.ConvTranspose(
            int(sum(self.output_channels)),
            (kernels[-1], kernels[-1]),
            strides=(2, 2),
            padding="VALID",
            kernel_init=xavier_init,
        )(x)
        x = batch_major_unflatten(x, lead)
        out: Dict[str, jax.Array] = {}
        start = 0
        for k, c in zip(self.keys, self.output_channels):
            out[k] = x[..., start : start + c]
            start += c
        return out


class MLPDecoder(nn.Module):
    keys: Sequence[str]
    output_dims: Sequence[int]
    mlp_layers: int = 4
    dense_units: int = 400
    layer_norm: bool = False
    act: Any = "elu"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        x = V2MLP(self.dense_units, self.mlp_layers, None, self.act, self.layer_norm, dtype=self.dtype)(latent)
        x = x.astype(jnp.float32)
        return {
            k: nn.Dense(d, kernel_init=xavier_init)(x) for k, d in zip(self.keys, self.output_dims)
        }


class MultiDecoderV2(nn.Module):
    cnn_decoder: Optional[nn.Module] = None
    mlp_decoder: Optional[nn.Module] = None

    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder(latent))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder(latent))
        return out


class RecurrentModel(nn.Module):
    """Dense+act projection -> LayerNormGRUCell with bias and LN (reference
    RecurrentModel:246: the GRU always keeps its LayerNorm in V2)."""

    recurrent_state_size: int
    dense_units: int
    layer_norm: bool = False  # LN of the pre-GRU MLP only
    act: Any = "elu"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, inp: jax.Array, recurrent_state: jax.Array) -> jax.Array:
        feat = DenseActLn(self.dense_units, self.act, self.layer_norm, dtype=self.dtype)(inp)
        new_h, _ = LayerNormGRUCell(
            hidden_size=self.recurrent_state_size, use_bias=True, layer_norm=True,
            dtype=self.dtype,
        )(recurrent_state, feat)
        return new_h.astype(jnp.float32)


def compute_stochastic_state(
    logits: jax.Array,
    discrete: int,
    key: Optional[jax.Array],
    sample: bool = True,
    noise: Optional[jax.Array] = None,
) -> jax.Array:
    """(..., stoch*discrete) logits -> (..., stoch, discrete) one-hot ST
    sample (reference dreamer_v2/utils.py:44); no unimix in V2.

    ``noise`` is pre-drawn Gumbel noise of the reshaped logits' shape —
    the categorical sample becomes ``argmax(logits + noise)`` with the
    same straight-through estimator, letting train scans hoist all RNG
    out of their latency-bound bodies (see dreamer_v3.agent)."""
    logits = logits.reshape(*logits.shape[:-1], -1, discrete)
    if noise is not None and sample:
        hard = jax.nn.one_hot(
            jnp.argmax(logits + noise, -1), discrete, dtype=logits.dtype
        )
        p = jax.nn.softmax(logits, -1)
        return jax.lax.stop_gradient(hard) + p - jax.lax.stop_gradient(p)
    dist = OneHotCategoricalStraightThrough(logits=logits)
    return dist.rsample(key) if sample else dist.mode


class RSSM(nn.Module):
    """Discrete-latent RSSM with zeros initial state and is_first-gated
    zero resets (reference RSSM:301)."""

    actions_dim: Sequence[int]
    embedded_obs_dim: int
    recurrent_state_size: int
    dense_units: int
    stochastic_size: int = 32
    discrete_size: int = 32
    representation_hidden_size: int = 600
    transition_hidden_size: int = 600
    layer_norm: bool = False
    recurrent_layer_norm: bool = False
    act: Any = "elu"
    dtype: Any = jnp.float32

    def setup(self) -> None:
        stoch = self.stochastic_size * self.discrete_size
        self.recurrent_model = RecurrentModel(
            recurrent_state_size=self.recurrent_state_size,
            dense_units=self.dense_units,
            layer_norm=self.recurrent_layer_norm,
            act=self.act,
            dtype=self.dtype,
        )
        self.representation_model = V2MLP(
            self.representation_hidden_size, 1, stoch, self.act, self.layer_norm, dtype=self.dtype
        )
        self.transition_model = V2MLP(
            self.transition_hidden_size, 1, stoch, self.act, self.layer_norm, dtype=self.dtype
        )

    def recurrent_step(self, inp: jax.Array, recurrent_state: jax.Array) -> jax.Array:
        return self.recurrent_model(inp, recurrent_state)

    def _representation(
        self,
        recurrent_state: jax.Array,
        embedded_obs: jax.Array,
        key: Optional[jax.Array],
        noise: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        logits = self.representation_model(jnp.concatenate([recurrent_state, embedded_obs], -1))
        return logits, compute_stochastic_state(logits, self.discrete_size, key, noise=noise)

    def _transition(
        self,
        recurrent_out: jax.Array,
        key: Optional[jax.Array],
        sample_state: bool = True,
        noise: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        logits = self.transition_model(recurrent_out)
        return logits, compute_stochastic_state(
            logits, self.discrete_size, key, sample=sample_state, noise=noise
        )

    def representation_embed_proj(self, embedded_obs: jax.Array) -> jax.Array:
        """Embed-side half of the representation model's first Dense.

        The first DenseActLn of the representation model sees
        ``[h_t, embed_t]``; splitting its kernel lets the (big) embed-side
        product — plus the Dense bias — run as ONE batched matmul over the
        whole sequence outside the train scan, and moves its
        (embed_dim, units) kernel-gradient accumulation out of the
        backward while-loop's carry (same argument as the DV3 hoist,
        dreamer_v3.agent.RSSM.representation_embed_proj)."""
        p = self.representation_model.variables["params"]["DenseActLn_0"]["Dense_0"]
        k_e = p["kernel"][self.recurrent_state_size:].astype(self.dtype)
        return embedded_obs.astype(self.dtype) @ k_e + p["bias"].astype(self.dtype)

    def _representation_from_proj(
        self,
        emb_proj: jax.Array,
        recurrent_state: jax.Array,
        key: Optional[jax.Array] = None,
        noise: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Posterior from a precomputed embed projection: the scan-body
        slice of :meth:`_representation` (manually unrolled V2MLP(layers=1)
        so the h-side product adds onto ``emb_proj``)."""
        from sheeprl_tpu.models.models import ln_act_apply, resolve_activation

        params = self.representation_model.variables["params"]
        p = params["DenseActLn_0"]["Dense_0"]
        k_h = p["kernel"][: self.recurrent_state_size].astype(self.dtype)
        x = recurrent_state.astype(self.dtype) @ k_h + emb_proj
        if self.layer_norm:
            # DenseActLn uses flax LayerNorm defaults (eps 1e-6, f32 stats)
            x = ln_act_apply(
                params["DenseActLn_0"]["LayerNorm_0"], x,
                eps=1e-6, act=self.act, dtype=self.dtype,
            )
        else:
            x = resolve_activation(self.act)(x.astype(self.dtype))
        head = params["Dense_0"]
        logits = x.astype(jnp.float32) @ head["kernel"] + head["bias"]
        return logits, compute_stochastic_state(
            logits, self.discrete_size, key, noise=noise
        )

    def dynamic_posterior_from_proj(
        self,
        posterior: jax.Array,
        recurrent_state: jax.Array,
        action: jax.Array,
        emb_proj: jax.Array,
        is_first: jax.Array,
        key: Optional[jax.Array] = None,
        noise: Optional[jax.Array] = None,
    ):
        """:meth:`dynamic_posterior` with the representation model's
        embed-side product precomputed (see
        :meth:`representation_embed_proj`)."""
        action = (1 - is_first) * action
        posterior = (1 - is_first) * posterior.reshape(*posterior.shape[:-2], -1)
        recurrent_state = (1 - is_first) * recurrent_state
        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        posterior_logits, posterior = self._representation_from_proj(
            emb_proj, recurrent_state, key, noise=noise
        )
        return recurrent_state, posterior, posterior_logits

    def dynamic(
        self,
        posterior: jax.Array,
        recurrent_state: jax.Array,
        action: jax.Array,
        embedded_obs: jax.Array,
        is_first: jax.Array,
        key: jax.Array,
    ):
        """One dynamic step; zero resets where is_first (reference
        dynamic:336-369)."""
        k1, k2 = jax.random.split(key)
        action = (1 - is_first) * action
        posterior = (1 - is_first) * posterior.reshape(*posterior.shape[:-2], -1)
        recurrent_state = (1 - is_first) * recurrent_state
        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        prior_logits, prior = self._transition(recurrent_state, k1)
        posterior_logits, posterior = self._representation(recurrent_state, embedded_obs, k2)
        return recurrent_state, posterior, prior, posterior_logits, prior_logits

    def dynamic_posterior(
        self,
        posterior: jax.Array,
        recurrent_state: jax.Array,
        action: jax.Array,
        embedded_obs: jax.Array,
        is_first: jax.Array,
        key: Optional[jax.Array] = None,
        noise: Optional[jax.Array] = None,
    ):
        """Sequential-only slice of :meth:`dynamic` for the train scan: the
        transition model (prior) is a pure function of ``h_t``, its SAMPLE
        is unused by the world-model loss, and it batches over the stacked
        recurrent states outside the scan (see dreamer_v3.agent)."""
        action = (1 - is_first) * action
        posterior = (1 - is_first) * posterior.reshape(*posterior.shape[:-2], -1)
        recurrent_state = (1 - is_first) * recurrent_state
        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        posterior_logits, posterior = self._representation(
            recurrent_state, embedded_obs, key, noise=noise
        )
        return recurrent_state, posterior, posterior_logits

    def imagination(
        self,
        prior: jax.Array,
        recurrent_state: jax.Array,
        actions: jax.Array,
        key: Optional[jax.Array],
        noise: Optional[jax.Array] = None,
    ):
        recurrent_state = self.recurrent_model(
            jnp.concatenate([prior, actions], -1), recurrent_state
        )
        _, imagined_prior = self._transition(recurrent_state, key, noise=noise)
        return imagined_prior, recurrent_state


class Actor(nn.Module):
    """DV2 actor: ELU trunk + per-subaction one-hot ST heads (discrete) or a
    TruncatedNormal/TanhNormal/Normal head (continuous) (reference Actor:416)."""

    actions_dim: Sequence[int]
    is_continuous: bool
    distribution: str = "auto"
    init_std: float = 0.0
    min_std: float = 0.1
    dense_units: int = 400
    mlp_layers: int = 4
    layer_norm: bool = False
    act: Any = "elu"
    dtype: Any = jnp.float32

    def _dist_name(self) -> str:
        d = self.distribution.lower()
        if d == "auto":
            return "trunc_normal" if self.is_continuous else "discrete"
        return d

    @nn.compact
    def __call__(
        self,
        state: jax.Array,
        greedy: bool = False,
        key: Optional[jax.Array] = None,
        mask: Optional[Dict[str, jax.Array]] = None,
    ):
        x = state
        for _ in range(self.mlp_layers):
            x = DenseActLn(self.dense_units, self.act, self.layer_norm, dtype=self.dtype)(x)
        x = x.astype(jnp.float32)  # dist heads in f32
        if self.is_continuous:
            pre = nn.Dense(int(np.sum(self.actions_dim)) * 2, kernel_init=xavier_init)(x)
            mean, std = jnp.split(pre, 2, -1)
            name = self._dist_name()
            if name == "tanh_normal":
                mean = 5 * jnp.tanh(mean / 5)
                std = jax.nn.softplus(std + self.init_std) + self.min_std
                dist = Independent(TanhNormal(mean, std), 1)
            elif name == "normal":
                dist = Independent(Normal(mean, std), 1)
            elif name == "trunc_normal":
                std = 2 * jax.nn.sigmoid((std + self.init_std) / 2) + self.min_std
                dist = Independent(TruncatedNormal(jnp.tanh(mean), std, -1.0, 1.0), 1)
            else:
                raise ValueError(f"Bad continuous distribution: {name}")
            # reference (greedy) samples 100 and keeps the argmax-log-prob
            # one; for these unimodal dists the mode is that argmax
            actions = dist.mode if greedy else dist.rsample(key)
            return (actions,), (dist,)
        heads = [nn.Dense(d, kernel_init=xavier_init)(x) for d in self.actions_dim]
        actions: List[jax.Array] = []
        dists = []
        keys = jax.random.split(key, len(heads)) if key is not None else [None] * len(heads)
        # MineDojo-style conditional masks (reference MinedojoActor:577),
        # vectorized: craft head constrained when the functional action is
        # craft (15), inventory head for equip/place (16/17) / destroy (18)
        functional_action = None
        for i, logits in enumerate(heads):
            if mask is not None:
                if i == 0 and "mask_action_type" in mask:
                    logits = jnp.where(mask["mask_action_type"], logits, -jnp.inf)
                elif i == 1 and "mask_craft_smelt" in mask:
                    is_craft = (functional_action == 15)[..., None]
                    valid = jnp.where(is_craft, mask["mask_craft_smelt"], True)
                    logits = jnp.where(valid, logits, -jnp.inf)
                elif i == 2 and "mask_equip_place" in mask and "mask_destroy" in mask:
                    fa = functional_action[..., None]
                    valid = jnp.where(
                        (fa == 16) | (fa == 17),
                        mask["mask_equip_place"],
                        jnp.where(fa == 18, mask["mask_destroy"], True),
                    )
                    logits = jnp.where(valid, logits, -jnp.inf)
            d = OneHotCategoricalStraightThrough(logits=logits)
            dists.append(d)
            actions.append(d.mode if greedy else d.rsample(keys[i]))
            if functional_action is None:
                functional_action = actions[0].argmax(-1)
        return tuple(actions), tuple(dists)


# cfg.algo.actor.cls target for MineDojo runs (reference MinedojoActor:577)
MinedojoActor = Actor


def add_exploration_noise(
    actions: Sequence[jax.Array],
    key: jax.Array,
    expl_amount: float,
    actions_dim: Sequence[int],
    is_continuous: bool,
) -> Sequence[jax.Array]:
    """Epsilon-style exploration noise (reference Actor.add_exploration_noise:
    clipped Normal jitter for continuous, uniform one-hot resample with
    probability ``expl_amount`` for discrete). ``expl_amount`` may be a
    traced scalar (decay schedules); amount 0 is then a no-op rather than a
    short-circuit."""
    if isinstance(expl_amount, (int, float)) and expl_amount <= 0.0:
        return tuple(actions)
    if is_continuous:
        flat = jnp.concatenate(list(actions), -1)
        noisy = jnp.clip(flat + expl_amount * jax.random.normal(key, flat.shape), -1.0, 1.0)
        # the clip belongs to the noise: with amount 0 (traced) return the
        # raw action so unbounded heads are not silently truncated
        return (jnp.where(jnp.asarray(expl_amount) > 0, noisy, flat),)
    out = []
    keys = jax.random.split(key, 2 * len(actions))
    for i, act in enumerate(actions):
        sample = OneHotCategorical(logits=jnp.zeros_like(act)).sample(keys[2 * i])
        coin = jax.random.uniform(keys[2 * i + 1], act.shape[:-1] + (1,))
        out.append(jnp.where(coin < expl_amount, sample, act))
    return tuple(out)


class WorldModel:
    """Container of the world-model modules sharing one params tree
    (reference WorldModel:707). ``continue_model`` may be None
    (use_continues=False default in V2)."""

    def __init__(self, encoder, rssm, observation_model, reward_model, continue_model=None):
        self.encoder = encoder
        self.rssm = rssm
        self.observation_model = observation_model
        self.reward_model = reward_model
        self.continue_model = continue_model


class PlayerDV2:
    """Stateful env-interaction wrapper with zeros init states
    (reference PlayerDV2:735)."""

    def __init__(
        self,
        world_model: WorldModel,
        actor: Actor,
        params: Dict[str, Any],
        actions_dim: Sequence[int],
        num_envs: int,
        stochastic_size: int,
        recurrent_state_size: int,
        discrete_size: int = 32,
        actor_type: Optional[str] = None,
        expl_amount: float = 0.0,
        device=None,
    ):
        self.wm = world_model
        self.actor_module = actor
        self.actions_dim = tuple(actions_dim)
        self.num_envs = num_envs
        self.stochastic_size = stochastic_size
        self.discrete_size = discrete_size
        self.recurrent_state_size = recurrent_state_size
        self.actor_type = actor_type
        self.expl_amount = expl_amount
        self.device = device
        self.params = params

        def _step(params, obs, prev_actions, recurrent_state, stochastic_state, key, mask, greedy, expl_amount):
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
            stoch_flat = stoch.reshape(*stoch.shape[:-2], self.stochastic_size * self.discrete_size)
            actions, _ = self.actor_module.apply(
                params["actor"],
                jnp.concatenate([stoch_flat, recurrent_state], -1),
                greedy,
                k2,
                mask,
            )
            # greedy/expl_amount are static_argnums=(7, 8): static trace
            # specialization, not tracer concretization
            if expl_amount > 0.0 and not greedy:  # jaxlint: disable=retrace-branch
                actions = add_exploration_noise(
                    actions, k3, expl_amount, self.actions_dim, self.actor_module.is_continuous
                )
            return actions, jnp.concatenate(actions, -1), recurrent_state, stoch_flat

        self._step = jax.jit(_step, static_argnums=(7, 8))
        self.init_states()

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, value):
        self._params = place_player_params(value, self.device)

    def init_states(self, reset_envs: Optional[Sequence[int]] = None) -> None:
        if reset_envs is None or len(reset_envs) == 0:
            self.actions = jnp.zeros((1, self.num_envs, int(np.sum(self.actions_dim))))
            self.recurrent_state = jnp.zeros((1, self.num_envs, self.recurrent_state_size))
            self.stochastic_state = jnp.zeros(
                (1, self.num_envs, self.stochastic_size * self.discrete_size)
            )
        else:
            idx = np.asarray(reset_envs)
            self.actions = self.actions.at[:, idx].set(0.0)
            self.recurrent_state = self.recurrent_state.at[:, idx].set(0.0)
            self.stochastic_state = self.stochastic_state.at[:, idx].set(0.0)

    def get_actions(
        self, obs: Dict[str, jax.Array], key: jax.Array, greedy: bool = False, mask=None
    ) -> Sequence[jax.Array]:
        if self.device is not None:
            obs = jax.device_put(obs, self.device)
            key = jax.device_put(key, self.device)
        actions, flat, self.recurrent_state, self.stochastic_state = self._step(
            self._params,
            obs,
            self.actions,
            self.recurrent_state,
            self.stochastic_state,
            key,
            mask,
            greedy,
            float(self.expl_amount),
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
    target_critic_state: Optional[Any] = None,
):
    """-> (world_model, actor, critic(V2MLP), params) with
    params = {world_model, actor, critic, target_critic} (reference
    build_agent:836)."""
    world_model_cfg = cfg.algo.world_model
    actor_cfg = cfg.algo.actor
    critic_cfg = cfg.algo.critic

    recurrent_state_size = world_model_cfg.recurrent_model.recurrent_state_size
    stochastic_size = world_model_cfg.stochastic_size * world_model_cfg.discrete_size
    latent_state_size = stochastic_size + recurrent_state_size

    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    use_continues = bool(world_model_cfg.use_continues)

    cnn_act = world_model_cfg.encoder.get("cnn_act", "elu")
    dense_act = world_model_cfg.encoder.get("dense_act", "elu")
    enc_ln = bool(world_model_cfg.encoder.layer_norm)
    # fabric.precision policy: trunks compute in bf16 under *-mixed/true,
    # heads/LN statistics/scan carries stay f32 (same split as DV3)
    compute_dtype = runtime.compute_dtype

    cnn_encoder = (
        CNNEncoder(
            keys=cnn_keys,
            channels_multiplier=world_model_cfg.encoder.cnn_channels_multiplier,
            layer_norm=enc_ln,
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
            layer_norm=enc_ln,
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
            # the fixed 4-stage VALID encoder/decoder pair round-trips 64x64
            # only (reference CNNEncoder:31 'assumes that the image is a 64x64')
            raise ValueError(
                f"DreamerV2's conv encoder/decoder require env.screen_size=64, got: {size}"
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
        dense_units=world_model_cfg.recurrent_model.dense_units,
        stochastic_size=world_model_cfg.stochastic_size,
        discrete_size=world_model_cfg.discrete_size,
        representation_hidden_size=world_model_cfg.representation_model.hidden_size,
        transition_hidden_size=world_model_cfg.transition_model.hidden_size,
        layer_norm=bool(world_model_cfg.representation_model.layer_norm),
        recurrent_layer_norm=bool(world_model_cfg.recurrent_model.layer_norm),
        act=dense_act,
        dtype=compute_dtype,
    )

    cnn_decoder = (
        CNNDecoder(
            keys=tuple(cfg.algo.cnn_keys.decoder),
            output_channels=[int(obs_space[k].shape[-1]) for k in cfg.algo.cnn_keys.decoder],
            channels_multiplier=world_model_cfg.observation_model.cnn_channels_multiplier,
            cnn_encoder_output_dim=cnn_encoder_output_dim,
            layer_norm=bool(world_model_cfg.observation_model.layer_norm),
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
            layer_norm=bool(world_model_cfg.observation_model.layer_norm),
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
        layer_norm=bool(world_model_cfg.reward_model.layer_norm),
        dtype=compute_dtype,
    )
    continue_model = (
        V2MLP(
            units=world_model_cfg.discount_model.dense_units,
            layers=world_model_cfg.discount_model.mlp_layers,
            output_dim=1,
            act=dense_act,
            layer_norm=bool(world_model_cfg.discount_model.layer_norm),
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
        layer_norm=bool(actor_cfg.layer_norm),
        act=actor_cfg.get("dense_act", "elu"),
        dtype=compute_dtype,
    )
    critic = V2MLP(
        units=critic_cfg.dense_units,
        layers=critic_cfg.mlp_layers,
        output_dim=1,
        act=critic_cfg.get("dense_act", "elu"),
        layer_norm=bool(critic_cfg.layer_norm),
        dtype=compute_dtype,
    )

    # ------------------------------------------------------------- init
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
            jnp.zeros((B, world_model_cfg.stochastic_size, world_model_cfg.discrete_size)),
            jnp.zeros((B, recurrent_state_size)),
            jnp.zeros((B, int(np.sum(actions_dim)))),
            dummy_embed,
            jnp.zeros((B, 1)),
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
    target_critic_params = (
        jax.tree_util.tree_map(jnp.asarray, target_critic_state)
        if target_critic_state is not None
        else jax.tree_util.tree_map(jnp.copy, critic_params)
    )
    params = {
        "world_model": wm_params,
        "actor": actor_params,
        "critic": critic_params,
        "target_critic": target_critic_params,
    }
    return world_model, actor, critic, params
