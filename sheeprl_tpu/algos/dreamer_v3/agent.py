"""DreamerV3 agent (flax) — counterpart of reference
sheeprl/algos/dreamer_v3/agent.py (CNNEncoder:42, MLPEncoder:100,
CNNDecoder:154, MLPDecoder:229, RecurrentModel:281, RSSM:344,
DecoupledRSSM:501, PlayerDV3:596, Actor:694, build_agent:935).

Structure: one top-level flax module per optimizer group — the world model
is a dict of modules {encoder, rssm, observation_model, reward_model,
continue_model} sharing a single params pytree ``params["world_model"]``;
actor and critic are separate. The reference's weight-tying between agent
and player (agent.py:1229-1235) is inherent here: the player applies the
same params.

Numerical-parity notes (SURVEY.md §7 "hard parts"):
- unimix 1% on RSSM and actor logits;
- Hafner initialization (agent.py:1170-1180): trunc-normal fan-avg
  everywhere, uniform fan-avg on dist heads, zeros on reward/critic heads;
- learnable initial recurrent state passed through tanh;
- ``is_first``-gated resets inside the dynamic step;
- images are NHWC; frame (H, W, C).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models.models import (
    MLP,
    LayerNormGRUCell,
    batch_major_flatten,
    batch_major_unflatten,
    gru_cell_apply,
    linear_ln_act_apply,
    ln_act_apply,
    resolve_activation,
)
from sheeprl_tpu.utils.distribution import (
    Independent,
    Normal,
    OneHotCategoricalStraightThrough,
    TanhNormal,
)
from sheeprl_tpu.utils.utils import place_player_params, symlog

# Hafner inits (reference dreamer_v3/utils.py:143-187)
trunc_init = nn.initializers.variance_scaling(1.0, "fan_avg", "truncated_normal")


def uniform_out_init(scale: float) -> Callable:
    if scale == 0.0:
        return nn.initializers.zeros_init()
    return nn.initializers.variance_scaling(scale, "fan_avg", "uniform")


def _ln_enabled(cfg_node: Any) -> bool:
    """Map the reference's layer_norm `cls` strings to a bool."""
    if cfg_node is None:
        return False
    cls = str(cfg_node.get("cls", "")) if isinstance(cfg_node, dict) else str(cfg_node)
    return "identity" not in cls.lower()


def _ln_eps(cfg_node: Any) -> float:
    if isinstance(cfg_node, dict):
        return float(cfg_node.get("kw", {}).get("eps", 1e-3))
    return 1e-3


class LinearLnAct(nn.Module):
    """Dense (no bias when followed by LN) -> LayerNorm -> activation —
    the Dreamer building block."""

    units: int
    layer_norm: bool = True
    eps: float = 1e-3
    act: Any = "silu"
    kernel_init: Callable = trunc_init
    dtype: Any = jnp.float32  # compute dtype; params stay f32, LN reduces f32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = nn.Dense(
            self.units,
            use_bias=not self.layer_norm,
            kernel_init=self.kernel_init,
            dtype=self.dtype,
        )(x)
        if self.layer_norm:
            x = nn.LayerNorm(epsilon=self.eps)(x)  # f32 statistics
        return resolve_activation(self.act)(x.astype(self.dtype))


class DreamerMLP(nn.Module):
    """Stack of LinearLnAct blocks + optional output head with its own init."""

    units: int
    layers: int
    output_dim: Optional[int] = None
    layer_norm: bool = True
    eps: float = 1e-3
    act: Any = "silu"
    out_init: Callable = trunc_init
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        for _ in range(self.layers):
            x = LinearLnAct(self.units, self.layer_norm, self.eps, self.act, dtype=self.dtype)(x)
        if self.output_dim is not None:
            # heads emit f32: downstream distributions/losses stay exact
            x = nn.Dense(self.output_dim, kernel_init=self.out_init)(x.astype(jnp.float32))
        return x


class CNNEncoder(nn.Module):
    """4-ish-stage conv encoder, kernel 4 stride 2, channels [1,2,4,8]*mult,
    NHWC, LayerNorm over channels + SiLU; flattens to a feature vector."""

    keys: Sequence[str]
    channels_multiplier: int
    stages: int = 4
    layer_norm: bool = True
    eps: float = 1e-3
    act: Any = "silu"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)  # channel concat
        # sharding-critical: see batch_major_flatten
        x, lead = batch_major_flatten(x, 3)
        for i in range(self.stages):
            x = nn.Conv(
                (2**i) * self.channels_multiplier,
                (4, 4),
                strides=(2, 2),
                padding=[(1, 1), (1, 1)],
                use_bias=not self.layer_norm,
                kernel_init=trunc_init,
                dtype=self.dtype,
            )(x)
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=self.eps)(x)  # f32 statistics
            x = resolve_activation(self.act)(x.astype(self.dtype))
        return batch_major_unflatten(x.reshape(x.shape[0], -1), lead)


class MLPEncoder(nn.Module):
    keys: Sequence[str]
    mlp_layers: int = 4
    dense_units: int = 512
    layer_norm: bool = True
    eps: float = 1e-3
    act: Any = "silu"
    symlog_inputs: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate(
            [symlog(obs[k]) if self.symlog_inputs else obs[k] for k in self.keys], -1
        )
        return DreamerMLP(
            self.dense_units, self.mlp_layers, None, self.layer_norm, self.eps, self.act,
            dtype=self.dtype,
        )(x)


class MultiEncoderDV3(nn.Module):
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
    """Linear projection -> (4, 4, 8*mult) -> transposed convs back to
    (H, W, sum(channels)); returns a dict split per image key."""

    keys: Sequence[str]
    output_channels: Sequence[int]
    channels_multiplier: int
    cnn_encoder_output_dim: int
    image_size: Tuple[int, int]
    stages: int = 4
    layer_norm: bool = True
    eps: float = 1e-3
    act: Any = "silu"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        x = nn.Dense(self.cnn_encoder_output_dim, kernel_init=trunc_init, dtype=self.dtype)(latent)
        # sharding-critical: see batch_major_flatten
        x, lead = batch_major_flatten(x, 1)
        x = x.reshape(-1, 4, 4, (2 ** (self.stages - 1)) * self.channels_multiplier)
        for i in range(self.stages - 1):
            ch = (2 ** (self.stages - i - 2)) * self.channels_multiplier
            x = nn.ConvTranspose(
                ch,
                (4, 4),
                strides=(2, 2),
                padding=[(2, 2), (2, 2)],
                use_bias=not self.layer_norm,
                kernel_init=trunc_init,
                dtype=self.dtype,
            )(x)
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=self.eps)(x)  # f32 statistics
            x = resolve_activation(self.act)(x.astype(self.dtype))
        # final deconv emits f32 for the reconstruction distributions
        x = nn.ConvTranspose(
            int(sum(self.output_channels)),
            (4, 4),
            strides=(2, 2),
            padding=[(2, 2), (2, 2)],
            kernel_init=uniform_out_init(1.0),
        )(x.astype(jnp.float32))
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
    dense_units: int = 512
    layer_norm: bool = True
    eps: float = 1e-3
    act: Any = "silu"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        x = DreamerMLP(
            self.dense_units, self.mlp_layers, None, self.layer_norm, self.eps, self.act,
            dtype=self.dtype,
        )(latent)
        x = x.astype(jnp.float32)  # heads emit f32 for the dists
        return {
            k: nn.Dense(d, kernel_init=uniform_out_init(1.0))(x)
            for k, d in zip(self.keys, self.output_dims)
        }


class MultiDecoderDV3(nn.Module):
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
    """MLP projection -> LayerNormGRUCell (reference RecurrentModel:281)."""

    recurrent_state_size: int
    dense_units: int
    layer_norm: bool = True
    eps: float = 1e-3
    fused: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, inp: jax.Array, recurrent_state: jax.Array) -> jax.Array:
        feat = LinearLnAct(self.dense_units, self.layer_norm, self.eps, "silu", dtype=self.dtype)(inp)
        new_h, _ = LayerNormGRUCell(
            hidden_size=self.recurrent_state_size,
            use_bias=False,
            layer_norm=True,
            fused=self.fused,
            dtype=self.dtype,
        )(recurrent_state, feat)
        # the carried recurrent state stays f32 across scan steps
        return new_h.astype(jnp.float32)


def compute_stochastic_state(
    logits: jax.Array,
    discrete: int,
    key: Optional[jax.Array],
    sample: bool = True,
    noise: Optional[jax.Array] = None,
) -> jax.Array:
    """(..., stoch*discrete) logits -> (..., stoch, discrete) one-hot ST
    sample (reference dreamer_v2/utils.py:44).

    ``noise`` is pre-drawn Gumbel noise of the reshaped logits' shape: the
    categorical sample is then ``argmax(logits + noise)`` with the same
    straight-through estimator, and no RNG runs at the call site.  Used by
    the train scans, whose bodies are latency-bound — hoisting the threefry
    chains out of the ``lax.scan`` body batches all of a rollout's RNG into
    one fused op outside the sequential loop."""
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
    """Recurrent State-Space Model with discrete latents (reference RSSM:344).

    ``decoupled`` makes the posterior depend only on the embedded obs
    (reference DecoupledRSSM:501)."""

    actions_dim: Sequence[int]
    embedded_obs_dim: int
    recurrent_state_size: int
    dense_units: int
    stochastic_size: int = 32
    discrete_size: int = 32
    hidden_size: int = 1024
    unimix: float = 0.01
    layer_norm: bool = True
    eps: float = 1e-3
    act: Any = "silu"
    learnable_initial_recurrent_state: bool = True
    decoupled: bool = False
    fused_gru: bool = False
    fused_seq: bool = False
    dtype: Any = jnp.float32

    def setup(self) -> None:
        stoch = self.stochastic_size * self.discrete_size
        self.recurrent_model = RecurrentModel(
            recurrent_state_size=self.recurrent_state_size,
            dense_units=self.dense_units,
            layer_norm=self.layer_norm,
            eps=self.eps,
            fused=self.fused_gru,
            dtype=self.dtype,
        )
        self.representation_model = DreamerMLP(
            self.hidden_size, 1, stoch, self.layer_norm, self.eps, self.act, uniform_out_init(1.0),
            dtype=self.dtype,
        )
        self.transition_model = DreamerMLP(
            self.hidden_size, 1, stoch, self.layer_norm, self.eps, self.act, uniform_out_init(1.0),
            dtype=self.dtype,
        )
        if self.learnable_initial_recurrent_state:
            self.initial_recurrent_state = self.param(
                "initial_recurrent_state", nn.initializers.zeros, (self.recurrent_state_size,)
            )
        else:
            self.initial_recurrent_state = jnp.zeros((self.recurrent_state_size,))

    def recurrent_step(self, inp: jax.Array, recurrent_state: jax.Array) -> jax.Array:
        """Expose the recurrent model for the player's stateful step."""
        return self.recurrent_model(inp, recurrent_state)

    def init_all(self, posterior, recurrent_state, action, embedded_obs, is_first, key):
        """Initialization path touching every submodule (the decoupled
        dynamic skips the representation model)."""
        out = self.dynamic(posterior, recurrent_state, action, embedded_obs, is_first, key)
        if self.decoupled:
            self._representation(embedded_obs, key)
        return out

    def _uniform_mix(self, logits: jax.Array) -> jax.Array:
        logits = logits.reshape(*logits.shape[:-1], -1, self.discrete_size)
        if self.unimix > 0.0:
            probs = jax.nn.softmax(logits, -1)
            uniform = jnp.ones_like(probs) / self.discrete_size
            probs = (1 - self.unimix) * probs + self.unimix * uniform
            logits = jnp.log(probs)
        return logits.reshape(*logits.shape[:-2], -1)

    def get_initial_states(self, batch_shape: Sequence[int]) -> Tuple[jax.Array, jax.Array]:
        init_rec = jnp.broadcast_to(
            jnp.tanh(self.initial_recurrent_state), (*batch_shape, self.recurrent_state_size)
        )
        _, initial_posterior = self._transition(init_rec, sample_state=False, key=None)
        return init_rec, initial_posterior

    def _representation(
        self,
        embedded_obs: jax.Array,
        key: Optional[jax.Array],
        recurrent_state: Optional[jax.Array] = None,
        noise: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        if self.decoupled:
            x = embedded_obs
        else:
            x = jnp.concatenate([recurrent_state, embedded_obs], -1)
        logits = self._uniform_mix(self.representation_model(x))
        return logits, compute_stochastic_state(logits, self.discrete_size, key, noise=noise)

    def _transition(
        self,
        recurrent_out: jax.Array,
        key: Optional[jax.Array],
        sample_state: bool = True,
        noise: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        logits = self._uniform_mix(self.transition_model(recurrent_out))
        return logits, compute_stochastic_state(
            logits, self.discrete_size, key, sample=sample_state, noise=noise
        )

    def dynamic(
        self,
        posterior: jax.Array,
        recurrent_state: jax.Array,
        action: jax.Array,
        embedded_obs: jax.Array,
        is_first: jax.Array,
        key: Optional[jax.Array],
        noise: Optional[Tuple[jax.Array, jax.Array]] = None,
    ):
        """One dynamic-learning step with is_first-gated resets.

        ``noise`` — optional pre-drawn (prior_gumbel, posterior_gumbel) pair,
        see :func:`compute_stochastic_state`."""
        if noise is not None:
            k1 = k2 = None
            n1, n2 = noise
        else:
            k1, k2 = jax.random.split(key)
            n1 = n2 = None
        action = (1 - is_first) * action
        initial_recurrent_state, initial_posterior = self.get_initial_states(recurrent_state.shape[:-1])
        recurrent_state = (1 - is_first) * recurrent_state + is_first * initial_recurrent_state
        posterior = posterior.reshape(*posterior.shape[:-2], -1)
        posterior = (1 - is_first) * posterior + is_first * initial_posterior.reshape(posterior.shape)

        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        prior_logits, prior = self._transition(recurrent_state, k1, noise=n1)
        if self.decoupled:
            return recurrent_state, prior, prior_logits
        posterior_logits, posterior = self._representation(embedded_obs, k2, recurrent_state, noise=n2)
        return recurrent_state, posterior, prior, posterior_logits, prior_logits

    def representation_embed_proj(self, embedded_obs: jax.Array) -> jax.Array:
        """Embed-side half of the representation model's first matmul.

        The first Dense of the representation model sees ``[h_t, embed_t]``;
        splitting its kernel lets the (big) embed half run as ONE batched
        matmul over the whole sequence outside the train scan, while only
        the small h-side product stays on the sequential critical path.
        Crucially this also moves the (embed_dim, units) kernel-gradient
        accumulation out of the backward while-loop's carry."""
        p = self.representation_model.variables["params"]["LinearLnAct_0"]["Dense_0"]
        k_e = p["kernel"][self.recurrent_state_size:].astype(self.dtype)
        out = embedded_obs.astype(self.dtype) @ k_e
        if not self.layer_norm:
            out = out + p["bias"].astype(self.dtype)
        return out

    def _representation_from_proj(
        self,
        emb_proj: jax.Array,
        recurrent_state: jax.Array,
        noise: Optional[jax.Array] = None,
        key: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Posterior from a precomputed embed projection (scan-body path of
        :meth:`_representation`; non-decoupled only).  Manually unrolls the
        DreamerMLP(layers=1) block so the h-side product can be added to
        ``emb_proj`` before the LayerNorm."""
        params = self.representation_model.variables["params"]
        p = params["LinearLnAct_0"]["Dense_0"]
        k_h = p["kernel"][: self.recurrent_state_size].astype(self.dtype)
        x = recurrent_state.astype(self.dtype) @ k_h + emb_proj
        if self.layer_norm:
            x = ln_act_apply(
                params["LinearLnAct_0"]["LayerNorm_0"], x,
                eps=self.eps, act=self.act, dtype=self.dtype,
            )
        else:
            x = resolve_activation(self.act)(x.astype(self.dtype))
        head = params["Dense_0"]
        logits = x.astype(jnp.float32) @ head["kernel"] + head["bias"]
        logits = self._uniform_mix(logits)
        return logits, compute_stochastic_state(
            logits, self.discrete_size, key, noise=noise
        )

    def dynamic_posterior(
        self,
        posterior: jax.Array,
        recurrent_state: jax.Array,
        action: jax.Array,
        emb_proj: jax.Array,
        is_first: jax.Array,
        init_states: Tuple[jax.Array, jax.Array],
        key: Optional[jax.Array] = None,
        noise: Optional[jax.Array] = None,
    ):
        """The sequential-only slice of :meth:`dynamic` for the train scan.

        Two things are deliberately NOT here, because they are
        t-independent given ``h_t`` and batch over the whole sequence
        outside the ``lax.scan`` (the scan body is latency-bound, so every
        op removed from it is ~T ops removed from the critical path):

        - the transition model / prior — its logits are a pure function of
          the stacked recurrent states (and the prior SAMPLE is unused by
          the world-model loss);
        - the initial-state computation — ``get_initial_states`` runs the
          transition MLP on a constant, so it is evaluated once and passed
          in as ``init_states``.
        """
        init_rec, init_post = init_states
        action = (1 - is_first) * action
        recurrent_state = (1 - is_first) * recurrent_state + is_first * init_rec
        posterior = posterior.reshape(*posterior.shape[:-2], -1)
        posterior = (1 - is_first) * posterior + is_first * init_post.reshape(posterior.shape)
        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        posterior_logits, posterior = self._representation_from_proj(
            emb_proj, recurrent_state, noise=noise, key=key
        )
        return recurrent_state, posterior, posterior_logits

    def recurrent_step_gated(
        self,
        prev_posterior: jax.Array,
        recurrent_state: jax.Array,
        action: jax.Array,
        is_first: jax.Array,
        init_states: Tuple[jax.Array, jax.Array],
    ) -> jax.Array:
        """Decoupled-RSSM scan body: is_first-gated reset + recurrent model
        only (posteriors are precomputed in batch, priors are batched over
        the stacked recurrent states outside the scan).

        Kept as the reference semantics for
        :meth:`recurrent_features_seq` + :meth:`gru_step_gated`, which split
        the same computation so the input projection leaves the scan; the
        identity is pinned by ``tests/test_models/test_models.py``."""
        init_rec, init_post = init_states
        action = (1 - is_first) * action
        recurrent_state = (1 - is_first) * recurrent_state + is_first * init_rec
        prev = prev_posterior.reshape(*prev_posterior.shape[:-2], -1)
        prev = (1 - is_first) * prev + is_first * init_post.reshape(prev.shape)
        return self.recurrent_model(
            jnp.concatenate([prev, action], -1), recurrent_state
        )

    def recurrent_features_seq(
        self,
        prev_posteriors: jax.Array,
        actions: jax.Array,
        is_first: jax.Array,
        init_post: jax.Array,
    ) -> jax.Array:
        """is_first-gated inputs + the recurrent model's input projection,
        batched over the whole (T, B) sequence.

        The projection sees only ``[z_{t-1}, a_t]`` — never ``h`` — so when
        every posterior is known up front (DecoupledRSSM: the posterior
        depends only on the embedded obs, reference DecoupledRSSM:501) the
        whole Dense+LN+SiLU block runs as ONE matmul over T*B rows instead
        of T sequential (B, .) matmuls inside the scan, and its
        kernel-gradient accumulation leaves the backward while-loop's carry
        (same argument as :meth:`representation_embed_proj`)."""
        prev = prev_posteriors.reshape(*prev_posteriors.shape[:-2], -1)
        # init_post: (B, stoch, discrete) or (B, stoch*discrete) -> (B, N),
        # broadcasting against prev's (T, B, N)
        prev = (1 - is_first) * prev + is_first * init_post.reshape(init_post.shape[0], -1)
        actions = (1 - is_first) * actions
        inp = jnp.concatenate([prev, actions], -1)
        return linear_ln_act_apply(
            self.recurrent_model.variables["params"]["LinearLnAct_0"],
            inp,
            layer_norm=self.layer_norm,
            eps=self.eps,
            act="silu",  # RecurrentModel hard-codes silu for its projection
            dtype=self.dtype,
        )

    def gru_step_gated(
        self,
        feat: jax.Array,
        recurrent_state: jax.Array,
        is_first: jax.Array,
        init_rec: jax.Array,
    ) -> jax.Array:
        """The sequential residue of :meth:`recurrent_step_gated` once
        :meth:`recurrent_features_seq` has batched the input projection:
        is_first-gated state reset + one GRU cell step."""
        recurrent_state = (1 - is_first) * recurrent_state + is_first * init_rec
        p = self.recurrent_model.variables["params"]["LayerNormGRUCell_0"]
        return gru_cell_apply(
            p, recurrent_state, feat, fused=self.fused_gru, dtype=self.dtype
        ).astype(jnp.float32)

    def seq_scan_eligible(self, feat_dim: int) -> bool:
        """Is the one-kernel sequence GRU usable for this model size?"""
        from sheeprl_tpu.ops.seq_gru import fits_vmem

        # no layer_norm condition: the GRU cell's LN is unconditional in
        # RecurrentModel (self.layer_norm only governs the MLP blocks)
        return (
            self.fused_seq
            and self.recurrent_state_size % 128 == 0
            and feat_dim % 128 == 0
            and fits_vmem(self.recurrent_state_size, feat_dim, self.dtype)
        )

    def gru_sequence_gated(
        self,
        feats: jax.Array,
        is_first: jax.Array,
        init_rec: jax.Array,
    ) -> jax.Array:
        """The whole decoupled dynamic recurrence in ONE Pallas kernel: T
        is_first-gated GRU steps with the weight matrix VMEM-resident
        (ops/seq_gru.py). Semantically identical to scanning
        :meth:`gru_step_gated` over ``feats`` from a zero carry."""
        from sheeprl_tpu.ops.seq_gru import gru_sequence

        p = self.recurrent_model.variables["params"]["LayerNormGRUCell_0"]
        h0 = jnp.zeros((feats.shape[1], self.recurrent_state_size))
        dt = self.dtype

        def _run(interpret: bool):
            def f(h0_, xs, w, g, b, fi, ir):
                return gru_sequence(h0_, xs, w, g, b, fi, ir, 1e-6, interpret, dt)

            return f

        # interpret mode per lowering platform (tests/CPU players), same
        # pattern as gru_cell_apply
        return jax.lax.platform_dependent(
            h0,
            feats,
            p["Dense_0"]["kernel"],
            p["LayerNorm_0"]["scale"],
            p["LayerNorm_0"]["bias"],
            is_first.astype(jnp.float32),
            init_rec,
            tpu=_run(False),
            default=_run(True),
        )

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
    """DV3 actor: trunk MLP + per-subaction heads with unimix'd ST one-hot
    dists (discrete) or scaled-Normal (continuous) (reference Actor:694)."""

    actions_dim: Sequence[int]
    is_continuous: bool
    distribution: str = "auto"
    init_std: float = 0.0
    min_std: float = 0.1
    max_std: float = 1.0
    dense_units: int = 1024
    mlp_layers: int = 5
    layer_norm: bool = True
    eps: float = 1e-3
    act: Any = "silu"
    unimix: float = 0.01
    action_clip: float = 1.0
    dtype: Any = jnp.float32

    def _dist_name(self) -> str:
        d = self.distribution.lower()
        if d == "auto":
            return "scaled_normal" if self.is_continuous else "discrete"
        return d

    def _uniform_mix(self, logits: jax.Array) -> jax.Array:
        if self.unimix > 0.0:
            probs = jax.nn.softmax(logits, -1)
            uniform = jnp.ones_like(probs) / probs.shape[-1]
            probs = (1 - self.unimix) * probs + self.unimix * uniform
            logits = jnp.log(probs)
        return logits

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
            x = LinearLnAct(self.dense_units, self.layer_norm, self.eps, self.act, dtype=self.dtype)(x)
        x = x.astype(jnp.float32)  # dist heads in f32
        if self.is_continuous:
            pre = nn.Dense(int(np.sum(self.actions_dim)) * 2, kernel_init=uniform_out_init(1.0))(x)
            mean, std = jnp.split(pre, 2, -1)
            name = self._dist_name()
            if name == "tanh_normal":
                mean = 5 * jnp.tanh(mean / 5)
                std = jax.nn.softplus(std + self.init_std) + self.min_std
                dist = Independent(TanhNormal(mean, std), 1)
            elif name == "normal":
                dist = Independent(Normal(mean, std), 1)
            elif name == "scaled_normal":
                std = (self.max_std - self.min_std) * jax.nn.sigmoid(std + self.init_std) + self.min_std
                dist = Independent(Normal(jnp.tanh(mean), std), 1)
            else:
                raise ValueError(f"Bad continuous distribution: {name}")
            if greedy:
                # reference samples 100 and keeps the argmax-log-prob one;
                # for these unimodal dists the mean is that argmax
                actions = dist.mean
            else:
                actions = dist.rsample(key)
            if self.action_clip > 0.0:
                clip = jnp.full_like(actions, self.action_clip)
                actions = actions * jax.lax.stop_gradient(
                    clip / jnp.maximum(clip, jnp.abs(actions))
                )
            return (actions,), (dist,)
        heads = [
            nn.Dense(d, kernel_init=uniform_out_init(1.0))(x) for d in self.actions_dim
        ]
        actions: List[jax.Array] = []
        dists = []
        keys = jax.random.split(key, len(heads)) if key is not None else [None] * len(heads)
        # MineDojo-style conditional masks (reference MinedojoActor:848,
        # vectorized instead of python loops over the batch): head 0 gets
        # the action-type mask; head 1 (craft item) is constrained only when
        # the sampled functional action is craft (15); head 2 (inventory
        # slot) only for equip/place (16/17) or destroy (18)
        functional_action = None
        for i, logits in enumerate(heads):
            logits = self._uniform_mix(logits)
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


# cfg.algo.actor.cls target for MineDojo runs (reference MinedojoActor:848);
# the conditional-mask logic lives directly in Actor's discrete branch, so
# the Minedojo variant is the same module
MinedojoActor = Actor


class WorldModel:
    """Container of the world-model modules sharing one params tree
    (reference dreamer_v2/agent.py WorldModel:707)."""

    def __init__(self, encoder, rssm, observation_model, reward_model, continue_model):
        self.encoder = encoder
        self.rssm = rssm
        self.observation_model = observation_model
        self.reward_model = reward_model
        self.continue_model = continue_model


class PlayerDV3:
    """Stateful env-interaction wrapper: carries per-env (actions,
    recurrent_state, stochastic_state), masked-reset on dones
    (reference PlayerDV3:596). The RSSM step + actor sampling is one jitted
    function, optionally pinned to the host CPU backend."""

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
        decoupled_rssm: bool = False,
        actor_type: Optional[str] = None,
        device=None,
    ):
        self.wm = world_model
        self.actor_module = actor
        self.actions_dim = tuple(actions_dim)
        self.num_envs = num_envs
        self.stochastic_size = stochastic_size
        self.discrete_size = discrete_size
        self.recurrent_state_size = recurrent_state_size
        self.decoupled_rssm = decoupled_rssm
        self.actor_type = actor_type
        self.device = device
        self.params = params  # {"world_model": ..., "actor": ...}

        def _step(params, obs, prev_actions, recurrent_state, stochastic_state, key, greedy):
            embedded_obs = self.wm.encoder.apply(params["world_model"]["encoder"], obs)
            recurrent_state = self.wm.rssm.apply(
                params["world_model"]["rssm"],
                jnp.concatenate([stochastic_state, prev_actions], -1),
                recurrent_state,
                method=RSSM.recurrent_step,
            )
            k1, k2 = jax.random.split(key)
            if self.decoupled_rssm:
                _, stoch = self.wm.rssm.apply(
                    params["world_model"]["rssm"], embedded_obs, k1, method=RSSM._representation
                )
            else:
                _, stoch = self.wm.rssm.apply(
                    params["world_model"]["rssm"],
                    embedded_obs,
                    k1,
                    recurrent_state,
                    method=RSSM._representation,
                )
            stoch_flat = stoch.reshape(*stoch.shape[:-2], self.stochastic_size * self.discrete_size)
            actions, _ = self.actor_module.apply(
                params["actor"],
                jnp.concatenate([stoch_flat, recurrent_state], -1),
                greedy,
                k2,
            )
            return actions, jnp.concatenate(actions, -1), recurrent_state, stoch_flat

        def _reset(rssm_params, mask, actions, recurrent_state, stochastic_state):
            num_envs = mask.shape[0]
            rec, stoch = self.wm.rssm.apply(rssm_params, (1, num_envs), method=RSSM.get_initial_states)
            m = mask[None, :, None]
            return (
                jnp.where(m, 0.0, actions),
                jnp.where(m, rec.astype(recurrent_state.dtype), recurrent_state),
                jnp.where(m, stoch.reshape(1, num_envs, -1).astype(stochastic_state.dtype), stochastic_state),
            )

        self._step = jax.jit(_step, static_argnums=(6,))
        # one program for every reset, whichever envs are done, and what it
        # returns is laid out like the step's own outputs (with the
        # weights), so the step compiles once
        self._reset = jax.jit(_reset)
        self.init_states()
        # an episode's end hands the reset device arrays, not host zeros:
        # that program compiles here, not in the middle of a run
        self.init_states(range(num_envs))

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, value):
        self._params = place_player_params(value, self.device)

    def init_states(self, reset_envs: Optional[Sequence[int]] = None) -> None:
        """Reset the given envs' (actions, recurrent, stochastic) states, or
        with no argument every env's, from nothing and at the current
        ``num_envs`` (the test episode runs with one)."""
        if reset_envs is None or len(reset_envs) == 0:
            mask = np.ones((self.num_envs,), dtype=bool)
            states = tuple(
                np.zeros((1, self.num_envs, width), np.float32)
                for width in (
                    int(np.sum(self.actions_dim)),
                    self.recurrent_state_size,
                    self.stochastic_size * self.discrete_size,
                )
            )
        else:
            mask = np.zeros((self.num_envs,), dtype=bool)
            mask[np.asarray(reset_envs)] = True
            states = (self.actions, self.recurrent_state, self.stochastic_state)
        self.actions, self.recurrent_state, self.stochastic_state = self._reset(
            self._params["world_model"]["rssm"], mask, *states
        )

    def get_actions(
        self, obs: Dict[str, jax.Array], key: jax.Array, greedy: bool = False, mask=None
    ) -> Sequence[jax.Array]:
        if self.device is not None:
            obs = jax.device_put(obs, self.device)
            key = jax.device_put(key, self.device)
        actions, flat, self.recurrent_state, self.stochastic_state = self._step(
            self._params, obs, self.actions, self.recurrent_state, self.stochastic_state, key, greedy
        )
        self.actions = flat
        return actions


def build_player(
    runtime,
    world_model: WorldModel,
    actor: Actor,
    player_params: Dict[str, Any],
    actions_dim: Sequence[int],
    num_envs: int,
    cfg: Dict[str, Any],
    actor_type: Optional[str] = None,
) -> PlayerDV3:
    """The training loops' player over ``{"world_model", "actor"}`` params,
    sized by ``cfg`` and placed where ``runtime.player_device`` says."""
    return PlayerDV3(
        world_model,
        actor,
        player_params,
        actions_dim,
        num_envs,
        cfg.algo.world_model.stochastic_size,
        cfg.algo.world_model.recurrent_model.recurrent_state_size,
        discrete_size=cfg.algo.world_model.discrete_size,
        decoupled_rssm=bool(cfg.algo.world_model.decoupled_rssm),
        actor_type=actor_type,
        device=runtime.player_device(player_params),
    )


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
    """-> (world_model(WorldModel), actor(Actor), critic(DreamerMLP), params)

    ``params`` = {"world_model": {...}, "actor": ..., "critic": ...,
    "target_critic": ...}.
    """
    world_model_cfg = cfg.algo.world_model
    actor_cfg = cfg.algo.actor
    critic_cfg = cfg.algo.critic

    recurrent_state_size = world_model_cfg.recurrent_model.recurrent_state_size
    stochastic_size = world_model_cfg.stochastic_size * world_model_cfg.discrete_size
    latent_state_size = stochastic_size + recurrent_state_size

    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    cnn_stages = int(np.log2(cfg.env.screen_size) - np.log2(4))
    # fabric.precision policy: trunks compute in bf16 under *-mixed/true
    # (dist heads, LayerNorm statistics and the scan-carried states stay
    # f32 — see the per-module dtype notes)
    compute_dtype = runtime.compute_dtype

    cnn_encoder = (
        CNNEncoder(
            keys=cnn_keys,
            channels_multiplier=world_model_cfg.encoder.cnn_channels_multiplier,
            stages=cnn_stages,
            layer_norm=_ln_enabled(world_model_cfg.encoder.cnn_layer_norm),
            eps=_ln_eps(world_model_cfg.encoder.cnn_layer_norm),
            act="silu",
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
            layer_norm=_ln_enabled(world_model_cfg.encoder.mlp_layer_norm),
            eps=_ln_eps(world_model_cfg.encoder.mlp_layer_norm),
            dtype=compute_dtype,
        )
        if len(mlp_keys) > 0
        else None
    )
    encoder = MultiEncoderDV3(cnn_encoder, mlp_encoder)

    cnn_encoder_output_dim = (
        (2 ** (cnn_stages - 1)) * world_model_cfg.encoder.cnn_channels_multiplier * 4 * 4
        if cnn_encoder is not None
        else 0
    )
    mlp_encoder_output_dim = world_model_cfg.encoder.dense_units if mlp_encoder is not None else 0
    embedded_obs_dim = cnn_encoder_output_dim + mlp_encoder_output_dim

    rssm = RSSM(
        actions_dim=tuple(actions_dim),
        embedded_obs_dim=embedded_obs_dim,
        recurrent_state_size=recurrent_state_size,
        dense_units=world_model_cfg.recurrent_model.dense_units,
        stochastic_size=world_model_cfg.stochastic_size,
        discrete_size=world_model_cfg.discrete_size,
        hidden_size=world_model_cfg.transition_model.hidden_size,
        unimix=cfg.algo.unimix,
        layer_norm=_ln_enabled(world_model_cfg.recurrent_model.layer_norm),
        eps=_ln_eps(world_model_cfg.recurrent_model.layer_norm),
        learnable_initial_recurrent_state=world_model_cfg.learnable_initial_recurrent_state,
        decoupled=bool(world_model_cfg.decoupled_rssm),
        fused_gru=bool(world_model_cfg.recurrent_model.get("fused", False)),
        fused_seq=bool(world_model_cfg.recurrent_model.get("fused_seq", False)),
        dtype=compute_dtype,
    )

    cnn_decoder = (
        CNNDecoder(
            keys=tuple(cfg.algo.cnn_keys.decoder),
            output_channels=[int(obs_space[k].shape[-1]) for k in cfg.algo.cnn_keys.decoder],
            channels_multiplier=world_model_cfg.observation_model.cnn_channels_multiplier,
            cnn_encoder_output_dim=cnn_encoder_output_dim,
            image_size=tuple(obs_space[cfg.algo.cnn_keys.decoder[0]].shape[:2]),
            stages=cnn_stages,
            layer_norm=_ln_enabled(world_model_cfg.observation_model.cnn_layer_norm),
            eps=_ln_eps(world_model_cfg.observation_model.cnn_layer_norm),
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
            layer_norm=_ln_enabled(world_model_cfg.observation_model.mlp_layer_norm),
            eps=_ln_eps(world_model_cfg.observation_model.mlp_layer_norm),
            dtype=compute_dtype,
        )
        if len(cfg.algo.mlp_keys.decoder) > 0
        else None
    )
    observation_model = MultiDecoderDV3(cnn_decoder, mlp_decoder)

    reward_model = DreamerMLP(
        units=world_model_cfg.reward_model.dense_units,
        layers=world_model_cfg.reward_model.mlp_layers,
        output_dim=world_model_cfg.reward_model.bins,
        layer_norm=_ln_enabled(world_model_cfg.reward_model.layer_norm),
        eps=_ln_eps(world_model_cfg.reward_model.layer_norm),
        out_init=uniform_out_init(0.0),
        dtype=compute_dtype,
    )
    continue_model = DreamerMLP(
        units=world_model_cfg.discount_model.dense_units,
        layers=world_model_cfg.discount_model.mlp_layers,
        output_dim=1,
        layer_norm=_ln_enabled(world_model_cfg.discount_model.layer_norm),
        eps=_ln_eps(world_model_cfg.discount_model.layer_norm),
        out_init=uniform_out_init(1.0),
        dtype=compute_dtype,
    )
    world_model = WorldModel(encoder, rssm, observation_model, reward_model, continue_model)

    actor = Actor(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        distribution=cfg.distribution.get("type", "auto"),
        init_std=actor_cfg.init_std,
        min_std=actor_cfg.min_std,
        max_std=actor_cfg.get("max_std", 1.0),
        dense_units=actor_cfg.dense_units,
        mlp_layers=actor_cfg.mlp_layers,
        layer_norm=_ln_enabled(actor_cfg.layer_norm),
        eps=_ln_eps(actor_cfg.layer_norm),
        unimix=cfg.algo.unimix,
        action_clip=actor_cfg.action_clip,
        dtype=compute_dtype,
    )
    critic = DreamerMLP(
        units=critic_cfg.dense_units,
        layers=critic_cfg.mlp_layers,
        output_dim=critic_cfg.bins,
        layer_norm=_ln_enabled(critic_cfg.layer_norm),
        eps=_ln_eps(critic_cfg.layer_norm),
        out_init=uniform_out_init(0.0),
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
            method=RSSM.init_all,
        )
        wm_params = {
            "encoder": encoder.init(k(), dummy_obs),
            "rssm": rssm_params,
            "observation_model": observation_model.init(k(), dummy_latent),
            "reward_model": reward_model.init(k(), dummy_latent),
            "continue_model": continue_model.init(k(), dummy_latent),
        }
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
