"""SAC-AE agent (flax) — counterpart of reference
sheeprl/algos/sac_ae/agent.py (CNNEncoder:26, MLPEncoder:89, MLPDecoder:122,
CNNDecoder:153, SACAEQFunction:204, SACAECritic:226,
SACAEContinuousActor:240, SACAEAgent:321, SACAEPlayer:453, build_agent:505).

SAC with a pixel autoencoder (arXiv:1910.01741):
- conv stack [32]*4 * mult, kernel 3, strides [2, 1, 1, 1], VALID, NHWC,
  then Dense(features_dim) -> LayerNorm -> tanh;
- the ACTOR shares the critic encoder's conv weights but owns a private
  Dense head, and its gradients never touch the conv stack (the reference
  ties ``.model`` only and detaches conv features, agent.py:442-447, 77-83);
- delta-orthogonal conv init / orthogonal dense init (reference
  sac_ae/utils.py:79);
- decoder inverts the encoder, with the final transposed conv reproducing
  torch's ``output_padding=1`` via explicit ((2, 3), (2, 3)) pads.

Functional param layout:
``params = {critic: {encoder, qfs}, target: {encoder, qfs}, actor, decoder,
log_alpha}``; the weight tying of the reference is positional — the actor
and player read the conv weights out of ``params["critic"]["encoder"]``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.utils.utils import place_player_params

LOG_STD_MIN = -10.0
LOG_STD_MAX = 2.0

sg = jax.lax.stop_gradient

ortho_init = nn.initializers.orthogonal()


def delta_ortho_init(key, shape, dtype=jnp.float32):
    """Delta-orthogonal conv init (arXiv:1806.05393; reference
    sac_ae/utils.py:79): zero kernel with an orthogonal center tap, relu
    gain. Unlike jax's built-in it accepts fan_in > fan_out (orthogonal on
    the transposed matrix), matching torch's ``nn.init.orthogonal_``."""
    w = jnp.zeros(shape, dtype)
    center = nn.initializers.orthogonal(scale=float(np.sqrt(2.0)))(key, shape[-2:], dtype)
    return w.at[shape[0] // 2, shape[1] // 2].set(center)


class AEConvStack(nn.Module):
    """[32, 32, 32, 32] * mult, kernel 3, strides [2, 1, 1, 1], VALID,
    ReLU; flattens."""

    channels_multiplier: int = 1

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        for stride in (2, 1, 1, 1):
            x = nn.Conv(
                32 * self.channels_multiplier,
                (3, 3),
                strides=(stride, stride),
                padding="VALID",
                kernel_init=delta_ortho_init,
            )(x)
            x = nn.relu(x)
        return x.reshape(*x.shape[:-3], -1)


class AEFeatureHead(nn.Module):
    """Dense(features_dim) -> LayerNorm -> tanh (reference CNNEncoder.fc)."""

    features_dim: int

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = nn.Dense(self.features_dim, kernel_init=ortho_init)(x)
        x = nn.LayerNorm()(x)
        return jnp.tanh(x)


class AECNNEncoder(nn.Module):
    keys: Sequence[str]
    features_dim: int
    channels_multiplier: int = 1

    def setup(self) -> None:
        self.convnet = AEConvStack(self.channels_multiplier)
        self.head = AEFeatureHead(self.features_dim)

    def conv(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)
        return self.convnet(x)

    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        return self.head(self.conv(obs))


class AEMLPEncoder(nn.Module):
    keys: Sequence[str]
    dense_units: int = 64
    mlp_layers: int = 2
    layer_norm: bool = False

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], -1)
        for _ in range(self.mlp_layers):
            x = nn.Dense(self.dense_units, kernel_init=ortho_init)(x)
            if self.layer_norm:
                x = nn.LayerNorm()(x)
            x = nn.relu(x)
        return x


class AECNNDecoder(nn.Module):
    """fc -> (s4, s4, 32*mult) -> 3 VALID deconvs k3 s1 -> final deconv k3
    s2 with torch-style output_padding=1 (reference CNNDecoder:153)."""

    keys: Sequence[str]
    channels: Sequence[int]
    conv_output_shape: Tuple[int, int, int]  # (s4, s4, 32*mult)
    channels_multiplier: int = 1

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        lead = latent.shape[:-1]
        x = nn.Dense(int(np.prod(self.conv_output_shape)), kernel_init=ortho_init)(latent)
        x = x.reshape(-1, *self.conv_output_shape)
        for _ in range(3):
            x = nn.ConvTranspose(
                32 * self.channels_multiplier,
                (3, 3),
                strides=(1, 1),
                padding="VALID",
                kernel_init=delta_ortho_init,
            )(x)
            x = nn.relu(x)
        x = nn.ConvTranspose(
            int(sum(self.channels)),
            (3, 3),
            strides=(2, 2),
            padding=((2, 3), (2, 3)),
            kernel_init=delta_ortho_init,
        )(x)
        x = x.reshape(*lead, *x.shape[1:])
        out: Dict[str, jax.Array] = {}
        start = 0
        for k, c in zip(self.keys, self.channels):
            out[k] = x[..., start : start + c]
            start += c
        return out


class AEMLPDecoder(nn.Module):
    keys: Sequence[str]
    output_dims: Sequence[int]
    dense_units: int = 64
    mlp_layers: int = 2
    layer_norm: bool = False

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        x = latent
        for _ in range(self.mlp_layers):
            x = nn.Dense(self.dense_units, kernel_init=ortho_init)(x)
            if self.layer_norm:
                x = nn.LayerNorm()(x)
            x = nn.relu(x)
        return {
            k: nn.Dense(d, kernel_init=ortho_init)(x) for k, d in zip(self.keys, self.output_dims)
        }


class SACAEQFunction(nn.Module):
    hidden_size: int = 256

    @nn.compact
    def __call__(self, features: jax.Array, action: jax.Array) -> jax.Array:
        x = jnp.concatenate([features, action], -1)
        x = nn.relu(nn.Dense(self.hidden_size, kernel_init=ortho_init)(x))
        x = nn.relu(nn.Dense(self.hidden_size, kernel_init=ortho_init)(x))
        return nn.Dense(1, kernel_init=ortho_init)(x)


class SACAEActorTrunk(nn.Module):
    """MLP (hidden, hidden) + mean/logstd heads; logstd squashed into
    [LOG_STD_MIN, LOG_STD_MAX] by tanh rescale (reference
    SACAEContinuousActor:240)."""

    action_dim: int
    hidden_size: int = 1024

    @nn.compact
    def __call__(self, features: jax.Array) -> Tuple[jax.Array, jax.Array]:
        x = nn.relu(nn.Dense(self.hidden_size, kernel_init=ortho_init)(features))
        x = nn.relu(nn.Dense(self.hidden_size, kernel_init=ortho_init)(x))
        mean = nn.Dense(self.action_dim, kernel_init=ortho_init)(x)
        log_std = nn.Dense(self.action_dim, kernel_init=ortho_init)(x)
        log_std = jnp.tanh(log_std)
        log_std = LOG_STD_MIN + 0.5 * (LOG_STD_MAX - LOG_STD_MIN) * (log_std + 1)
        return mean, log_std


class SACAEModules:
    """Static container of the flax modules + action-space scaling."""

    def __init__(
        self,
        cnn_encoder: Optional[AECNNEncoder],
        mlp_encoder: Optional[AEMLPEncoder],
        actor_cnn_head: Optional[AEFeatureHead],
        actor_trunk: SACAEActorTrunk,
        qf: SACAEQFunction,
        cnn_decoder: Optional[AECNNDecoder],
        mlp_decoder: Optional[AEMLPDecoder],
        num_critics: int,
        action_low,
        action_high,
    ):
        self.cnn_encoder = cnn_encoder
        self.mlp_encoder = mlp_encoder
        self.actor_cnn_head = actor_cnn_head
        self.actor_trunk = actor_trunk
        self.qf = qf
        self.cnn_decoder = cnn_decoder
        self.mlp_decoder = mlp_decoder
        self.num_critics = num_critics
        self.action_scale = jnp.asarray((action_high - action_low) / 2.0, jnp.float32)
        self.action_bias = jnp.asarray((action_high + action_low) / 2.0, jnp.float32)

    # ------------------------------------------------------------- features
    def critic_features(self, enc_params, obs) -> jax.Array:
        feats = []
        if self.cnn_encoder is not None:
            feats.append(self.cnn_encoder.apply(enc_params["cnn"], obs))
        if self.mlp_encoder is not None:
            feats.append(self.mlp_encoder.apply(enc_params["mlp"], obs))
        return jnp.concatenate(feats, -1) if len(feats) > 1 else feats[0]

    def actor_features(self, enc_params, actor_params, obs) -> jax.Array:
        """Conv weights come (detached) from the critic encoder; the Dense
        head is the actor's own (reference agent.py:442-447 ties .model
        only; detach_encoder_features=True in the actor/critic calls of the
        actor update)."""
        feats = []
        if self.cnn_encoder is not None:
            conv = self.cnn_encoder.apply(enc_params["cnn"], obs, method=AECNNEncoder.conv)
            feats.append(self.actor_cnn_head.apply(actor_params["cnn_head"], sg(conv)))
        if self.mlp_encoder is not None:
            feats.append(sg(self.mlp_encoder.apply(enc_params["mlp"], obs)))
        return jnp.concatenate(feats, -1) if len(feats) > 1 else feats[0]

    # ------------------------------------------------------------- heads
    def q_values(self, qfs_params, features, actions) -> jax.Array:
        """(B, num_critics) — ensemble vmapped over stacked params."""
        q = jax.vmap(lambda p: self.qf.apply(p, features, actions))(qfs_params)  # (N, B, 1)
        return jnp.moveaxis(q[..., 0], 0, -1)

    def actions_and_log_probs(self, enc_params, actor_params, obs, key):
        mean, log_std = self.actor_trunk.apply(
            actor_params["trunk"], self.actor_features(enc_params, actor_params, obs)
        )
        std = jnp.exp(log_std)
        x = mean + std * jax.random.normal(key, mean.shape)
        y = jnp.tanh(x)
        action = y * self.action_scale + self.action_bias
        logp = -((x - mean) ** 2) / (2 * std**2) - log_std - 0.5 * jnp.log(2 * jnp.pi)
        logp = logp - jnp.log(self.action_scale * (1 - y**2) + 1e-6)
        return action, logp.sum(-1, keepdims=True)

    def greedy_actions(self, enc_params, actor_params, obs) -> jax.Array:
        mean, _ = self.actor_trunk.apply(
            actor_params["trunk"], self.actor_features(enc_params, actor_params, obs)
        )
        return jnp.tanh(mean) * self.action_scale + self.action_bias

    def decode(self, dec_params, latent) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder.apply(dec_params["cnn"], latent))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder.apply(dec_params["mlp"], latent))
        return out


class SACAEPlayer:
    """Env-interaction policy over the tied conv + private actor head
    (reference SACAEPlayer:453)."""

    def __init__(self, modules: SACAEModules, params, prepare_obs_fn, device=None):
        self.modules = modules
        self.prepare_obs_fn = prepare_obs_fn
        self.device = device
        self.params = params  # {"encoder": ..., "actor": ...}

        def _act(params, obs, key):
            a, _ = modules.actions_and_log_probs(params["encoder"], params["actor"], obs, key)
            return a

        def _greedy(params, obs):
            return modules.greedy_actions(params["encoder"], params["actor"], obs)

        self._act = jax.jit(_act)
        self._greedy = jax.jit(_greedy)

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, value):
        self._params = place_player_params(value, self.device)

    def get_actions(self, obs, key=None, greedy: bool = False):
        prepared = self.prepare_obs_fn(obs)
        if self.device is not None:
            prepared = jax.device_put(prepared, self.device)
            key = jax.device_put(key, self.device) if key is not None else None
        if greedy:
            return self._greedy(self._params, prepared)
        return self._act(self._params, prepared, key)


def build_agent(
    runtime,
    cfg: Dict[str, Any],
    obs_space,
    action_space,
    agent_state: Optional[Dict[str, Any]] = None,
):
    """-> (modules(SACAEModules), params, target_entropy)."""
    act_dim = int(np.prod(action_space.shape))
    target_entropy = -act_dim

    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    cnn_channels = [int(obs_space[k].shape[-1]) for k in cnn_keys]
    mlp_dims = [int(obs_space[k].shape[0]) for k in mlp_keys]
    screen_size = int(obs_space[cnn_keys[0]].shape[0]) if cnn_keys else 0
    mult = int(cfg.algo.encoder.cnn_channels_multiplier)

    cnn_encoder = (
        AECNNEncoder(
            keys=cnn_keys, features_dim=cfg.algo.encoder.features_dim, channels_multiplier=mult
        )
        if cnn_keys
        else None
    )
    mlp_encoder = (
        AEMLPEncoder(
            keys=mlp_keys,
            dense_units=cfg.algo.encoder.dense_units,
            mlp_layers=cfg.algo.encoder.mlp_layers,
            layer_norm=bool(cfg.algo.encoder.layer_norm),
        )
        if mlp_keys
        else None
    )

    # conv output spatial size: strides [2, 1, 1, 1], kernel 3, VALID
    if cnn_keys:
        s = (screen_size - 3) // 2 + 1
        for _ in range(3):
            s -= 2
        if s <= 0:
            raise ValueError(f"screen_size {screen_size} too small for the SAC-AE conv stack")
        if screen_size % 2 != 0:
            raise ValueError("SAC-AE decoder requires an even env.screen_size")
        conv_output_shape = (s, s, 32 * mult)
        cnn_features_dim = int(cfg.algo.encoder.features_dim)
    else:
        conv_output_shape = None
        cnn_features_dim = 0
    mlp_features_dim = cfg.algo.encoder.dense_units if mlp_encoder is not None else 0
    features_dim = cnn_features_dim + mlp_features_dim

    actor_cnn_head = AEFeatureHead(cfg.algo.encoder.features_dim) if cnn_keys else None
    actor_trunk = SACAEActorTrunk(action_dim=act_dim, hidden_size=cfg.algo.actor.hidden_size)
    qf = SACAEQFunction(hidden_size=cfg.algo.critic.hidden_size)
    num_critics = int(cfg.algo.critic.n)

    cnn_dec_keys = tuple(cfg.algo.cnn_keys.decoder)
    mlp_dec_keys = tuple(cfg.algo.mlp_keys.decoder)
    cnn_decoder = (
        AECNNDecoder(
            keys=cnn_dec_keys,
            channels=[int(obs_space[k].shape[-1]) for k in cnn_dec_keys],
            conv_output_shape=conv_output_shape,
            channels_multiplier=int(cfg.algo.decoder.cnn_channels_multiplier),
        )
        if len(cnn_dec_keys) > 0
        else None
    )
    mlp_decoder = (
        AEMLPDecoder(
            keys=mlp_dec_keys,
            output_dims=[int(obs_space[k].shape[0]) for k in mlp_dec_keys],
            dense_units=cfg.algo.decoder.dense_units,
            mlp_layers=cfg.algo.decoder.mlp_layers,
            layer_norm=bool(cfg.algo.decoder.layer_norm),
        )
        if len(mlp_dec_keys) > 0
        else None
    )

    modules = SACAEModules(
        cnn_encoder,
        mlp_encoder,
        actor_cnn_head,
        actor_trunk,
        qf,
        cnn_decoder,
        mlp_decoder,
        num_critics,
        action_space.low,
        action_space.high,
    )

    B = 1
    dummy_obs = {}
    for k in cnn_keys:
        dummy_obs[k] = jnp.zeros((B, *obs_space[k].shape), jnp.float32)
    for k in mlp_keys:
        dummy_obs[k] = jnp.zeros((B, *obs_space[k].shape), jnp.float32)
    dummy_feat = jnp.zeros((B, features_dim), jnp.float32)
    dummy_act = jnp.zeros((B, act_dim), jnp.float32)
    k = runtime.next_key

    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
        return modules, params, target_entropy

    enc_params = {}
    if cnn_encoder is not None:
        enc_params["cnn"] = cnn_encoder.init(k(), dummy_obs)
    if mlp_encoder is not None:
        enc_params["mlp"] = mlp_encoder.init(k(), dummy_obs)

    qfs_params = jax.vmap(lambda kk: qf.init(kk, dummy_feat, dummy_act))(
        jax.random.split(k(), num_critics)
    )
    actor_params = {"trunk": actor_trunk.init(k(), dummy_feat)}
    if actor_cnn_head is not None:
        conv_flat_dim = int(np.prod(conv_output_shape))
        actor_params["cnn_head"] = actor_cnn_head.init(k(), jnp.zeros((B, conv_flat_dim)))

    dec_params = {}
    if cnn_decoder is not None:
        dec_params["cnn"] = cnn_decoder.init(k(), dummy_feat)
        rec = cnn_decoder.apply(dec_params["cnn"], dummy_feat)
        for key_, c in zip(cnn_decoder.keys, cnn_decoder.channels):
            expect = (B, screen_size, screen_size, c)
            if rec[key_].shape != expect:
                raise RuntimeError(
                    f"SAC-AE decoder shape mismatch for '{key_}': {rec[key_].shape} != {expect}"
                )
    if mlp_decoder is not None:
        dec_params["mlp"] = mlp_decoder.init(k(), dummy_feat)

    params = {
        "critic": {"encoder": enc_params, "qfs": qfs_params},
        "target": {
            "encoder": jax.tree_util.tree_map(jnp.copy, enc_params),
            "qfs": jax.tree_util.tree_map(jnp.copy, qfs_params),
        },
        "actor": actor_params,
        "decoder": dec_params,
        "log_alpha": jnp.log(jnp.asarray([float(cfg.algo.alpha.alpha)], jnp.float32)),
    }
    return modules, params, target_entropy
