"""FLOPs one DreamerV3 update needs, from the configuration's shapes alone.

What counts: the multiply-adds of every dense and convolution layer (2 FLOPs
each), forward once, and twice more where the layer's parameters or inputs
take a gradient.  What does not count: recomputation (the two rematted
scans run their forward twice; the chip does that work, the algorithm does
not need it), LayerNorm, activations, the optimizer, sampling.  XLA's cost
analysis counts the recompute and is not used.

The layer list follows ``sheeprl_tpu/algos/dreamer_v3/agent.py`` (encoder:
4x4 stride-2 convs with channels m,2m,4m,8m; RSSM: dense -> LayerNorm GRU,
one-hidden-layer transition and representation heads; decoder: dense ->
transposed convs; reward / continue / actor / critic MLPs of ``mlp_layers``
x ``dense_units``) and the update in ``dreamer_v3.py``: world model forward
and backward over B*T frames; an imagination rollout of ``horizon`` steps
from every one of the B*T posterior states, forward only for a discrete
actor (REINFORCE: no gradient runs through the dynamics); then, over the
(horizon+1)*B*T imagined latents, reward, continue and critic forward, the
actor forward and backward (the policies are recomputed on the detached
trajectory), and over horizon*B*T the critic forward and backward and the
target critic forward."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class DV3Shapes:
    batch: int  # global batch B
    seq_len: int  # T
    horizon: int
    image: int  # square side, pixels
    channels: int
    cnn_mult: int
    recurrent: int
    dense: int
    mlp_layers: int
    hidden: int  # transition / representation hidden size
    stochastic: int
    discrete: int
    actions: int
    reward_bins: int = 255
    critic_bins: int = 255
    continuous_actions: bool = False

    @classmethod
    def from_config(cls, config: dict, global_batch: int) -> "DV3Shapes":
        s = config["shapes"]
        return cls(
            batch=int(global_batch),
            seq_len=int(s["per_rank_sequence_length"]),
            horizon=int(s["horizon"]),
            image=int(s["screen_size"]),
            channels=int(s["image_channels"]),
            cnn_mult=int(s["cnn_channels_multiplier"]),
            recurrent=int(s["recurrent_state_size"]),
            dense=int(s["dense_units"]),
            mlp_layers=int(s["mlp_layers"]),
            hidden=int(s["hidden_size"]),
            stochastic=int(s["stochastic_size"]),
            discrete=int(s["discrete_size"]),
            actions=int(s["actions_dim"]),
            reward_bins=int(s.get("reward_bins", 255)),
            critic_bins=int(s.get("critic_bins", 255)),
        )


def _stages(image: int) -> int:
    n, side = 0, image
    while side > 4:
        side //= 2
        n += 1
    return n


def _mlp_macs(inp: int, units: int, layers: int, out: int) -> int:
    return inp * units + (layers - 1) * units * units + units * out


def macs_per_row(s: DV3Shapes) -> Dict[str, int]:
    """Multiply-adds of one forward pass of each part, for ONE row (one
    frame, one latent or one imagination step)."""
    stages = _stages(s.image)
    stoch = s.stochastic * s.discrete
    latent = stoch + s.recurrent
    embed = 4 * 4 * (2 ** (stages - 1)) * s.cnn_mult

    enc, side, cin = 0, s.image, s.channels
    for i in range(stages):
        side //= 2
        cout = (2**i) * s.cnn_mult
        enc += side * side * 16 * cin * cout  # per output pixel: 4x4xCin MACs per output channel
        cin = cout
    dec = latent * embed
    side, cin = 4, (2 ** (stages - 1)) * s.cnn_mult
    for i in range(stages):
        cout = (2 ** (stages - i - 2)) * s.cnn_mult if i < stages - 1 else s.channels
        dec += side * side * 16 * cin * cout  # transposed: each input pixel feeds 4x4xCout
        side *= 2
        cin = cout
    recurrent = (stoch + s.actions) * s.dense + (s.dense + s.recurrent) * 3 * s.recurrent
    transition = s.recurrent * s.hidden + s.hidden * stoch
    representation = (s.recurrent + embed) * s.hidden + s.hidden * stoch
    actor_out = s.actions * (2 if s.continuous_actions else 1)
    return {
        "encoder": enc,
        "decoder": dec,
        "recurrent": recurrent,
        "transition": transition,
        "representation": representation,
        "reward": _mlp_macs(latent, s.dense, s.mlp_layers, s.reward_bins),
        "continue": _mlp_macs(latent, s.dense, s.mlp_layers, 1),
        "actor": _mlp_macs(latent, s.dense, s.mlp_layers, actor_out),
        "critic": _mlp_macs(latent, s.dense, s.mlp_layers, s.critic_bins),
    }


def update_flops(s: DV3Shapes) -> Dict[str, float]:
    """FLOPs one update needs, by part, and their ``total``."""
    m = macs_per_row(s)
    frames = s.batch * s.seq_len
    imagined = (s.horizon + 1) * frames
    fwd_bwd = 3  # forward + gradient w.r.t. inputs + gradient w.r.t. weights
    world_model = fwd_bwd * frames * (
        m["encoder"] + m["recurrent"] + m["transition"] + m["representation"]
        + m["decoder"] + m["reward"] + m["continue"]
    )
    rollout_passes = fwd_bwd if s.continuous_actions else 1
    rollout = rollout_passes * s.horizon * frames * (m["recurrent"] + m["transition"] + m["actor"])
    heads_on_imagined = imagined * (m["reward"] + m["continue"] + m["critic"])
    backward = fwd_bwd - 1
    actor = backward * imagined * m["actor"]
    critic = s.horizon * frames * (backward * m["critic"] + m["critic"])  # + target critic forward
    parts = {
        "world_model": 2.0 * world_model,
        "imagination_rollout": 2.0 * rollout,
        "heads_on_imagined": 2.0 * heads_on_imagined,
        "actor": 2.0 * actor,
        "critic": 2.0 * critic,
    }
    parts["total"] = sum(parts.values())
    return parts


def mfu_percent(flops_per_step: float, steps_per_s: float, chips: int, peak_flops_per_s: float) -> float:
    return 100.0 * flops_per_step * steps_per_s / (chips * peak_flops_per_s)
