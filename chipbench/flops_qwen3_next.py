"""FLOPs and bytes one PPO minibatch step over whole causal episodes of the
Qwen3-Next policy needs, from the configuration's shapes (recompute excluded),
and the gated delta rule's own work.

A multiply-add is two operations.  Forward, per position of the ``S = P + R``
of an episode:

- a linear layer (Gated DeltaNet): the projections ``2 * hidden * (qkvz + ba)``
  and ``2 * Hv * dv * hidden`` (out), the convolution ``2 * K * channels``, and
  the delta rule (``gdn_forward_flops`` below);
- a full layer: q with its gate, k, v and o, ``2 * hidden * head_dim * (2 * heads
  + 2 * kv_heads) + 2 * heads * head_dim * hidden``, and attention, whatever
  implements it, ``2 * heads * (head_dim + head_dim)`` a (query, key) pair the
  causal mask leaves, the pairs COUNTED FROM THE MASK (``S (S + 1) / 2``);
- every layer's routed part: the router ``2 * hidden * router_width`` (all
  experts, always), the shared expert ``2 * 3 * hidden * shared_width`` and its
  gate ``2 * hidden``, and ``2 * 3 * hidden * f`` per *assignment to a held
  expert*, the count being the program's own counter over the window (else the
  expectation ``positions * top_k * held / router_width``).

Once per step: the head pass over the ``R`` response positions (with the value
head).  The backward pass needs twice the forward's products, so a step is three
forwards.  The embedding is a gather and counts nothing.

The delta rule, the chunked form at ``CHUNK`` positions (``gdn_forward_flops``),
per chunk: ``K K^T`` (its strict lower triangle) and ``Q K^T`` (the lower
triangle with the diagonal) once a key head; a unit lower-triangular solve with
``dv + dk`` right-hand columns, ``W S`` and ``Q S`` (``2 dk dv`` a position),
the masked ``(Q K^T * Gamma) V'`` (a triangle) and ``K^T V'`` (``2 dk dv`` a
position) once a value head.  The same work whatever implements it: the count
reads the algorithm's shapes, not the program's ops.  Its bytes
(``gdn_forward_bytes``): q, k, v, g, beta read and o written once each in
float32, and the state (``Hv dk dv`` in float32) once at every chunk boundary.
In the step the op runs forward, again in the block's rematerialised forward,
and backward at twice the forward: ``GDN_PASSES`` forwards' worth of both."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

CHUNK = 64
GDN_PASSES = 4  # forward, the rematerialised forward, the backward at twice a forward
F32 = 4


@dataclass(frozen=True)
class Qwen3NextShapes:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    key_heads: int
    value_heads: int
    d_k: int
    d_v: int
    conv_kernel: int
    interval: int
    layers: int
    expert_width: int
    shared_width: int
    router_width: int
    top_k: int
    experts_held: int
    vocab: int
    prompt: int
    response: int
    episodes: int  # a minibatch

    @classmethod
    def from_config(cls, config: dict, traffic: dict, tiny: bool = False) -> "Qwen3NextShapes":
        c = {**config, **(config.get("tiny_shapes", {}) if tiny else {})}
        t = {**traffic, **(traffic.get("tiny", {}) if tiny else {})}
        return cls(
            hidden=c["hidden_size"], heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], key_heads=c["linear_num_key_heads"], value_heads=c["linear_num_value_heads"],
            d_k=c["linear_key_head_dim"], d_v=c["linear_value_head_dim"], conv_kernel=c["linear_conv_kernel_dim"],
            interval=c["full_attention_interval"], layers=c["num_hidden_layers"], expert_width=c["moe_intermediate_size"],
            shared_width=c["shared_expert_intermediate_size"], router_width=c["router_width"],
            top_k=c["num_experts_per_tok"], experts_held=c["num_experts"], vocab=c["vocab_size"],
            prompt=t["prompt_len"], response=t["response_len"], episodes=t["minibatch_episodes"],
        )

    @property
    def positions(self) -> int:
        """One episode: prompt and response."""
        return self.prompt + self.response

    @property
    def frames(self) -> int:
        """Env steps (= response tokens) a minibatch step retires."""
        return self.episodes * self.response

    @property
    def full_layers(self) -> int:
        return self.layers // self.interval

    @property
    def linear_layers(self) -> int:
        return self.layers - self.full_layers

    @property
    def routed_blocks(self) -> int:
        return self.layers

    @property
    def channels(self) -> int:
        """The linear layer's convolution: q, k and v side by side."""
        return 2 * self.key_heads * self.d_k + self.value_heads * self.d_v


def pairs(n: int) -> int:
    """(query, key) pairs of one sequence of ``n`` positions under the causal mask."""
    return n * (n + 1) // 2


def expected_assignments(s: Qwen3NextShapes) -> float:
    """Assignments to held experts per routed block and step under even routing."""
    return s.episodes * s.positions * s.top_k * s.experts_held / s.router_width


def expert_flops_per_assignment(s: Qwen3NextShapes) -> int:
    return 2 * 3 * s.hidden * s.expert_width


def gdn_forward_flops(s: Qwen3NextShapes, n: Optional[int] = None) -> float:
    """The delta rule's chunked form over one sequence of ``n`` positions (an episode's by default), in one
    linear layer, forward."""
    n = s.positions if n is None else n
    chunks, c = -(-n // CHUNK), CHUNK
    per_key_head = (c * (c - 1) // 2 + c * (c + 1) // 2) * 2 * s.d_k  # K K^T below the diagonal, Q K^T on and below
    per_value_head = (c * (c - 1) * (s.d_v + s.d_k)  # the unit triangular solve, dv + dk columns
                      + 3 * c * 2 * s.d_k * s.d_v  # W S, Q S, K^T V'
                      + c * (c + 1) // 2 * 2 * s.d_v)  # (Q K^T * Gamma) V', on and below the diagonal
    return float(chunks * (s.key_heads * per_key_head + s.value_heads * per_value_head))


def gdn_forward_bytes(s: Qwen3NextShapes, n: Optional[int] = None) -> float:
    """Bytes the delta rule moves at least over one sequence in one linear layer, forward: q, k, v, g and beta
    read and o written once each, the state at every chunk boundary, float32."""
    n = s.positions if n is None else n
    per_position = 2 * s.key_heads * s.d_k + 2 * s.value_heads * s.d_v + 2 * s.value_heads
    return float(F32 * (n * per_position + -(-n // CHUNK) * s.value_heads * s.d_k * s.d_v))


def gdn_step(s: Qwen3NextShapes) -> Dict[str, float]:
    """The delta rule's FLOPs and bytes a minibatch step, every linear layer, forward + remat + backward."""
    scale = GDN_PASSES * s.linear_layers * s.episodes
    return {"flops": scale * gdn_forward_flops(s), "bytes": scale * gdn_forward_bytes(s)}


def roofline_seconds(flops: float, nbytes: float, peak_flops: float, peak_bytes: float) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bytes)


def forward_flops(s: Qwen3NextShapes, assignments: Optional[float] = None) -> Dict[str, float]:
    """``assignments``: to held experts, per routed block and step (the counter's mean); the
    even-routing expectation when None."""
    positions = s.episodes * s.positions
    if assignments is None:
        assignments = expected_assignments(s)
    qkvz, ba = s.channels + s.value_heads * s.d_v, 2 * s.value_heads
    out = {
        "gdn_projections": s.linear_layers * positions * (2.0 * s.hidden * (qkvz + ba) + 2.0 * s.value_heads * s.d_v * s.hidden
                                                          + 2.0 * s.conv_kernel * s.channels),
        "gdn_scan": s.linear_layers * s.episodes * gdn_forward_flops(s),
        "attn_projections": s.full_layers * positions * (2.0 * s.hidden * s.head_dim * (2 * s.heads + 2 * s.kv_heads)
                                                         + 2.0 * s.heads * s.head_dim * s.hidden),
        "attention": s.full_layers * s.episodes * 2.0 * s.heads * 2 * s.head_dim * pairs(s.positions),
        "router": s.routed_blocks * positions * 2.0 * s.hidden * s.router_width,
        "shared": s.routed_blocks * positions * (2.0 * 3 * s.hidden * s.shared_width + 2.0 * s.hidden),
        "experts": s.routed_blocks * float(assignments) * expert_flops_per_assignment(s),
        "head": s.frames * 2.0 * s.hidden * (s.vocab + 1),
    }
    out["total"] = sum(out.values())
    return out


def step_flops(s: Qwen3NextShapes, assignments: Optional[float] = None) -> Dict[str, float]:
    """Forward and backward of one minibatch step."""
    return {k: 3.0 * v for k, v in forward_flops(s, assignments).items()}
