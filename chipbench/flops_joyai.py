"""FLOPs one PPO + MTP minibatch step over whole causal episodes needs, from the
configuration's shapes (recompute excluded).

A multiply-add is two operations.  Forward, per position of the ``S = P + R``
of an episode, in every one of the ``layers + 1`` blocks (the trunk's and the
MTP module's):

- projections: the five products of latent attention, ``2 * (h*rq + rq*H*(dn+dr)
  + h*(rkv+dr) + rkv*H*(dn+dv) + H*dv*h)``;
- attention, in its UNABSORBED form under the causal mask, whatever implements
  it: ``S (S + 1) / 2`` (query, key) pairs an episode, ``2 * H * (dn + dr + dv)``
  a pair.  Lane padding inside a kernel is not needed work.

Per position: the dense MLP ``2 * 3 * h * intermediate`` in the leading dense
blocks; in every routed block (the trunk's and the MTP module's) the router
``2 * h * router_width`` (all experts, always), the shared expert ``2 * 3 * h *
shared_width`` and ``2 * 3 * h * f`` per *assignment to a held expert*, the
count of assignments being the program's own counter over the window, not the
expectation ``positions * top_k * held / router_width``: a skewed router then
cannot push a share of the peak over 100 %.  Once per step: the MTP module's
projection ``2 * 2h * h`` a position, and two head passes over the ``R`` response
positions (the policy's, with the value head; the MTP module's).

The backward pass needs twice the forward's products, so a step is three
forwards.  The embedding is a gather and counts nothing."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class MlaShapes:
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    dense_width: int
    expert_width: int
    shared_experts: int
    router_width: int
    top_k: int
    experts_held: int
    layers: int  # the trunk's blocks
    dense_layers: int
    mtp_modules: int
    vocab: int
    prompt: int
    response: int
    episodes: int  # a minibatch

    @classmethod
    def from_config(cls, config: dict, traffic: dict, tiny: bool = False) -> "MlaShapes":
        c = {**config, **(config.get("tiny_shapes", {}) if tiny else {})}
        t = {**traffic, **(traffic.get("tiny", {}) if tiny else {})}
        return cls(
            hidden=c["hidden_size"], heads=c["num_attention_heads"], q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
            nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"], dense_width=c["intermediate_size"],
            expert_width=c["moe_intermediate_size"], shared_experts=c["n_shared_experts"], router_width=c["router_width"],
            top_k=c["num_experts_per_tok"], experts_held=c["n_routed_experts"], layers=c["num_hidden_layers"],
            dense_layers=c["first_k_dense_replace"], mtp_modules=c["num_nextn_predict_layers"], vocab=c["vocab_size"],
            prompt=t["prompt_len"], response=t["response_len"], episodes=t["minibatch_episodes"],
        )

    @property
    def positions(self) -> int:
        """One episode: prompt and response under the causal mask."""
        return self.prompt + self.response

    @property
    def frames(self) -> int:
        """Env steps (= response tokens) a minibatch step retires."""
        return self.episodes * self.response

    @property
    def blocks(self) -> int:
        return self.layers + self.mtp_modules

    @property
    def routed_blocks(self) -> int:
        return self.layers - self.dense_layers + self.mtp_modules


def visible_pairs(s: MlaShapes) -> int:
    """(query, key) pairs of one episode under the causal mask."""
    return s.positions * (s.positions + 1) // 2


def expected_assignments(s: MlaShapes) -> float:
    """Assignments to held experts per routed block and step under even routing."""
    return s.episodes * s.positions * s.top_k * s.experts_held / s.router_width


def expert_flops_per_assignment(s: MlaShapes) -> int:
    return 2 * 3 * s.hidden * s.expert_width


def forward_flops(s: MlaShapes, assignments: Optional[float] = None) -> Dict[str, float]:
    """``assignments``: to held experts, per routed block and step (the
    counter's mean); the even-routing expectation when None."""
    positions = s.episodes * s.positions
    if assignments is None:
        assignments = expected_assignments(s)
    qk = s.nope + s.rope
    per_position = (s.hidden * s.q_rank + s.q_rank * s.heads * qk + s.hidden * (s.kv_rank + s.rope)
                    + s.kv_rank * s.heads * (s.nope + s.v_dim) + s.heads * s.v_dim * s.hidden)
    out = {
        "projections": s.blocks * positions * 2.0 * per_position,
        "attention": s.blocks * s.episodes * 2.0 * s.heads * (qk + s.v_dim) * visible_pairs(s),
        "dense_mlp": s.dense_layers * positions * 2.0 * 3 * s.hidden * s.dense_width,
        "router": s.routed_blocks * positions * 2.0 * s.hidden * s.router_width,
        "shared": s.routed_blocks * positions * 2.0 * 3 * s.hidden * s.shared_experts * s.expert_width,
        "experts": s.routed_blocks * float(assignments) * expert_flops_per_assignment(s),
        "mtp_projection": s.mtp_modules * positions * 2.0 * 2 * s.hidden * s.hidden,
        "head": s.frames * 2.0 * s.hidden * ((s.vocab + 1) + s.mtp_modules * s.vocab),
    }
    out["total"] = sum(out.values())
    return out


def step_flops(s: MlaShapes, assignments: Optional[float] = None) -> Dict[str, float]:
    """Forward and backward of one minibatch step."""
    return {k: 3.0 * v for k, v in forward_flops(s, assignments).items()}
