"""FLOPs one PPO minibatch step over packed denoising trajectories needs, from
the configuration's shapes and the mask rule (recompute excluded).

A multiply-add is two operations.  Forward, per packed position and layer:

- projections: q, k, v and the output product, ``2 * (h * Hq*D + 2 * h * Hkv*D + Hq*D * h)``;
- router: ``2 * h * router_width`` (all experts, always);
- experts: ``2 * 3 * h * f`` per *assignment to a held expert*.  The count of
  assignments is the program's own counter over the window, not the
  expectation ``positions * top_k * held / router_width``: a skewed router
  then cannot push a share of the peak over 100 %;
- attention: ``4 * Hq * D`` per (query, visible key) pair, the pairs counted
  from the mask rule (``visible_pairs``), not from the tiles visited.

Once per step: the head and the value head at the action positions.  The
backward pass needs twice the forward's products (dX and dW of every product;
dQ, dK, dV and dP against the forward's two in attention), so a step is three
forwards.  The embedding is a gather and counts nothing."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class SdarShapes:
    hidden: int
    q_heads: int
    kv_heads: int
    head_dim: int
    router_width: int
    top_k: int
    experts_held: int
    expert_width: int
    layers: int
    vocab: int
    prompt: int
    response: int
    block: int
    steps: int
    episodes: int  # a minibatch

    @classmethod
    def from_config(cls, config: dict, traffic: dict, tiny: bool = False) -> "SdarShapes":
        c = {**config, **(config.get("tiny_shapes", {}) if tiny else {})}
        t = {**traffic, **(traffic.get("tiny", {}) if tiny else {})}
        return cls(
            hidden=c["hidden_size"], q_heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], router_width=c["router_width"], top_k=c["num_experts_per_tok"],
            experts_held=c["num_experts"], expert_width=c["moe_intermediate_size"], layers=c["num_hidden_layers"],
            vocab=c["vocab_size"], prompt=t["prompt_len"], response=t["response_len"], block=c["block_length"],
            steps=c["denoise_steps"], episodes=t["minibatch_episodes"],
        )

    @property
    def packed_positions(self) -> int:
        """One episode: the clean sequence and every step's noised copy of its block."""
        return self.prompt + self.response + self.steps * self.response

    @property
    def frames(self) -> int:
        """Env steps (= revealed response tokens) a minibatch step retires."""
        return self.episodes * self.response


def visible_pairs(s: SdarShapes) -> int:
    """(query, key) pairs of one packed episode that the mask lets through.
    A clean position of block ``b`` sees the ``block * (b + 1)`` clean
    positions of blocks ``<= b``; a noised position of response block ``r``
    sees the clean positions before its block and its own copy's ``block``."""
    clean_blocks = (s.prompt + s.response) // s.block
    clean = sum(s.block * s.block * (b + 1) for b in range(clean_blocks))
    noised = sum(s.steps * s.block * (s.prompt + r * s.block + s.block) for r in range(s.response // s.block))
    return clean + noised


def expected_assignments(s: SdarShapes) -> float:
    """Assignments to held experts per layer and step under even routing."""
    return s.episodes * s.packed_positions * s.top_k * s.experts_held / s.router_width


def expert_flops_per_assignment(s: SdarShapes) -> int:
    return 2 * 3 * s.hidden * s.expert_width


def forward_flops(s: SdarShapes, assignments: Optional[float] = None) -> Dict[str, float]:
    """``assignments``: to held experts, per layer and step (the counter's
    mean); the even-routing expectation when None."""
    positions = s.episodes * s.packed_positions
    if assignments is None:
        assignments = expected_assignments(s)
    qd, kvd = s.q_heads * s.head_dim, s.kv_heads * s.head_dim
    out = {
        "projections": s.layers * positions * 2.0 * (s.hidden * qd + 2 * s.hidden * kvd + qd * s.hidden),
        "router": s.layers * positions * 2.0 * s.hidden * s.router_width,
        "experts": s.layers * float(assignments) * expert_flops_per_assignment(s),
        "attention": s.layers * s.episodes * 4.0 * qd * visible_pairs(s),
        "head": s.frames * 2.0 * s.hidden * (s.vocab + 1),
    }
    out["total"] = sum(out.values())
    return out


def step_flops(s: SdarShapes, assignments: Optional[float] = None) -> Dict[str, float]:
    """Forward and backward of one minibatch step."""
    return {k: 3.0 * v for k, v in forward_flops(s, assignments).items()}
