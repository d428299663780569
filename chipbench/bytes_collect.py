"""Bytes one cached forward pass of collection has to read from HBM, from the
configuration's shapes, whatever implements the pass (``flops_sdar.py`` and
``flops_joyai.py`` count the update's operations the same way).

A pass of collection runs the model over a handful of positions (48 in
``sdar_ep8_loop``: 12 envs x a block of 4), so it is bound by what it reads, not
by what it computes: every parameter of every block once, at the width the
configuration computes in (bf16 under ``bf16-mixed``: 2 bytes, however the
program stores or casts its weights), and the cache of the positions so far.
The positions' own activations (48 x 2048 numbers a product) are noise beside
that and count nothing; neither does the embedding, a gather of 48 rows.  The
head is read by the passes that are scored (SDAR's fifth pass of a block, which
only commits the block to the cache, is not).

A pass needs the weights of the held experts its rows reach, and no others: the
grouped product skips an expert without rows.  ``reached`` is that number, the
mean over a rollout's cached passes and routed layers, COUNTED by
``experts_reached`` from the routing of a recorded rollout (the driver hands it
the update's routing choice over the first rollout's episodes, position by
position; the cached passes chose the same but for the few per cent of near
ties that bf16 rounding flips).  Without a count (``reached=None``) every held
expert is taken as read: an upper bound on the bytes, and on the share of the
roofline that rests on them."""

from __future__ import annotations

from typing import Dict, Optional

from chipbench.flops_joyai import MlaShapes
from chipbench.flops_sdar import SdarShapes

COMPUTE_BYTES = {"bf16-mixed": 2, "bf16-true": 2, "32-true": 4}


def sdar_pass_of_position(s: SdarShapes):
    """The cached pass of collection that ran each position of a packed episode
    (``reference.packed_layout``: the clean sequence, then every response
    block's ``steps`` noised copies): copy ``j`` of block ``b`` is denoising pass
    ``j`` of that block, the block's clean positions are its fifth pass, which
    commits it; -1 for the prompt (the prefill)."""
    import numpy as np

    n_blocks = s.response // s.block
    clean = np.repeat(np.arange(n_blocks) * (s.steps + 1) + s.steps, s.block)
    copies = np.repeat(np.arange(n_blocks)[:, None] * (s.steps + 1) + np.arange(s.steps)[None, :], s.block)
    return np.concatenate([np.full(s.prompt, -1), clean, copies])


def mla_pass_of_position(s: MlaShapes):
    """Causal: response token ``t`` is appended by cached pass ``t``; -1 for the prompt."""
    import numpy as np

    return np.concatenate([np.full(s.prompt, -1), np.arange(s.response)])


def experts_reached(policy: str, shapes, top_i, offset: int) -> float:
    """Distinct held experts (``offset`` .. ``offset + experts_held``) that the
    rows of one cached pass reach in one routed layer, the mean over the
    rollout's cached passes and the trunk's routed layers.  ``top_i``: (routed
    layers, envs, positions of an episode, k) expert ids, the trunk's layers
    first (the causal policy's multi-token-prediction module, which collection
    does not run, after them)."""
    import numpy as np

    pass_of = PASS_OF[policy](shapes)
    top_i = np.asarray(top_i)
    if top_i.shape[2] != pass_of.shape[0]:
        raise ValueError(f"routing over {top_i.shape[2]} positions an episode, the layout has {pass_of.shape[0]}")
    layers = top_i.shape[0] - getattr(shapes, "mtp_modules", 0)
    held, n_passes = shapes.experts_held, int(pass_of.max()) + 1
    total = 0
    for layer in range(layers):
        local = top_i[layer] - offset  # (envs, positions, k)
        ok = (local >= 0) & (local < held) & (pass_of[None, :, None] >= 0)
        total += np.unique((np.broadcast_to(pass_of[None, :, None], local.shape)[ok] * held + local[ok])).size
    return total / (layers * n_passes)


def sdar_rollout_bytes(s: SdarShapes, envs: int, width: int = 2, reached: Optional[float] = None) -> Dict[str, float]:
    """One rollout of the block-diffusion policy: ``n_blocks x (steps + 1)``
    cached passes over a block (the prefill aside), ``n_blocks x steps`` of them
    scored.  ``layers``: a pass's parameters; ``head``: a scored pass's;
    ``cache``: keys and values of the clean positions before the block, the
    mean over the rollout's blocks; ``rollout``: all of it for one rollout."""
    qd, kvd = s.q_heads * s.head_dim, s.kv_heads * s.head_dim
    attention = s.hidden * qd + 2 * s.hidden * kvd + qd * s.hidden + 2 * s.head_dim  # q, k, v, o and the two head norms
    experts = (s.experts_held if reached is None else reached) * 3 * s.hidden * s.expert_width
    layer = attention + s.hidden * s.router_width + experts + 2 * s.hidden  # and the block's two norms
    n_blocks = s.response // s.block
    passes, scored = n_blocks * (s.steps + 1), n_blocks * s.steps
    mean_length = s.prompt + s.block * (n_blocks - 1) / 2.0
    out = {
        "passes": passes, "scored_passes": scored, "positions": envs * (s.prompt + passes * s.block),  # the prefill's too
        "layers": float(width * s.layers * layer),
        "head": float(width * (s.hidden * (s.vocab + 1) + s.hidden)),  # head, value head, final norm
        "cache": float(width * s.layers * 2 * envs * mean_length * kvd),
    }
    out["rollout"] = passes * (out["layers"] + out["cache"]) + scored * out["head"]
    return out


def mla_rollout_bytes(s: MlaShapes, envs: int, width: int = 2, reached: Optional[float] = None) -> Dict[str, float]:
    """One rollout of the causal policy: one cached, scored pass a response
    token through the trunk's blocks (the multi-token-prediction module does
    not run in collection); the cache is the latent and the shared rotary key
    of every position so far."""
    heads = s.heads
    attention = (s.hidden * s.q_rank + s.q_rank * heads * (s.nope + s.rope) + s.hidden * (s.kv_rank + s.rope)
                 + s.kv_rank * heads * (s.nope + s.v_dim) + heads * s.v_dim * s.hidden + s.q_rank + s.kv_rank)
    dense = 3 * s.hidden * s.dense_width
    routed = (s.hidden * s.router_width + s.router_width + s.shared_experts * 3 * s.hidden * s.expert_width
              + (s.experts_held if reached is None else reached) * 3 * s.hidden * s.expert_width)
    layers = s.layers * (attention + 2 * s.hidden) + s.dense_layers * dense + (s.layers - s.dense_layers) * routed
    mean_length = s.prompt + (s.response - 1) / 2.0
    out = {
        "passes": s.response, "scored_passes": s.response, "positions": envs * (s.prompt + s.response),
        "layers": float(width * layers),
        "head": float(width * (s.hidden * (s.vocab + 1) + s.hidden)),
        "cache": float(width * s.layers * envs * mean_length * (s.kv_rank + s.rope)),
    }
    out["rollout"] = out["passes"] * (out["layers"] + out["cache"]) + out["scored_passes"] * out["head"]
    return out


# by ``algo.policy``, which the traffic mix names: (the shapes' class, the count), and the layout
KINDS = {"sdar_moe": (SdarShapes, sdar_rollout_bytes), "mla_moe": (MlaShapes, mla_rollout_bytes)}
PASS_OF = {"sdar_moe": sdar_pass_of_position, "mla_moe": mla_pass_of_position}


def rollout_bytes(policy: str, config: dict, traffic: dict, tiny: bool, envs: int,
                  reached: Optional[float] = None) -> Dict[str, float]:
    """``precision`` of the configuration's file gives the compute width."""
    shapes, count = KINDS[policy]
    return count(shapes.from_config(config, traffic, tiny), envs, COMPUTE_BYTES[config["precision"]], reached)
