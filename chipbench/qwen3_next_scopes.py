"""Device time of the Qwen3-Next policy's update, split by the
``jax.named_scope`` that owns each op (``make_episode_update_fn``,
``models/qwen3_next.py`` and the shared ``RoutedExperts`` wrap their phases in
the scopes of ``TOKENS``): ``gdn_scan`` is the gated delta rule alone in the
linear layers (``ops/gated_delta_rule.py``: the chunked form, forward, backward
and the block's rematerialised forward), ``gdn_proj`` the rest of those layers'
input side (the input norm, projections, convolution, L2 norms, decay and write
strength) and ``gdn_out`` their output side (the gated norm and ``W_o``);
``gated_attn_kernel`` is the attention op alone in the full layer and
``gated_attn_proj`` the rest of that layer's attention.

The reduction is ``sdar_scopes.by_scope`` with this model's tokens (owner of an
op: the last token on its own path, else the nearest op that encloses it in
time; the compiler's ``ragged-dot-*`` ops by name).  Reduced once a run and
kept; the milliseconds per scope and the share under no scope go out on an
earlier line.  A program without these scopes (one without the model) gives
nothing to read, and every reader returns None."""

from __future__ import annotations

import functools
import os
from typing import Optional, Sequence

from chipbench import harness, scope_reduce, sdar_scopes, span_reduce, trace_reduce
from chipbench.scope_reduce import UNSCOPED

TOKENS = ("lm_embed", "gdn_proj", "gdn_scan", "gdn_out", "gated_attn_proj", "gated_attn_kernel", "moe_shared",
          "moe_router", "moe_dispatch", "moe_experts", "lm_head", "ppo_loss", "ppo_optim")


@functools.lru_cache(maxsize=1)
def _this_run(pattern: str) -> Optional[dict]:
    table = span_reduce.window_table()
    if table is None:
        return None
    devices = scope_reduce.load_scoped(trace_reduce.newest_xplane(os.path.join(harness.OUT, "trace")))
    got = sdar_scopes.by_scope(devices, table["window"], pattern, TOKENS)
    if got is None or not any(token in got["self_s"] for token in ("gdn_scan", "gdn_proj", "gated_attn_kernel")):
        return None  # a program without this model's scopes: nothing to read
    per_call = 1e3 / got["count"]
    harness.note(qwen3_next_update_scopes={
        "calls": got["count"],
        "ms_per_call": {k: v * per_call for k, v in got["self_s"].items()},
        "unscoped_pct": 100.0 * got["self_s"].get(UNSCOPED, 0.0) / got["seconds"],
        "top_ops_ms_per_call": {k: [[name, s * per_call] for name, s in ops]
                                for k, ops in scope_reduce.top_ops(got, 6).items()},
    })
    return got


def seconds_per_step(evidence: dict, tokens: Sequence[str]) -> Optional[float]:
    """Self seconds per minibatch step of the ops the tokens own."""
    pattern, steps_per_call = evidence.get("programs", {}).get("update"), evidence.get("steps_per_call")
    if evidence.get("trace") is None or not pattern or not steps_per_call:
        return None
    got = _this_run(pattern)
    if got is None:
        return None
    return sum(got["self_s"].get(t, 0.0) for t in tokens) / (got["count"] * steps_per_call)


def ms_per_step(evidence: dict, tokens: Sequence[str]) -> Optional[float]:
    seconds = seconds_per_step(evidence, tokens)
    return None if seconds is None else 1e3 * seconds
