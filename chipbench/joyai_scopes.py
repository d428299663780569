"""Device time of the causal language-model policy's update, split by the
``jax.named_scope`` that owns each op (``make_episode_update_fn``,
``models/mla_moe.py`` and the shared ``RoutedExperts`` wrap their phases in the
scopes of ``TOKENS``).

The reduction is ``sdar_scopes.by_scope`` with this model's tokens (owner of an
op: the last token on its own path, else the nearest op that encloses it in
time; the compiler's ``ragged-dot-*`` ops by name).  ``mtp_module`` is the
outermost scope of everything the multi-token-prediction module runs, so it is
never the LAST token of an op inside the module's block: the split gives the
module's inner scopes their own owners (``mla_proj`` ... of all six blocks
together), and ``under`` sums, in a second reduction over the one token, every
op whose path holds ``mtp_module`` anywhere: what multi-token prediction costs
as one number, overlapping the split by the module's block.  (The grouped
products the compiler renames carry no path: the MTP block's stay with
``moe_experts`` and are missing from the module's total.)  Reduced once a run
and kept; the milliseconds per scope and the share under no scope go out on an
earlier line."""

from __future__ import annotations

import functools
import os
from typing import Optional, Sequence

from chipbench import harness, scope_reduce, sdar_scopes, span_reduce, trace_reduce
from chipbench.peaks import peaks_for
from chipbench.scope_reduce import UNSCOPED

MODULE = "mtp_module"
TOKENS = ("lm_embed", "mla_proj", "mla_kernel", "dense_mlp", "moe_shared", "moe_router", "moe_dispatch", "moe_experts",
          "lm_head", MODULE, "ppo_loss", "ppo_optim")


@functools.lru_cache(maxsize=1)
def _this_run(pattern: str) -> Optional[dict]:
    table = span_reduce.window_table()
    if table is None:
        return None
    devices = scope_reduce.load_scoped(trace_reduce.newest_xplane(os.path.join(harness.OUT, "trace")))
    got = sdar_scopes.by_scope(devices, table["window"], pattern, TOKENS)
    if got is None or not any(token in got["self_s"] for token in TOKENS if token.startswith(("mla_", "mtp_"))):
        return None  # a program without this model's scopes: nothing to read
    module = sdar_scopes.by_scope(devices, table["window"], pattern, (MODULE,))
    got["under"] = {MODULE: module["self_s"].get(MODULE, 0.0) if module else 0.0}
    per_call = 1e3 / got["count"]
    harness.note(joyai_update_scopes={
        "calls": got["count"],
        "ms_per_call": {k: v * per_call for k, v in got["self_s"].items()},
        "ms_per_call_under": {k: v * per_call for k, v in got["under"].items()},
        "unscoped_pct": 100.0 * got["self_s"].get(UNSCOPED, 0.0) / got["seconds"],
        "top_ops_ms_per_call": {k: [[name, s * per_call] for name, s in ops]
                                for k, ops in scope_reduce.top_ops(got, 6).items()},
    })
    return got


def seconds_per_step(evidence: dict, tokens: Sequence[str], under: bool = False) -> Optional[float]:
    """Self seconds per minibatch step of the ops the tokens own, or (``under``) of the ops whose
    path holds the token anywhere."""
    pattern, steps_per_call = evidence.get("programs", {}).get("update"), evidence.get("steps_per_call")
    if evidence.get("trace") is None or not pattern or not steps_per_call:
        return None
    got = _this_run(pattern)
    if got is None:
        return None
    return sum(got["under" if under else "self_s"].get(t, 0.0) for t in tokens) / (got["count"] * steps_per_call)


def ms_per_step(evidence: dict, tokens: Sequence[str], under: bool = False) -> Optional[float]:
    seconds = seconds_per_step(evidence, tokens, under)
    return None if seconds is None else 1e3 * seconds


def roofline_pct(evidence: dict, flops_per_step: Optional[float], tokens: Sequence[str]) -> Optional[float]:
    """``flops_per_step`` over the tokens' device time, against the bf16 peak of ``peaks.json``."""
    seconds = seconds_per_step(evidence, tokens)
    if flops_per_step is None or not seconds:
        return None
    return 100.0 * flops_per_step / seconds / peaks_for(evidence["device_kind"])["bf16_flops_per_s"]
