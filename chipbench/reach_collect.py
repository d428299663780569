"""The held experts collection's cached passes reach, as the PROGRAM counted
them (PR 34), for the readers ``collect_experts_reached`` and
``collect_counted_roofline_pct``.

``envs/jax/collect.py``'s language-model rollouts sum, inside the rollout
program, from every cached pass (not the prefill) and every routed layer of
the trunk, what the routed layer has anyway (``aux["load"]``, the rows per
held expert): the distinct held experts the pass's rows reached,
``experts_reached``.  It rides the ``jaxenv`` section of ``telemetry.jsonl``,
cumulative over the rollouts whose episode events were fetched
(``event_fetches``; every rollout at ``metric.fetch_every=1``).

``bytes_collect.experts_reached`` estimates the same mean from the UPDATE's
routing of the FIRST rollout (the cached passes choose otherwise at the few
per cent of positions where bf16 flips a near tie, which at about two
assignments a pass and layer is no longer small beside the count); this is the
count itself, over the window's own rollouts.

How many (cached pass, routed layer) pairs a rollout holds comes from the
cell's shapes, which the driver leaves under the evidence's ``cell``
(``drivers/ppo_loop_causal.py``).  The causal policy alone is read here: one
cached pass a response token through every routed layer of the trunk.  (The
block-diffusion collector counts too; its cell, ``sdar_ep8_loop``, runs
``ppo_loop``, which leaves no ``cell``: the ``benchmark`` PR that points that
cell at the counter brings its pairs, PERF.md section 7.)
"""

from __future__ import annotations

from typing import Optional

from chipbench import bytes_collect

# by ``algo.policy``: (cached pass, routed trunk layer) pairs of one rollout
LAYER_PASSES = {"mla_moe": lambda s: s.response * (s.layers - s.dense_layers)}
COUNTER = "experts_reached"


def counted(evidence: dict) -> Optional[dict]:
    """The window's delta of the program's counter and what it was taken
    over: ``{"experts_reached", "rollouts"`` (those whose events were
    fetched)``, "layer_passes"`` (a rollout)``, "mean_reached"`` (distinct held
    experts a cached pass and routed layer)``}``.  None for a program without
    the counter, a kind or a driver that leaves nothing to take it over, or a
    window of one record."""
    sections = [r["jaxenv"] for r in evidence.get("telemetry", []) if COUNTER in r.get("jaxenv", {})]
    cell, policy = evidence.get("cell"), evidence.get("collect", {}).get("policy")
    if len(sections) < 2 or cell is None or policy not in LAYER_PASSES:
        return None
    first, last = sections[0], sections[-1]
    rollouts = last["event_fetches"] - first["event_fetches"]
    if rollouts <= 0:
        return None
    shapes = bytes_collect.KINDS[policy][0].from_config(cell["config"], cell["traffic"], cell["tiny"])
    out = {COUNTER: last[COUNTER] - first[COUNTER], "rollouts": rollouts, "layer_passes": LAYER_PASSES[policy](shapes)}
    out["mean_reached"] = out[COUNTER] / (rollouts * out["layer_passes"])
    return out


def rollout_bytes(evidence: dict) -> Optional[dict]:
    """``bytes_collect.rollout_bytes`` of the cell with the counted mean in
    place of the estimate: what a rollout's cached passes had to read."""
    got = counted(evidence)
    if got is None:
        return None
    cell, collect = evidence["cell"], evidence["collect"]
    return bytes_collect.rollout_bytes(collect["policy"], cell["config"], cell["traffic"], cell["tiny"], collect["envs"],
                                       reached=got["mean_reached"])
