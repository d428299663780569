"""Driver ``ppo_loop``: the whole PPO iteration of a language-model policy as
users launch it: ``sheeprl_tpu.cli.run`` on the main thread with the
configuration's and the traffic mix's overrides, under ``program_ppo.PpoSpy``.
Collection by the kind's fused collector at the newest weights, then one
update call, then the loss fetch; nothing of a policy kind is here but what the
traffic mix names (``policy``) and the configuration's file holds.

The window is ``drivers/loop.py``'s (``Window``): it opens at the first
iteration boundary that follows a loss fetch once ``warmup_updates`` update
calls have run, closes at the last such boundary inside ``--seconds``, and the
run then ends by the program's own clean stop (SIGTERM to itself; the forced
checkpoint lies after the window).  Env frames = policy steps between the two
boundaries.

What decides ``correct`` (after the stop; its seconds are not in ``setup_s``).
At the first update call the spy copied to the host the parameters the first
rollout acted with, the rollout as collection recorded it through its
cache-carrying passes and handed it to the update, and what the call returned
(parameters, metrics, its probe of every minibatch step).  The plain reference
(``chipbench/reference/<policy>.py``, float32, "highest", the dense mask)
takes the first minibatch step of that call from the same parameters and the
same recorded rollout, through the kind's train driver (``drivers/sdar_train.py``
or ``drivers/causal_lm_train.py``: its ``reference_for``, ``compare`` and
``LIMITS``, imported, so both cells hold the update to one measurement):

1. **The update** (GAE over the recorded rewards and values, the minibatch, the
   three losses, the gradient, the optimizer step): the step's losses, the
   gradient's norm whole and leaf by leaf, the norm of every leaf's change and
   the routing counts against the reference's, and the norm of ``returned -
   initial`` (the driver's own subtraction) over the sum of the steps' changes.
   Every one of the call's ``steps_per_call`` minibatch steps has to have moved
   the state (``steps_missing``).
2. **The collected episodes against a full forward pass**: the log-probabilities
   and values collection RECORDED for the minibatch's episodes against the
   reference's (``recorded_*``): what the cache-carrying passes produced.  The
   reference takes over the program's routing choice (of the update's own full
   pass over the same episodes) where its own gap is under the train driver's
   ``ROUTE_MARGIN``.
3. Finite losses, no compile in the window, update calls = iterations, every
   rollout at the newest weights (``params_age`` 0), the program's own count of
   passes and positions a rollout equal to the shapes' (``bytes_collect``).
"""

from __future__ import annotations

import gc
import glob
import importlib
import os
import shutil
import time
from typing import Any, Dict

from chipbench import bytes_collect
from chipbench.drivers.loop import Window
from chipbench.harness import Context, note, require
from chipbench.program_ppo import PpoSpy, read_telemetry, recompile_monitor, run_cli

# by ``algo.policy``, which the traffic mix names: the kind's train driver (what holds an update call to the
# plain reference: ``compared_call``, ``reference_for``, ``compare``, ``LIMITS``) and the benchmark's door
# into the program for the kind (its ``hyper`` and ``model_cfg`` read the run's configuration)
KINDS = {
    "sdar_moe": ("chipbench.drivers.sdar_train", "chipbench.program_sdar", "LmUpdate"),
    "mla_moe": ("chipbench.drivers.causal_lm_train", "chipbench.program_joyai", "CausalLmUpdate"),
}

# ---- the limits this driver brings: the RECORDED log-probabilities (about -9.85 each) and values of the first
# minibatch's episodes against the reference's, 3,072 cells.  PERF.md section 4 has the readings behind each
# (my chip runs, PR 32): the range over the sound runs (four seeds on the comparison as it stands, ten earlier
# ones on one episode's 1,024 cells) | the program at fabric.precision=bf16-true | a cache written one block
# late (benchmarks/ppo_loop_controls.py; chipbench/testdata/sdar_ep8_loop_readings.json keeps them all).
# The MEAN is the reading that sees a cache-carrying fault: bf16 products under f32 norms, softmax and router
# read 4.42-4.46e-3 over three episodes (4.4-4.9e-3 over one: a normalised quantity, the seeds within 6 % of
# their middle), parameters stored in bf16 3.1e-3 (both sides then start from the same rounded weights), a
# cache written one block late 8.7e-3 (8.2e-3, 8.9e-3 over one episode): with weights drawn at 0.02 attention
# is diffuse, and the four keys a block no longer sees carry 0.4 % of its mass among 500-1,500, which is why
# the fault is no larger than 1.7-2.0 times the rounding.  The limit stands at the geometric mean of the
# sound runs' largest and the late cache's smallest.  The WORST cell is a routing choice that the cached pass
# alone made otherwise (3.0-7.0e-2 | 4.3e-2 | 6.9e-2 - 7.4e-2), so its limit only holds a single wrong cell (a
# wrong token or position reads about 1) and stands over twice the largest.  Values follow the seed's value
# head (mean 1.9-7.7e-3, worst 2.2-6.2e-2 | 2.6-3.1e-3, 2.7-6.1e-2 | 7.6-9.1e-3, 4.9-6.5e-2): they tell neither
# control from a sound run, and stand four times and over twice the largest.
RECORDED_LIMITS = {"recorded_logp_mean_abs": 6.3e-3, "recorded_logp_max_abs": 1.6e-1,
                   "recorded_value_mean_abs": 3e-2, "recorded_value_max_abs": 1.5e-1}


def limits_for(policy: str) -> Dict[str, float]:
    """The train driver's limits on the update (its file has the readings behind
    each; ``returned_shortfall`` is the one ``bf16-true`` fails), this driver's
    on what collection recorded, and no minibatch step missing."""
    return {**importlib.import_module(KINDS[policy][0]).LIMITS, **RECORDED_LIMITS, "steps_missing": 0}


def first_call_as_program(spy: PpoSpy, policy: str):
    """The loop's first update call as the spy kept it, in the shape the train
    drivers take a program in (``compared_call``, ``reference_for``): the kind's
    door with nothing built, which hands out the state the call was given and
    what the call returned (its ``hyper`` and ``model_cfg`` read the run's own
    configuration and policy).  So the loop's update goes through the very
    measurement the kind's train cell is held to."""
    door, kept = getattr(importlib.import_module(KINDS[policy][1]), KINDS[policy][2]), spy.first_call

    class FirstCall(door):
        def __init__(self):  # the run is over: nothing is built
            self.cfg, self.policy = spy.cfg, spy.policy

        def initial_state(self):
            return kept["initial_params"], None

        def fresh_params(self):
            return None, kept["initial_params"]

        def update(self, *args, **kwargs):
            return kept["returned_params"], None, kept["metrics"], kept["probe"]

    return FirstCall()


def routing_by_episode(probe: Dict[str, Any]):
    """The update's routing choice of every episode of the rollout, (layers,
    episodes, positions, k), from the probe of the call's minibatch steps (one
    epoch: every episode is in one step)."""
    import numpy as np

    ids, top_i = np.asarray(probe["episodes"]), np.asarray(probe["top_i"])  # (steps, mb), (steps, layers, mb * N, k)
    (steps, mb), layers = ids.shape, top_i.shape[1]
    top_i = top_i.reshape(steps, layers, mb, -1, top_i.shape[-1])
    out = np.empty((layers, steps * mb) + top_i.shape[3:], top_i.dtype)
    for s in range(steps):
        out[:, ids[s]] = top_i[s]
    return out


def recorded_readings(data: Dict[str, Any], got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """What collection recorded for the compared episodes against the reference's full pass."""
    import numpy as np

    ids = got["episodes"]
    dl = np.abs(data["logprobs"][:, ids, 0].T - ref["logp"])
    dv = np.abs(data["values"][:, ids, 0].T - ref["values"])
    return {
        "recorded_logp_mean_abs": float(dl.mean()), "recorded_logp_max_abs": float(dl.max()),
        "recorded_value_mean_abs": float(dv.mean()), "recorded_value_max_abs": float(dv.max()),
        # not judged
        "recorded_logp_median_abs": float(np.median(dl)), "recorded_value_median_abs": float(np.median(dv)),
        "recorded_vs_update_logp_max_abs": float(np.abs(data["logprobs"][:, ids, 0].T - got["logp"]).max()),
        "cells": int(dl.size),
    }


def judge(readings: Dict[str, Any], limits: Dict[str, float]) -> Dict[str, Any]:
    """The readings over their limits: empty for a correct run."""
    return {k: v for k, v in readings.items() if k in limits and not v <= limits[k]}


def compare(spy: PpoSpy, policy: str, shapes, steps_per_call: int) -> Dict[str, Any]:
    """The first iteration against the reference, after the program has stopped."""
    train = importlib.import_module(KINDS[policy][0])
    kept = spy.first_call
    gc.collect()  # the loop's own state has gone out of scope: the reference needs the device's memory
    call = first_call_as_program(spy, policy)
    got, initial, _ = train.compared_call(call, shapes, kept["data"], None)
    kept.pop("returned_params")
    ref = train.reference_for(call, shapes, kept["data"], got, initial)
    readings = train.compare(got, ref)
    readings.update(recorded_readings(kept["data"], got, ref))
    readings.update(steps_change=got["steps_change"],
                    steps_missing=steps_per_call - sum(1 for change in got["steps_change"] if change > 0.0))
    return readings


def run(ctx: Context) -> dict:
    import jax
    import numpy as np

    policy = ctx.traffic["policy"]
    importlib.import_module(KINDS[policy][1])  # a program without the model fails here, at once
    shapes = bytes_collect.KINDS[policy][0].from_config(ctx.config, ctx.traffic, ctx.tiny)
    monitor = recompile_monitor("chipbench")
    shutil.rmtree(os.path.join(ctx.run_dir, ctx.name), ignore_errors=True)
    window = Window(ctx, monitor)
    spy = PpoSpy(on_boundary=window)
    run_cli(ctx.overrides(), spy)  # returns: the program's own clean stop
    require(window.start is not None and window.end is not None and window.end > window.start,
            f"the loop never reached its window ({len(spy.boundaries)} iterations, {spy.update_calls} update calls)")

    t0, steps0, calls0, _ = spy.boundaries[window.start]
    t1, steps1, calls1, _ = spy.boundaries[window.end]
    window_s = t1 - t0
    iterations = window.end - window.start
    policy_steps = steps1 - steps0
    update_calls = calls1 - calls0
    frames = policy_steps * int(ctx.param("action_repeat", 1))
    envs, response = int(ctx.param("episodes")), int(ctx.param("response_len"))

    paths = glob.glob(os.path.join(ctx.run_dir, ctx.name, "**", "telemetry.jsonl"), recursive=True)
    require(paths, "the run wrote no telemetry.jsonl")
    records = read_telemetry(paths[0])
    require(records, "telemetry.jsonl is empty")
    post_warmup = records[-1]["compiles"]["post_warmup"]
    shutil.rmtree(os.path.join(ctx.run_dir, ctx.name), ignore_errors=True)  # the stop's checkpoint is large
    window_compiles = window.after["total"] - window.before["total"]
    stale = sorted({r["jaxenv"]["params_age"] for r in records if "params_age" in r.get("jaxenv", {})} - {0})
    steps_per_call = envs // int(ctx.param("minibatch_episodes"))
    # the program's own count of a rollout's forward passes and the positions they ran over (the ``jaxenv``
    # section's cumulative counters over the window's records; a program without them gives None)
    first, last = records[window.start].get("jaxenv", {}), records[window.end - 1].get("jaxenv", {})
    counted = {k: (last[k] - first[k]) // (last["rollouts"] - first["rollouts"])
               for k in ("passes", "positions") if k in first and last["rollouts"] > first["rollouts"]}

    ctx.evidence.update(
        steps=update_calls * steps_per_call, steps_per_call=steps_per_call, window_s=window_s,
        steps_per_s=update_calls * steps_per_call / window_s, policy_steps=policy_steps, chips=ctx.chips,
        device_kind=jax.devices()[0].device_kind, window_compiles=window_compiles,
        # one record an iteration, written at its end: those of the window's iterations but the first, whose
        # wall would hold the profiler's start (the readers take shares between the first and the last)
        telemetry=records[window.start:window.end],
        programs=ctx.param("programs", {}), iterations=iterations,
    )
    times = np.diff([b[0] for b in spy.boundaries[window.start:window.end + 1]])
    losses = spy.fetched[calls0:calls1]
    bad = sorted({k for m in losses for k, v in m.items() if not np.isfinite(v)})
    note(window={"iterations": iterations, "policy_steps": policy_steps, "update_calls": update_calls,
                 "seconds": window_s, "iteration_s_quartiles": np.percentile(times, [25, 50, 75]).tolist()},
         compiles={"before": window.before, "after": window.after, "telemetry_post_warmup": post_warmup},
         telemetry_last={k: records[-1].get(k) for k in ("step", "timers_s", "sps", "compiles", "jaxenv")},
         first_call_copies_s=spy.first_call.get("copies_s"))

    # ---- what a rollout's cached passes have to read: the held experts a pass reaches are COUNTED, from the
    # routing of the first rollout's episodes as the update's full pass over them chose it
    reached = bytes_collect.experts_reached(policy, shapes, routing_by_episode(spy.first_call["probe"]),
                                            int(ctx.config["expert_offset"]))
    needed = bytes_collect.rollout_bytes(policy, ctx.config, ctx.traffic, ctx.tiny, envs, reached)
    ctx.evidence["collect"] = {"policy": policy, "envs": envs, "rollout_bytes": needed,
                               "cached_passes": counted["passes"] - 1 if counted else None}  # the prefill aside
    note(collect_needs={"experts_reached_per_pass_and_layer": reached, "experts_held": shapes.experts_held,
                        "counted_per_rollout": counted, **needed})

    # ---- what the first iteration produced, against the reference (after the stop: not set-up)
    t_reference = time.perf_counter()
    limits = limits_for(policy)
    readings = compare(spy, policy, shapes, steps_per_call)
    over = judge(readings, limits)
    note(compare_with_reference={"readings": readings, "limits": limits, "reference_s": time.perf_counter() - t_reference})

    require(not over, f"the first iteration vs the reference: {over} over {({k: limits[k] for k in over})}")
    require(readings["cells"] == int(ctx.param("minibatch_episodes")) * response,
            f"{readings['cells']} cells compared, the minibatch has {ctx.param('minibatch_episodes')} x {response}")
    require(not counted or (counted["passes"], counted["positions"]) == (needed["passes"] + 1, needed["positions"]),
            f"the program counted {counted} a rollout, the shapes give {needed['passes']} cached passes and a prefill "
            f"over {needed['positions']} positions")
    require(policy_steps == iterations * envs * response,
            f"{policy_steps} policy steps over {iterations} iterations of {envs} x {response}")
    require(update_calls == iterations, f"{update_calls} update calls over {iterations} iterations")
    require(len(losses) == update_calls, f"{len(losses)} loss fetches over {update_calls} update calls")
    require(not bad, f"non-finite losses in the window: {bad}")
    require(window_compiles == 0, f"{window_compiles} compiles inside the window")
    require(post_warmup == 0, f"{post_warmup} compiles after warm-up (telemetry)")
    require(not stale, f"rollouts acted with weights {stale} update calls old")
    return {
        "attempted": policy_steps,
        "failed": 0,
        "setup_s": t0 - ctx.t_process_start,
        "end_to_end": {"env_frames_per_s": (frames / window_s, "frames/s")},
    }
