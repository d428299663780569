"""Driver ``loop``: the whole collect -> replay -> train loop, as users
launch it: ``sheeprl_tpu.cli.run`` on the main thread with the
configuration's and the traffic mix's overrides, under the spy.

The window opens at the first iteration boundary that follows a loss fetch
once ``warmup_updates`` gradient steps have run, and closes at the last such
boundary inside ``--seconds``; env frames are counted between the two at the
program's own ``on_iteration(policy_step)``.  The run then ends by the
program's own clean stop: the process signals itself (SIGTERM), the loop's
``PreemptionHandler`` flag is seen after the iteration, and what the stop
costs (the emergency checkpoint) lies after the window.
"""

from __future__ import annotations

import glob
import os
import shutil
import signal
import time

from chipbench.harness import Context, fetch_losses, note, require
from chipbench.program import Spy, read_telemetry, recompile_monitor, run_cli

# a run that never reaches its window is stopped here (seconds after start)
GIVE_UP_S = 900.0


class Window:
    """Decides, at every iteration boundary, where the window is."""

    def __init__(self, ctx: Context, monitor):
        self.ctx, self.monitor = ctx, monitor
        self.warmup_updates = int(ctx.param("warmup_updates", 8))
        self.start = self.end = None  # indices into spy.boundaries
        self.before = self.after = None
        self.stopping = False
        self._profile = None

    def __call__(self, spy: Spy) -> None:
        if self.stopping:
            return
        t, _, grad_steps, after_fetch = spy.boundaries[-1]
        i = len(spy.boundaries) - 1
        if self.start is None:
            if after_fetch and grad_steps >= self.warmup_updates:
                self.start = i
                self.before = self.monitor.snapshot()
                self._profile = self.ctx.profile()
                self._profile.__enter__()
                # the profiler's start is set-up of the traced slice, not of the loop
                spy.boundaries[-1] = (time.perf_counter(),) + spy.boundaries[-1][1:]
            elif t - self.ctx.t_process_start > GIVE_UP_S:
                self._stop()
            return
        if after_fetch:
            self.end = i
        if t - spy.boundaries[self.start][0] >= self.ctx.window_seconds and self.end is not None:
            self.after = self.monitor.snapshot()
            self._profile.__exit__(None, None, None)
            self._stop()

    def _stop(self) -> None:
        self.stopping = True
        os.kill(os.getpid(), signal.SIGTERM)


def run(ctx: Context) -> dict:
    import jax
    import numpy as np

    monitor = recompile_monitor("chipbench")
    shutil.rmtree(os.path.join(ctx.run_dir, ctx.name), ignore_errors=True)
    window = Window(ctx, monitor)
    spy = Spy(on_boundary=window)
    run_cli(ctx.overrides(), spy)  # returns: the program's own clean stop
    require(window.start is not None and window.end is not None and window.end > window.start,
            f"the loop never reached its window ({len(spy.boundaries)} iterations, {spy.grad_steps} gradient steps)")

    t0, steps0, grads0, _ = spy.boundaries[window.start]
    t1, steps1, grads1, _ = spy.boundaries[window.end]
    window_s = t1 - t0
    action_repeat = int(ctx.param("action_repeat", 1))
    policy_steps = steps1 - steps0
    grad_steps = grads1 - grads0
    frames = policy_steps * action_repeat

    cache = spy.cache
    require(cache is not None and cache.active and cache._bufs, "the replay cache stayed on the host path")
    ring_platforms = sorted({d.platform for v in cache._bufs.values() for d in v.devices()})
    require(ctx.tiny or ring_platforms == ["tpu"], f"the replay ring lives on {ring_platforms}")

    ratio = float(ctx.param("replay_ratio"))
    got_ratio = grad_steps / max(policy_steps, 1)
    require(abs(got_ratio - ratio) <= 0.02 * ratio + 1.0 / max(policy_steps, 1),
            f"{grad_steps} gradient steps over {policy_steps} policy steps: ratio {got_ratio:.4f}, not {ratio}")

    losses = fetch_losses(spy.metrics[grads0:grads1])
    bad = sorted(k for k, v in losses.items() if not np.all(np.isfinite(v)))

    paths = glob.glob(os.path.join(ctx.run_dir, ctx.name, "**", "telemetry.jsonl"), recursive=True)
    require(paths, "the run wrote no telemetry.jsonl")
    records = read_telemetry(paths[0])
    require(records, "telemetry.jsonl is empty")
    post_warmup = records[-1]["compiles"]["post_warmup"]
    shutil.rmtree(os.path.join(ctx.run_dir, ctx.name), ignore_errors=True)  # the stop's checkpoint is large
    window_compiles = window.after["total"] - window.before["total"]

    ctx.evidence.update(
        steps=grad_steps, window_s=window_s, steps_per_s=grad_steps / window_s,
        policy_steps=policy_steps, chips=ctx.chips, device_kind=jax.devices()[0].device_kind,
        window_compiles=window_compiles, telemetry=records, programs=ctx.param("programs", {}),
        iterations=window.end - window.start,
    )
    times = np.diff([b[0] for b in spy.boundaries[window.start:window.end + 1]])
    note(window={"iterations": window.end - window.start, "policy_steps": policy_steps, "gradient_steps": grad_steps,
                 "seconds": window_s, "ratio": got_ratio,
                 "iteration_s_quartiles": np.percentile(times, [25, 50, 75]).tolist()},
         compiles={"before": window.before, "after": window.after, "telemetry_post_warmup": post_warmup},
         telemetry_last={k: records[-1].get(k) for k in ("policy_step", "timers_s", "sps", "compiles")})
    require(not bad, f"non-finite losses in the window: {bad}")
    require(window_compiles == 0, f"{window_compiles} compiles inside the window")
    require(post_warmup == 0, f"{post_warmup} compiles after warm-up (telemetry)")
    return {
        "attempted": policy_steps,
        "failed": 0,
        "setup_s": t0 - ctx.t_process_start,
        "end_to_end": {"env_frames_per_s": (frames / window_s, "frames/s")},
    }
