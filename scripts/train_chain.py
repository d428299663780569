"""Automated checkpoint-resume chain for long on-chip training runs.

A process whose host RSS grows under sustained train dispatch (seen in
rounds 2-4 with ``jax.live_arrays()`` flat; not re-checked on this tree)
is capped at a few hours.  This runner turns the manual mitigation into an
unattended chain:

    launch leg -> watch RSS / wall-clock -> stop leg at a checkpoint
    boundary -> relaunch with ``checkpoint.resume_from=<latest>`` -> ...

until a target policy step, an absolute deadline, or a failure cap is
reached.  Each leg's stdout goes to ``<chain-dir>/leg_NNN.log`` so reward
curves can be stitched across legs afterwards (``scripts/curve_from_logs.py``).

Example (the round-3 walker-walk run):

    python scripts/train_chain.py \
      --run-dir runs/dv3_walker --chain-dir runs/dv3_walker/chain_r3 \
      --target-step 100000 --deadline-ts 1785489000 \
      --leg-seconds 7200 --max-rss-gb 85 \
      -- exp=dreamer_v3_dmc_walker_walk env.num_envs=8 \
         algo.replay_ratio=0.3 buffer.size=100000 buffer.memmap=False \
         checkpoint.every=4000 checkpoint.keep_last=3 \
         root_dir=/root/repo/runs/dv3_walker

Stopping policy: a leg is SIGTERM'd (then SIGKILL'd after a grace period)
when it exceeds the per-leg wall-clock or RSS cap; progress since the
last checkpoint is lost, so ``checkpoint.every`` should be small relative
to the leg length.  The chain stops when the newest checkpoint reaches
``--target-step``, the deadline passes, or ``--max-failures`` legs in a
row exit without writing a new checkpoint.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import signal
import subprocess
import sys
import time


def latest_ckpt(run_dir: str):
    """Newest checkpoint by (step, mtime) under run_dir, or None."""
    best = None
    for path in glob.glob(os.path.join(run_dir, "**", "checkpoint", "ckpt_*_*.ckpt"), recursive=True):
        m = re.search(r"ckpt_(\d+)_\d+\.ckpt$", os.path.basename(path))
        if not m:
            continue
        key = (int(m.group(1)), os.path.getmtime(path))
        if best is None or key > best[0]:
            best = (key, path)
    return (best[0][0], best[1]) if best else (0, None)


def rss_gb(pid: int) -> float:
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024 / 1024
    except OSError:
        pass
    return 0.0


def stop(proc: subprocess.Popen, grace_s: float = 90.0) -> None:
    if proc.poll() is not None:
        return
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=grace_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run-dir", required=True, help="where checkpoints land (searched recursively)")
    ap.add_argument("--chain-dir", required=True, help="chain state: leg logs + status file")
    ap.add_argument("--target-step", type=int, required=True)
    ap.add_argument("--deadline-ts", type=float, required=True, help="unix ts: no legs past this; running leg is stopped")
    ap.add_argument("--leg-seconds", type=float, default=7200)
    ap.add_argument("--max-rss-gb", type=float, default=85)
    ap.add_argument("--max-failures", type=int, default=3)
    ap.add_argument("--poll-seconds", type=float, default=30)
    ap.add_argument("overrides", nargs="+", help="sheeprl.py overrides (after --)")
    args = ap.parse_args()

    os.makedirs(args.chain_dir, exist_ok=True)
    status_path = os.path.join(args.chain_dir, "status.jsonl")

    def note(**kw):
        kw["ts"] = round(time.time(), 1)
        with open(status_path, "a") as f:
            f.write(json.dumps(kw) + "\n")
        print(json.dumps(kw), flush=True)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    failures = 0
    leg = 0
    # number legs after any the chain dir already has (chain restart safety)
    existing = glob.glob(os.path.join(args.chain_dir, "leg_*.log"))
    if existing:
        leg = max(int(re.search(r"leg_(\d+)\.log$", p).group(1)) for p in existing) + 1

    while True:
        step, ckpt = latest_ckpt(args.run_dir)
        if step >= args.target_step:
            note(event="target_reached", step=step, ckpt=ckpt)
            return 0
        now = time.time()
        if now >= args.deadline_ts:
            note(event="deadline", step=step)
            return 0
        if failures >= args.max_failures:
            note(event="too_many_failures", step=step)
            return 1

        leg_log = os.path.join(args.chain_dir, f"leg_{leg:03d}.log")
        cmd = [sys.executable, os.path.join(repo, "sheeprl.py"), *args.overrides,
               f"run_name=chain_leg{leg:03d}"]
        if ckpt:
            cmd.append(f"checkpoint.resume_from={ckpt}")
        note(event="leg_start", leg=leg, from_step=step, ckpt=ckpt)
        t_leg = time.time()
        # unbuffered: reward lines must reach the log file as they happen,
        # or a SIGKILL'd leg loses the buffered tail the curve stitcher needs
        leg_env = {**os.environ, "PYTHONUNBUFFERED": "1"}
        with open(leg_log, "a") as lf:
            proc = subprocess.Popen(cmd, stdout=lf, stderr=lf, cwd=repo, env=leg_env)
            reason = "exit"
            while proc.poll() is None:
                time.sleep(args.poll_seconds)
                elapsed = time.time() - t_leg
                mem = rss_gb(proc.pid)
                if time.time() >= args.deadline_ts:
                    reason = "deadline"
                    stop(proc)
                elif elapsed > args.leg_seconds:
                    reason = "leg_wallclock"
                    stop(proc)
                elif mem > args.max_rss_gb:
                    reason = "rss_cap"
                    stop(proc)
        new_step, _ = latest_ckpt(args.run_dir)
        made_progress = new_step > step
        failures = 0 if made_progress else failures + 1
        note(event="leg_end", leg=leg, reason=reason, rc=proc.returncode,
             leg_s=round(time.time() - t_leg, 1), from_step=step, to_step=new_step,
             made_progress=made_progress)
        leg += 1


if __name__ == "__main__":
    sys.exit(main())
