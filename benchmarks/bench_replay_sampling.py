"""Replay-sampling ladder: uniform vs prioritized draws.

Times the per-batch cost of the on-device samplers at several cache
sizes (1e4 → 1e6 transitions) so the sum-tree's O(log n) descent can be
compared against the O(1) uniform gather it rides next to.  Also times
the write-side costs prioritization adds (max-priority seeding per
append, TD-driven ``update_priorities``), and the params-broadcast digest
cost ladder (host ``content_digest`` vs the one-dispatch device
``stream_digest_batched`` — ISSUE 14 tentpole c).

Each mode runs ``repeats`` rounds INTERLEAVED and the minimum feeds the
ratios (the PR-10 pattern: single runs swing 20-30% on a shared host).
Numbers are wall-clock per dispatched op with ``block_until_ready``, on
whatever device JAX finds: they name no device and are no device metric.

    python benchmarks/bench_replay_sampling.py [--out results/replay_sampling.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bench(fn, n_iters: int, warmup: int = 3) -> float:
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(n_iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n_iters


def _make_cache(cap, n_envs, feat, prioritized):
    from sheeprl_tpu.data.device_buffer import DeviceReplayCache

    cache = DeviceReplayCache(cap, n_envs, prioritized=prioritized, per_alpha=0.6)
    rng = np.random.default_rng(0)
    block = 4096
    t = 0
    while t < cap:
        n = min(block, cap - t)
        cache.add(
            {
                "observations": rng.standard_normal((n, n_envs, feat)).astype(np.float32),
                "actions": rng.standard_normal((n, n_envs, 2)).astype(np.float32),
                "rewards": rng.standard_normal((n, n_envs, 1)).astype(np.float32),
                "terminated": np.zeros((n, n_envs, 1), np.uint8),
                "next_observations": rng.standard_normal((n, n_envs, feat)).astype(np.float32),
            }
        )
        t += n
    return cache


def run_ladder(sizes=(10_000, 100_000, 1_000_000), batch=256, n_iters=20, feat=8, repeats=3):
    import jax

    rows = []
    for cap in sizes:
        n_envs = 1
        caches = {
            "uniform": _make_cache(cap, n_envs, feat, False),
            "per": _make_cache(cap, n_envs, feat, True),
        }
        keys = iter(jax.random.split(jax.random.PRNGKey(0), 100_000))

        # two draw shapes per mode: the r07-comparable plain draw (no
        # next-obs, no sampling exclusion) and the SAC-shaped draw
        # (sample_next_obs=True: the prioritized path pays a FULL
        # functional tree copy to zero the stale head row)
        def uni(nobs):
            kw = dict(sample_next_obs=True, obs_keys=("observations",)) if nobs else {}
            return caches["uniform"].sample_transitions(1, batch, next(keys), **kw)["rewards"]

        def per(nobs):
            kw = dict(sample_next_obs=True, obs_keys=("observations",)) if nobs else {}
            return caches["per"].sample_transitions_per(1, batch, next(keys), beta=0.4, **kw)[
                0
            ]["rewards"]

        idx = np.arange(batch, dtype=np.int32)
        td = np.abs(np.random.default_rng(1).standard_normal(batch)).astype(np.float32)

        def upd():
            caches["per"].update_priorities(idx, td)
            return caches["per"]._tree.tree

        modes = {
            "uniform": lambda: uni(False),
            "per": lambda: per(False),
            "uniform_nobs": lambda: uni(True),
            "per_nobs": lambda: per(True),
            "upd": upd,
        }
        # interleaved min-of-N over every mode (the PR-10 pattern)
        best = {m: float("inf") for m in modes}
        for _ in range(repeats):
            for m, fn in modes.items():
                best[m] = min(best[m], _bench(fn, n_iters))

        rows.append(
            {
                "capacity": cap,
                "batch": batch,
                "repeats": repeats,
                # r07-comparable legs (same shapes bench'd at r07)
                "uniform_sample_ms": round(best["uniform"] * 1e3, 4),
                "prioritized_sample_ms": round(best["per"] * 1e3, 4),
                "prioritized_over_uniform": round(best["per"] / best["uniform"], 3),
                # SAC-shaped legs (next-obs gathered; exclusion-bearing)
                "uniform_nobs_ms": round(best["uniform_nobs"] * 1e3, 4),
                "prioritized_nobs_ms": round(best["per_nobs"] * 1e3, 4),
                "nobs_prioritized_over_uniform": round(best["per_nobs"] / best["uniform_nobs"], 3),
                "update_priorities_ms": round(best["upd"] * 1e3, 4),
                "tree_depth": caches["per"]._tree.depth,
            }
        )
        print(json.dumps(rows[-1]), flush=True)
    return rows


def run_digest_ladder(leaf_counts=(4, 10, 16, 50), n_iters=300):
    """Params-broadcast digest cost per message: the PR-10 host
    ``content_digest`` walk vs the ISSUE-14 one-dispatch device digest,
    over synthetic params pytrees of growing leaf count (64x64 f32
    layers — a PPO/SAC actor tree is ~10-20 leaves).  Three device
    numbers per rung, because staging dominates on a CPU backend:
    device-resident leaves WITHOUT the final sync (the trainer's
    steady-state: dispatch now, int() at frame build), device-resident
    with sync, and host-numpy leaves including the jnp staging (the
    worst case — what a CPU player would pay at adoption)."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.resilience.integrity import content_digest, stream_digest_batched

    rng = np.random.default_rng(0)
    rows = []
    for n_leaves in leaf_counts:
        arrays = [
            (f"layer{i}/w", rng.standard_normal((64, 64)).astype(np.float32))
            for i in range(n_leaves)
        ]
        staged = [(k, jnp.asarray(a)) for k, a in arrays]

        def host():
            return content_digest(arrays)

        def dev_resident():
            return stream_digest_batched(staged)

        def dev_host_leaves():
            return stream_digest_batched(arrays)

        host()
        dev_resident()  # compile
        t0 = time.perf_counter()
        for _ in range(n_iters):
            host()
        host_us = (time.perf_counter() - t0) / n_iters * 1e6
        t0 = time.perf_counter()
        for _ in range(n_iters):
            dev_resident()
        dev_us = (time.perf_counter() - t0) / n_iters * 1e6
        # dispatch-only: the digest program is launched but the scalar is
        # not fetched (steady-state trainers overlap the fetch)
        from sheeprl_tpu.resilience.integrity import _digest_program_for

        fn = _digest_program_for(staged, 4096, False)
        staged_arrays = [a for _, a in staged]
        fn(*staged_arrays).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(n_iters):
            r = fn(*staged_arrays)
        dispatch_us = (time.perf_counter() - t0) / n_iters * 1e6
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(max(n_iters // 10, 10)):
            dev_host_leaves()
        stage_us = (time.perf_counter() - t0) / max(n_iters // 10, 10) * 1e6
        rows.append(
            {
                "n_leaves": n_leaves,
                "payload_kb": round(sum(a.nbytes for _, a in arrays) / 1024, 1),
                "host_content_digest_us": round(host_us, 1),
                "device_digest_us": round(dev_us, 1),
                "device_dispatch_only_us": round(dispatch_us, 1),
                "device_from_host_leaves_us": round(stage_us, 1),
            }
        )
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--sizes", default="10000,100000,1000000")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()
    sizes = tuple(int(s) for s in args.sizes.split(","))
    import jax

    rows = run_ladder(sizes=sizes, batch=args.batch, n_iters=args.iters, repeats=args.repeats)
    digest_rows = run_digest_ladder()
    result = {
        "metric": "replay_sampling_ladder",
        "backend": jax.default_backend(),
        "rows": rows,
        "digest_rows": digest_rows,
        "notes": (
            "digest device numbers split dispatch-only / synced / host-staged "
            "because jnp staging dominates for host leaves on a CPU backend"
        ),
    }
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
