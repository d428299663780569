"""Inference-service latency/throughput ladder (ISSUE 8 bench).

Measures the serving plane end to end over real queue channels: N worker
threads (standing in for env-worker processes) each fire single-row
observation requests through an :class:`InferenceClient` into one
:class:`InferenceServer`, for a grid of worker counts x batch deadlines.
Per cell: actions/s, request latency p50/p95 (client-observed), and the
server's batch-size histogram (how well the deadline coalesces traffic).
A direct-call LOCAL baseline (same jitted policy, no transport) anchors
the numbers — the remote/local ratio is the price of the hop, which the
centralization pays back by freeing workers from params adoption and by
batching many workers onto one accelerator dispatch.

Single-core caveat (same as bench_fanin): with workers, server thread and
the jitted policy time-slicing one host core, throughput here is a LOWER
bound; the batching effect (bigger buckets at higher worker counts) is
the portable signal.

Standalone::

    python benchmarks/bench_inference.py [--requests 256] [--out results.json]

or as bench.py's ``serve`` section.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import threading
import time

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

OBS_DIM = 8
ACT_DIM = 4
HIDDEN = 64


def _make_policy():
    """A jitted MLP policy of the dummy-env PPO player's scale."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    params = {
        "w1": jnp.asarray(rng.normal(size=(OBS_DIM, HIDDEN)).astype(np.float32) * 0.1),
        "w2": jnp.asarray(rng.normal(size=(HIDDEN, ACT_DIM)).astype(np.float32) * 0.1),
    }

    @jax.jit
    def apply(p, x):
        h = jnp.tanh(x @ p["w1"])
        return jnp.argmax(h @ p["w2"], axis=-1)

    def policy_fn(p, obs, key):
        return {"actions": np.asarray(apply(p, obs["state"]))}

    return policy_fn, params, apply


def _bench_local(apply, params, n_requests: int) -> dict:
    """Direct-call baseline: the same policy, one row per call, no hop."""
    import jax

    x = np.zeros((1, OBS_DIM), np.float32)
    np.asarray(apply(params, x))  # compile
    lats = []
    t0 = time.perf_counter()
    for i in range(n_requests):
        t1 = time.perf_counter()
        np.asarray(apply(params, x + i))
        lats.append(time.perf_counter() - t1)
    wall = time.perf_counter() - t0
    arr = np.sort(np.asarray(lats))
    return {
        "actions_per_s": round(n_requests / wall, 1),
        "latency_ms": {
            "p50": round(float(np.percentile(arr, 50)) * 1e3, 3),
            "p95": round(float(np.percentile(arr, 95)) * 1e3, 3),
        },
    }


def _bench_remote(policy_fn, params, n_workers: int, deadline_ms: float, n_requests: int) -> dict:
    from sheeprl_tpu.parallel.transport import make_transport
    from sheeprl_tpu.serve import InferenceClient, InferenceServer

    ctx = mp.get_context("spawn")
    hub, specs = make_transport(ctx, "queue", n_workers, window=8, min_bytes=0)
    srv = InferenceServer(policy_fn, params, deadline_ms=deadline_ms, max_batch=64)
    clients = [InferenceClient(specs[i].player_channel(), i, request_timeout_s=30.0) for i in range(n_workers)]
    for i in range(n_workers):
        srv.attach(i, hub.channel(i, timeout=5))
    srv.start()

    # warm the buckets so the grid cell measures steady state
    for c in clients:
        c.infer([("state", np.zeros((1, OBS_DIM), np.float32))], 1)

    fails = []

    def drive(cid):
        obs = np.zeros((1, OBS_DIM), np.float32)
        for i in range(n_requests):
            obs[0, 0] = i
            out, src = clients[cid].infer([("state", obs)], 1)
            if src != "remote":
                fails.append(cid)
                return

    threads = [threading.Thread(target=drive, args=(i,)) for i in range(n_workers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stats = srv.stats()
    # aggregate the client-observed latency windows
    lat_all = []
    for c in clients:
        p = c.stats()["latency_ms"]
        if p:
            lat_all.append(p)
    out = {
        "workers": n_workers,
        "deadline_ms": deadline_ms,
        "actions_per_s": round(n_workers * n_requests / wall, 1),
        "client_latency_ms": {
            "p50": round(float(np.median([p["p50"] for p in lat_all])), 3),
            "p95": round(float(max(p["p95"] for p in lat_all)), 3),
        },
        "server_latency_ms": stats["latency_ms"],
        "batch_hist": stats["batch_hist"],
        "failures": len(fails),
    }
    srv.close()
    for c in clients:
        c.close()
    hub.close()
    return out


def run_grid(n_requests: int = 256, workers=(1, 2, 4), deadlines=(1.0, 5.0)) -> dict:
    policy_fn, params, apply = _make_policy()
    local = _bench_local(apply, params, n_requests)
    cells = []
    for w in workers:
        for d in deadlines:
            cells.append(_bench_remote(policy_fn, params, w, d, n_requests))
    # headline: best remote throughput across the grid vs the local call
    best = max(cells, key=lambda c: c["actions_per_s"])
    return {
        "local_baseline": local,
        "grid": cells,
        "best_remote": {k: best[k] for k in ("workers", "deadline_ms", "actions_per_s")},
        "remote_over_local_throughput": round(best["actions_per_s"] / local["actions_per_s"], 3),
        "host_cpu_count": os.cpu_count(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    result = run_grid(n_requests=args.requests)
    text = json.dumps(result, indent=2)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
