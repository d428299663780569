"""Saturation swarm: drive a served policy with hundreds of session clients.

The CLI face of :func:`sheeprl_tpu.scale.swarm.run_swarm` (howto/
serving.md "Autoscaling"): N threaded SessionClients with HEAVY-TAILED
(lognormal) think times step a served recurrent policy to saturation,
recording per-client latency histograms and a p99 SLO verdict through
the PR-16 tracker.  Two targets:

- ``--checkpoint ckpt_*.ckpt`` — serve a trained recurrent checkpoint
  (recurrent PPO or Dreamer v3, the families scripts/serve_policy.py
  knows) behind ONE session server and swarm it;
- no checkpoint (the default) — a tiny synthetic recurrent-PPO module
  behind an ELASTIC ServePool (``--pool-min``/``--pool-max``) whose
  autoscaler grows and shrinks off the measured queue depth while the
  swarm runs: the quickest way to watch the whole elastic serving plane
  work on one box.

Examples::

    python scripts/swarm.py --clients 128 --steps 40 --pool-min 1 --pool-max 3
    python scripts/swarm.py --checkpoint logs/.../ckpt_1024_0.ckpt --clients 64
    python scripts/swarm.py --clients 64 --out benchmarks/results/swarm.json

The report JSON (``benchmarks/results/swarm_*.json`` row format) prints
on stdout; exit code 1 when requests were dropped or the p99 SLO
breached.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# runnable as `python scripts/swarm.py`
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def synthetic_session_parts(seed: int = 0, obs_dim: int = 4, hidden: int = 8):
    """A tiny recurrent-PPO module + session adapters, no checkpoint
    needed (shared with bench.py's swarm section and the scale chaos
    leg).  Returns ``(params, session_policy_fn, init_state_fn,
    obs_key, obs_dim)``."""
    import jax

    from sheeprl_tpu.algos.ppo_recurrent.agent import RecurrentPPOAgentModule
    from sheeprl_tpu.serve import make_recurrent_ppo_session_fns

    module = RecurrentPPOAgentModule(
        actions_dim=(2,),
        is_continuous=False,
        cnn_keys=(),
        mlp_keys=("state",),
        encoder_cfg=dict(
            cnn_features_dim=0, mlp_features_dim=16, dense_units=16,
            mlp_layers=1, dense_act="tanh", layer_norm=False,
        ),
        rnn_cfg={
            "lstm": {"hidden_size": hidden},
            "pre_rnn_mlp": {"apply": False, "dense_units": 8, "mlp_layers": 1,
                            "dense_act": "tanh", "layer_norm": False},
            "post_rnn_mlp": {"apply": False, "dense_units": 8, "mlp_layers": 1,
                             "dense_act": "tanh", "layer_norm": False},
        },
        actor_cfg=dict(dense_units=8, mlp_layers=1, dense_act="tanh", layer_norm=False),
        critic_cfg=dict(dense_units=8, mlp_layers=1, dense_act="tanh", layer_norm=False),
    )
    import jax.numpy as jnp
    import numpy as np

    k = jax.random.PRNGKey(seed)
    params = module.init(
        k,
        {"state": jnp.zeros((1, 1, obs_dim))},
        jnp.zeros((1, 1, 2)),
        jnp.ones((1, 1, 1)),
        jnp.zeros((1, hidden)),
        jnp.zeros((1, hidden)),
    )
    session_policy_fn, init_state_fn = make_recurrent_ppo_session_fns(module)
    del np
    return params, session_policy_fn, init_state_fn, "state", obs_dim


def warmup_buckets(session_fn, init_fn, params, obs_maker, max_batch: int) -> int:
    """Trace every power-of-two bucket once BEFORE the swarm starts, the
    way a production plane warms its traces at deploy: the report then
    measures steady-state serving, not the first batch's XLA compile.
    ``obs_maker(rows)`` builds one zero observation batch.  Returns the
    bucket count traced."""
    n = 0
    b = 1
    while b <= max_batch:
        state = init_fn(b, 0, params)
        session_fn(params, obs_maker(b), state)
        n += 1
        b *= 2
    return n


def run_pool_swarm(
    *,
    clients: int,
    steps: int,
    rows: int,
    think_mean_ms: float,
    think_sigma: float,
    pool_min: int,
    pool_max: int,
    seed: int = 0,
    deadline_ms: float = 2.0,
    max_batch: int = 16,
    slo_target_ms: float = 250.0,
    request_timeout_s: float = 1.0,
    session_capacity: int = 1024,
    session_ttl_s: float = 300.0,
):
    """The synthetic elastic-pool swarm (module docstring).  Returns
    ``(report, pool_stats)``."""
    import multiprocessing as mp

    from sheeprl_tpu.parallel.transport import make_transport
    from sheeprl_tpu.scale import Autoscaler, ServePool, run_swarm
    from sheeprl_tpu.serve.sessions import SessionInferenceServer

    import numpy as np

    params, session_fn, init_fn, obs_key, obs_dim = synthetic_session_parts(seed)
    warmup_buckets(
        session_fn, init_fn, params,
        lambda r: {obs_key: np.zeros((r, obs_dim), np.float32)},
        max_batch,
    )

    def factory(index: int, shared):
        return SessionInferenceServer(
            None,
            params,
            session_policy_fn=session_fn,
            init_state_fn=init_fn,
            shared=shared,
            capacity=session_capacity,
            idle_ttl_s=session_ttl_s,
            deadline_ms=deadline_ms,
            max_batch=max_batch,
            seed=seed,
            name=f"swarm-w{index}",
        )

    pool = ServePool(
        factory,
        min_workers=pool_min,
        max_workers=pool_max,
        autoscaler=Autoscaler(
            min_size=pool_min, max_size=pool_max,
            up_window_s=0.1, down_window_s=0.3,
            up_cooldown_s=0.2, down_cooldown_s=0.5,
            name="serve_pool",
        ),
        queue_high=4,
        queue_low=1,
    )
    pool.start()
    ctx = mp.get_context("spawn")
    hub, specs = make_transport(ctx, "queue", clients, window=8, min_bytes=0)
    for i in range(clients):
        pool.attach(i, hub.channel(i, timeout=5))
    try:
        report = run_swarm(
            [specs[i].player_channel() for i in range(clients)],
            steps=steps,
            rows=rows,
            obs_dim=obs_dim,
            obs_key=obs_key,
            think_mean_ms=think_mean_ms,
            think_sigma=think_sigma,
            seed=seed,
            client_kw={"request_timeout_s": request_timeout_s},
            slo_target_ms=slo_target_ms,
            control_tick=pool.control_tick,
        )
        stats = pool.stats()
    finally:
        pool.close()
        hub.close()
    return report, stats


def run_checkpoint_swarm(args):
    """Swarm one session server built from a trained checkpoint."""
    import multiprocessing as mp

    from scripts.serve_policy import build_server
    from sheeprl_tpu.parallel.transport import make_transport
    from sheeprl_tpu.scale import run_swarm
    from sheeprl_tpu.serve.sessions import SessionInferenceServer

    server, _, obs_keys, obs_space = build_server(
        args.checkpoint, greedy=False, deadline_ms=args.deadline_ms, max_batch=args.max_batch
    )
    if not isinstance(server, SessionInferenceServer):
        raise SystemExit(
            "swarm needs a recurrent family (recurrent PPO / Dreamer v3): "
            f"{args.checkpoint} built a stateless server"
        )
    import numpy as np

    def obs_fn(rng: "np.random.Generator", r: int):
        return [
            (k, rng.normal(size=(r,) + tuple(obs_space[k].shape)).astype(np.float32))
            for k in obs_keys
        ]

    warmup_buckets(
        server._session_policy_fn,
        server._init_state_fn,
        server._params,
        lambda r: {k: np.zeros((r,) + tuple(obs_space[k].shape), np.float32) for k in obs_keys},
        args.max_batch,
    )

    ctx = mp.get_context("spawn")
    hub, specs = make_transport(ctx, "queue", args.clients, window=8, min_bytes=0)
    for i in range(args.clients):
        server.attach(i, hub.channel(i, timeout=5))
    server.start()
    try:
        report = run_swarm(
            [specs[i].player_channel() for i in range(args.clients)],
            steps=args.steps,
            rows=args.rows,
            obs_fn=obs_fn,
            think_mean_ms=args.think_mean_ms,
            think_sigma=args.think_sigma,
            seed=args.seed,
            client_kw={"request_timeout_s": args.request_timeout},
            slo_target_ms=args.slo_target_ms,
        )
        stats = server.stats()
    finally:
        server.close()
        hub.close()
    return report, stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--checkpoint", default=None, help="recurrent ckpt_*.ckpt to serve (default: synthetic)")
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--steps", type=int, default=30, help="session steps per client")
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--think-mean-ms", type=float, default=2.0)
    ap.add_argument("--think-sigma", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline-ms", type=float, default=2.0)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--request-timeout", type=float, default=1.0)
    ap.add_argument("--slo-target-ms", type=float, default=250.0)
    ap.add_argument("--pool-min", type=int, default=1, help="synthetic mode: ServePool minimum workers")
    ap.add_argument("--pool-max", type=int, default=3, help="synthetic mode: ServePool maximum workers")
    ap.add_argument("--out", default=None, help="also write the report JSON here")
    args = ap.parse_args(argv)

    if args.checkpoint:
        report, stats = run_checkpoint_swarm(args)
    else:
        report, stats = run_pool_swarm(
            clients=args.clients,
            steps=args.steps,
            rows=args.rows,
            think_mean_ms=args.think_mean_ms,
            think_sigma=args.think_sigma,
            pool_min=args.pool_min,
            pool_max=args.pool_max,
            seed=args.seed,
            deadline_ms=args.deadline_ms,
            max_batch=args.max_batch,
            slo_target_ms=args.slo_target_ms,
            request_timeout_s=args.request_timeout,
        )
    out = dict(report.as_dict())
    out["server"] = {
        k: stats.get(k)
        for k in ("workers", "rebalanced", "requests", "dedup_hits", "sessions", "autoscale", "batch_hist")
        if k in stats
    }
    text = json.dumps(out, indent=2, default=str)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    ok = report.slo_ok and out.get("dropped", 1) == 0
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
