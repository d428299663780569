"""Standalone policy serving: point the InferenceServer at a checkpoint.

The same serving plane the decoupled loops embed (serve/service.py), run
as a process of its own for offline/production serving: load a trained
checkpoint, open a TCP listener (or in-process channels with
``--selftest``), and answer observation frames with actions — with
deadline batching, bucketed XLA traces, request-id dedupe, graceful
SIGTERM drain, and (``--watch``) validated hot checkpoint swap: newly
good-tagged checkpoints under the run root are spot-checked and swapped
in between batches; quarantined/corrupt candidates are refused and
logged.

Serve the newest checkpoint of a run over tcp::

    python scripts/serve_policy.py --checkpoint logs/.../ckpt_1024_0.ckpt \
        --host 0.0.0.0 --port 7501 --watch

Env workers connect with the client half::

    from sheeprl_tpu.parallel.transport import TcpChannel
    from sheeprl_tpu.serve import InferenceClient
    chan = TcpChannel(address=(host, 7501), player_id=0, reconnect=True)
    client = InferenceClient(chan, 0)
    out, src = client.infer([("state", obs)], rows)

``--selftest N`` instead drives the server with N in-process clients on
random observations and prints the latency/batching stats as JSON — the
quickest way to see the serving envelope working without a second
process.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

# runnable as `python scripts/serve_policy.py`
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def _load_run_cfg(ckpt_path: str):
    """The run config saved next to the checkpoint (same resolution as
    the evaluation app: <run>/config.yaml two levels up, falling back to
    the checkpoint's own directory)."""
    from sheeprl_tpu.config import dotdict
    from sheeprl_tpu.config.compose import yaml_load

    ckpt_dir = os.path.dirname(os.path.dirname(os.path.abspath(ckpt_path)))
    cfg_path = os.path.join(ckpt_dir, "config.yaml")
    if not os.path.exists(cfg_path):
        cfg_path = os.path.join(os.path.dirname(os.path.abspath(ckpt_path)), "config.yaml")
    if not os.path.exists(cfg_path):
        raise RuntimeError(f"Cannot find the run config next to the checkpoint: {cfg_path}")
    with open(cfg_path) as f:
        return dotdict(yaml_load(f.read()))


def build_server(
    ckpt_path: str,
    *,
    greedy: bool = True,
    deadline_ms: float = 5.0,
    max_batch: int = 64,
    session_capacity: int = 1024,
    session_ttl_s: float = 300.0,
):
    """Checkpoint -> a ready (not yet started) server + the obs keys its
    requests must carry.  Stateless families (PPO/SAC) get the PR-8
    InferenceServer; recurrent families (recurrent PPO, Dreamer v3) get
    the SESSION tier — clients must speak the session protocol
    (SessionClient.step), because a recurrent policy served statelessly
    is meaningless."""
    import gymnasium as gym

    from sheeprl_tpu.parallel.mesh import MeshRuntime
    from sheeprl_tpu.serve import (
        agent_params_loader,
        make_dreamer_session_fns,
        make_ppo_policy_fn,
        make_recurrent_ppo_session_fns,
        make_sac_policy_fn,
    )
    from sheeprl_tpu.serve import build_server as _make_server
    from sheeprl_tpu.utils.env import make_env

    cfg = _load_run_cfg(ckpt_path)
    algo = str(cfg.algo.name)
    if algo.startswith("ppo_recurrent"):
        family = "ppo_recurrent"
    elif algo.startswith("dreamer_v3"):
        family = "dreamer_v3"
    elif algo.startswith(("ppo", "a2c")):
        family = "ppo"
    elif algo.startswith(("sac", "droq")):
        family = "sac"
    else:
        raise ValueError(
            f"serve_policy supports the PPO/SAC/recurrent-PPO/Dreamer-v3 families, got algo={algo!r}"
        )

    # serves on what JAX finds (the chip when there is one); launch() also
    # places the persistent compile cache
    runtime = MeshRuntime(devices=1, precision=cfg.fabric.get("precision", "32-true"))
    runtime.launch()
    cfg.env.capture_video = False
    env = make_env(cfg, int(cfg.get("seed", 0)), 0, None, "serve", vector_env_idx=0)()
    observation_space, action_space = env.observation_space, env.action_space
    env.close()

    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape
        if is_continuous
        else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )

    policy_fn = session_policy_fn = init_state_fn = None
    if family == "ppo":
        from sheeprl_tpu.algos.ppo.agent import build_agent

        loader = agent_params_loader("agent")
        params = loader(ckpt_path)
        module, params = build_agent(runtime, actions_dim, is_continuous, cfg, observation_space, params)
        policy_fn = make_ppo_policy_fn(module, cfg.algo.cnn_keys.encoder, greedy=greedy)
        obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)
    elif family == "ppo_recurrent":
        from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent

        loader = agent_params_loader("agent")
        params = loader(ckpt_path)
        module, params = build_agent(runtime, actions_dim, is_continuous, cfg, observation_space, params)
        session_policy_fn, init_state_fn = make_recurrent_ppo_session_fns(module, greedy=greedy)
        obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)
    elif family == "dreamer_v3":
        from sheeprl_tpu.algos.dreamer_v3.agent import build_agent

        from sheeprl_tpu.utils.callback import load_checkpoint

        def loader(path: str):
            # Dreamer checkpoints carry top-level world_model/actor trees;
            # serving needs exactly the player's composite
            state = load_checkpoint(path)
            return {"world_model": state["world_model"], "actor": state["actor"]}

        state = loader(ckpt_path)
        world_model, actor_mod, _, params = build_agent(
            runtime, actions_dim, is_continuous, cfg, observation_space,
            state["world_model"], state["actor"],
        )
        params = {"world_model": params["world_model"], "actor": params["actor"]}
        wm_cfg = cfg.algo.world_model
        session_policy_fn, init_state_fn = make_dreamer_session_fns(
            world_model,
            actor_mod,
            actions_dim=actions_dim,
            stochastic_size=int(wm_cfg.stochastic_size),
            discrete_size=int(wm_cfg.discrete_size),
            recurrent_state_size=int(wm_cfg.recurrent_model.recurrent_state_size),
            decoupled_rssm=bool(wm_cfg.get("decoupled_rssm", False)),
            greedy=greedy,
        )
        obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)
    else:
        from sheeprl_tpu.algos.sac.agent import build_agent

        # decoupled SAC checkpoints carry the full agent tree; serving
        # needs only the actor subtree
        loader = agent_params_loader("agent")
        state_agent = loader(ckpt_path)
        actor, _, params, _ = build_agent(runtime, cfg, observation_space, action_space, state_agent)
        params = params["actor"]
        policy_fn = make_sac_policy_fn(actor, cfg.algo.mlp_keys.encoder, greedy=greedy)
        loader = agent_params_loader("agent/actor")
        obs_keys = list(cfg.algo.mlp_keys.encoder)

    server = _make_server(
        policy_fn,
        params,
        session={
            "enabled": session_policy_fn is not None,
            "capacity": int(session_capacity),
            "idle_ttl_s": float(session_ttl_s),
        },
        session_policy_fn=session_policy_fn,
        init_state_fn=init_state_fn,
        deadline_ms=deadline_ms,
        max_batch=max_batch,
        seed=int(cfg.get("seed", 0)),
        name=algo,
    )
    server.swap_params(params, source=os.path.abspath(ckpt_path))
    return server, loader, obs_keys, observation_space


def run_selftest(server, obs_keys, observation_space, n_clients: int, n_requests: int) -> int:
    """Drive the server with in-process clients over queue channels."""
    import multiprocessing as mp
    import threading

    import numpy as np

    from sheeprl_tpu.parallel.transport import make_transport
    from sheeprl_tpu.serve import InferenceClient

    from sheeprl_tpu.serve import SessionClient, SessionInferenceServer

    sessions = isinstance(server, SessionInferenceServer)
    ctx = mp.get_context("spawn")
    hub, specs = make_transport(ctx, "queue", n_clients, window=4, min_bytes=0)
    make_client = (lambda ch, i: SessionClient(ch, i, seed=i)) if sessions else InferenceClient
    clients = [make_client(specs[i].player_channel(), i) for i in range(n_clients)]
    for i in range(n_clients):
        server.attach(i, hub.channel(i, timeout=5))
    server.start()

    failures = []

    def drive(cid: int) -> None:
        rng = np.random.default_rng(cid)
        for _ in range(n_requests):
            obs = {
                k: rng.normal(size=(1,) + tuple(observation_space[k].shape)).astype(np.float32)
                for k in obs_keys
            }
            arrays = [(k, v) for k, v in obs.items()]
            if sessions:
                out, src = clients[cid].step(arrays, 1)
            else:
                out, src = clients[cid].infer(arrays, 1)
            if src != "remote" or out is None:
                failures.append(cid)
                return
        if sessions:
            clients[cid].close_session()

    threads = [threading.Thread(target=drive, args=(i,)) for i in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stats = server.stats()
    stats["selftest"] = {
        "clients": n_clients,
        "requests_per_client": n_requests,
        "wall_s": round(wall, 3),
        "actions_per_s": round(n_clients * n_requests / wall, 1),
        "failures": len(failures),
    }
    print(json.dumps(stats, indent=2))
    server.close()
    hub.close()
    return 1 if failures else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--checkpoint", required=True, help="ckpt_*.ckpt to serve")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=7501)
    ap.add_argument("--deadline-ms", type=float, default=5.0)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--sample", action="store_true", help="sample actions instead of greedy")
    ap.add_argument("--session-capacity", type=int, default=1024,
                    help="session-cache LRU bound (recurrent families)")
    ap.add_argument("--session-ttl", type=float, default=300.0,
                    help="session idle TTL in seconds (recurrent families)")
    ap.add_argument(
        "--watch", action="store_true",
        help="hot-swap: watch the run root for newly good-tagged checkpoints",
    )
    ap.add_argument("--watch-interval", type=float, default=2.0)
    ap.add_argument("--stats-every", type=float, default=10.0, help="stats JSON line cadence (s)")
    ap.add_argument("--selftest", type=int, default=0, metavar="N", help="drive with N in-process clients and exit")
    ap.add_argument("--selftest-requests", type=int, default=64)
    args = ap.parse_args(argv)

    server, loader, obs_keys, obs_space = build_server(
        args.checkpoint,
        greedy=not args.sample,
        deadline_ms=args.deadline_ms,
        max_batch=args.max_batch,
        session_capacity=args.session_capacity,
        session_ttl_s=args.session_ttl,
    )
    if args.watch:
        run_root = os.path.dirname(os.path.dirname(os.path.abspath(args.checkpoint)))
        server.watch(run_root, loader, interval_s=args.watch_interval)

    if args.selftest > 0:
        return run_selftest(server, obs_keys, obs_space, args.selftest, args.selftest_requests)

    from sheeprl_tpu.parallel.transport import TcpListener

    listener = TcpListener(args.host, args.port, window=8)
    print(f"serving {args.checkpoint} on {listener.address} (obs keys: {obs_keys})", flush=True)

    # adopt clients as they dial in (the hello frame carries their id)
    import threading

    def adopt_loop() -> None:
        seen = set()
        while server.alive or not server._stop.is_set():
            with listener._cond:
                pids = list(listener._channels)
            for pid in pids:
                if pid not in seen:
                    seen.add(pid)
                    server.attach(pid, listener._channels[pid])
                    print(f"client {pid} connected", flush=True)
            time.sleep(0.2)

    threading.Thread(target=adopt_loop, daemon=True).start()
    server.start()

    # SIGTERM/SIGINT: graceful drain — answer pending, send stop frames
    def on_term(signum, frame):
        print("drain requested", flush=True)
        server.request_drain()

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)

    last = 0.0
    while server._thread is not None and server._thread.is_alive():
        time.sleep(0.2)
        if time.monotonic() - last >= args.stats_every:
            last = time.monotonic()
            print(json.dumps(server.stats()), flush=True)
    print(json.dumps(server.stats()), flush=True)
    server.close()
    listener.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
