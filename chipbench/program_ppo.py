"""The benchmark's door into ``ppo.main`` for the language-model policies' whole
loop (``program.py`` is DreamerV3's door, ``program_sdar.py`` /
``program_joyai.py`` build the update alone).

``PpoSpy`` is ``program.Spy``'s pattern: it wraps three names that ``ppo.main``
looks up at call time, so the program gains no hook: the observability set-up
(for ``on_iteration(policy_step)``), the episode update's builder (to count
update calls and to keep what the FIRST call was given and what it returned)
and the loss fetch (to mark an iteration boundary that follows one).  Its
``boundaries`` have the layout of ``program.Spy``'s, with update calls where
that one counts gradient steps, so ``drivers/loop.py``'s ``Window`` reads
either.

What the first update call leaves on the host, for the comparison that
decides ``correct``: the parameters the first rollout acted with (copied
before the call, which is donated them), the rollout as collection recorded
it and handed it to the update (``data``: prompts, actions, log-probabilities,
values, rewards, dones of every env), the parameters the call returned, its
metrics and its probe (what each of its minibatch steps produced).  The copies
wait for the device inside the first iteration, which is set-up."""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

from chipbench.program import read_telemetry, recompile_monitor, run_cli  # noqa: F401  (the drivers' one import)


class PpoSpy:
    def __init__(self, on_boundary: Optional[Callable[["PpoSpy"], None]] = None):
        self.runtime = self.policy = self.cfg = None
        self.update_calls = 0
        self.fetches = 0
        self.fetched: List[Dict[str, float]] = []  # what each loss fetch brought to the host
        # one entry per loop iteration, taken where the program calls on_iteration: (host time, policy steps
        # before it, update calls dispatched, whether the iteration before ended in a loss fetch)
        self.boundaries: List[tuple] = []
        self.first_call: Dict[str, Any] = {}
        self._fetches_seen = 0
        self._on_boundary = on_boundary

    def __enter__(self):
        import sheeprl_tpu.algos.ppo.ppo as ppo

        self._ppo = ppo
        self._orig = (ppo.make_episode_update_fn, ppo.setup_observability, ppo.device_get_metrics)
        ppo.make_episode_update_fn = self._make_episode_update_fn
        ppo.setup_observability = self._setup_observability
        ppo.device_get_metrics = self._device_get_metrics
        return self

    def __exit__(self, *exc):
        ppo = self._ppo
        ppo.make_episode_update_fn, ppo.setup_observability, ppo.device_get_metrics = self._orig

    def _make_episode_update_fn(self, runtime, policy, tx, cfg):
        fn = self._orig[0](runtime, policy, tx, cfg)
        self.runtime, self.policy, self.cfg = runtime, policy, cfg

        def counted(params, opt_state, data, *rest):
            if self.update_calls == 0:
                return self._first_update(fn, params, opt_state, data, *rest)
            self.update_calls += 1
            return fn(params, opt_state, data, *rest)

        counted.health = fn.health
        return counted

    def _first_update(self, fn, params, opt_state, data, *rest):
        import jax
        import numpy as np

        t0 = time.perf_counter()
        self.first_call.update(
            initial_params=jax.device_get(params),  # the update is donated them
            data={k: np.asarray(v) for k, v in data.items()},
        )
        out = fn(params, opt_state, data, *rest)
        self.update_calls += 1
        returned, metrics, probe = jax.device_get((out[0], out[2], out[3]))
        self.first_call.update(returned_params=returned, metrics=metrics, probe=probe, copies_s=time.perf_counter() - t0)
        return out

    def _setup_observability(self, *args, **kwargs):
        obs = self._orig[1](*args, **kwargs)
        inner = obs.on_iteration

        def on_iteration(policy_step: int = 0):
            after_fetch = self.fetches != self._fetches_seen
            self._fetches_seen = self.fetches
            self.boundaries.append((time.perf_counter(), int(policy_step), self.update_calls, after_fetch))
            if self._on_boundary is not None:
                self._on_boundary(self)
            return inner(policy_step)

        obs.on_iteration = on_iteration
        return obs

    def _device_get_metrics(self, metrics):
        out = self._orig[2](metrics)
        self.fetches += 1
        self.fetched.append({k: float(v) for k, v in out.items()})
        return out
