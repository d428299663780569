"""The language-model policies' loop through ``cli.run`` (ISSUE 32, 34), both
kinds at tiny widths on the CPU: ``ppo.main``'s root spans reach
``telemetry.jsonl`` and cover an iteration of the fused path (the wait for the
update under its own name, a save under ``Time/checkpoint``), the collector's
counters ride the ``jaxenv`` section, an iteration fetches from the device as
often as stated, and the parameters after one whole iteration are the bits the
parent commit gave (``tests/test_envs_jax/lm_golden.json``)."""

import glob
import hashlib

import numpy as np
import pytest

from sheeprl_tpu.obs import read_records
from sheeprl_tpu.utils.timer import timer
from tests.test_envs_jax import lm_tiny
from tests.test_envs_jax.lm_tiny import ENVS, KINDS, P

ITERATIONS = 6
RESP = 64  # four times the collectors' tests: the device's share of an iteration at the tiny widths
# the spans that tile an iteration of the fused path, none inside another
ROOT_SPANS = ("Time/env_interaction_time", "Time/collect_wait", "Time/collect_events", "Time/pack", "Time/train_time",
              "Time/update_wait", "Time/publish", "Time/loss_fetch", "Time/log", "Time/checkpoint")
NEW_SPANS = ("Time/collect_wait", "Time/collect_events", "Time/pack", "Time/update_wait", "Time/publish")
COUNTERS = ("passes", "positions", "params_age", "experts_reached")
SAVING = "sdar_moe+save"  # the block-diffusion loop once more, with a save every second iteration


@pytest.fixture(scope="module")
def loops(tmp_path_factory):
    """kind -> (telemetry records of six iterations, one record each; loss fetches the loop made; the
    ``timer`` regions of the run in order, ``(name, "open" | "close")``)."""
    done = {}

    def run_of(case):
        if case in done:
            return done[case]
        import sheeprl_tpu.algos.ppo.ppo as ppo
        from sheeprl_tpu.cli import run

        kind = case.split("+")[0]
        tmp = tmp_path_factory.mktemp(f"lm_loop_{kind}")
        overrides = lm_tiny.overrides(kind, str(tmp), iterations=ITERATIONS, response=RESP)
        if case == SAVING:
            overrides += [f"checkpoint.every={2 * ENVS * RESP}"]
        fetches, inner = [], ppo.device_get_metrics
        ppo.device_get_metrics = lambda metrics: (fetches.append(1), inner(metrics))[1]
        order, enter, leave = [], timer.__enter__, timer.__exit__
        timer.__enter__ = lambda self: (order.append((self.name, "open")), enter(self))[1]
        timer.__exit__ = lambda self, *exc: (order.append((self.name, "close")), leave(self, *exc))[1]
        timer.reset()  # a run's last Time/log closes after its last reset: keep it out of this run's first record
        try:
            run(overrides)
        finally:
            ppo.device_get_metrics = inner
            timer.__enter__, timer.__exit__ = enter, leave
        files = glob.glob(f"{tmp}/{kind}/**/telemetry.jsonl", recursive=True)
        assert files, "the run wrote no telemetry.jsonl"
        done[case] = (read_records(files[0]), len(fetches), order)
        return done[case]

    return run_of


@pytest.fixture(scope="module", params=KINDS + (SAVING,))
def any_loop(request, loops):
    return (request.param, *loops(request.param))


@pytest.fixture(scope="module", params=KINDS)
def loop(request, loops):
    return (request.param, *loops(request.param))


def _sums(records):
    out = {}
    for r in records:
        for k, v in r["timers_s"].items():
            out[k] = out.get(k, 0.0) + v
    return out


@pytest.mark.parametrize("name", NEW_SPANS + ("Time/log",))
def test_new_spans_reach_telemetry(loop, name):
    _, records, *_ = loop
    assert len(records) == ITERATIONS
    assert _sums(records).get(name, 0.0) > 0.0
    assert any(name in r["timer_percentiles_s"] for r in records)


def test_root_spans_leave_under_two_per_cent_uncovered(any_loop):
    # the first record holds the compiles; shares are taken over the later ones, as the benchmark takes them.
    # Time/log shows one record late (the span holds the reset of its own interval), and so does Time/checkpoint,
    # which follows it in an iteration that saves: over five records it evens out
    case, records, *_ = any_loop
    wall = records[-1]["ts"] - records[0]["ts"]
    sums = _sums(records[1:])
    covered = sum(sums.get(k, 0.0) for k in ROOT_SPANS)
    assert 0.0 < covered <= wall + 1e-3
    assert (wall - covered) / wall < 0.02, {k: sums.get(k) for k in ROOT_SPANS} | {"wall": wall}
    # a save is a span of the iteration that saves, and of no other
    saves = [r["ckpt"]["saves"] for r in records]
    assert (sums.get("Time/checkpoint", 0.0) > 0.0) == (case == SAVING) == (saves[-1] > 0)
    if case == SAVING:
        assert saves[-1] == ITERATIONS // 2 - 1  # (the last iteration's save follows the last record)
        for before, r in zip(records, records[1:]):
            assert ("Time/checkpoint" in r["timers_s"]) == (before["ckpt"]["saves"] < r["ckpt"]["saves"]), r["step"]


def test_roots_open_one_after_the_other(any_loop):
    """None of the root spans lies inside another, and the wait for the update closes before the hand-over opens."""
    *_, order = any_loop
    roots = [(name, what) for name, what in order if name in ROOT_SPANS]
    assert all(a[1] == "open" and b == (a[0], "close") for a, b in zip(roots[::2], roots[1::2])), roots[:24]
    opened = [name for name, what in roots if what == "open"]
    after_wait = [b for a, b in zip(opened, opened[1:]) if a == "Time/update_wait"]
    assert len(after_wait) == ITERATIONS and set(after_wait) == {"Time/publish"}


def test_the_wait_for_the_rollout_is_a_span_of_its_own(loop):
    # the dispatch returns at once and the events' fetch waits for the device: the wait no longer hides
    _, records, *_ = loop
    sums = _sums(records[1:])
    assert sums["Time/collect_wait"] > sums["Time/env_interaction_time"]


def test_the_wait_for_the_update_has_left_the_hand_over(loop):
    # ppo.main blocks on the new parameters under Time/update_wait; publish's own barrier then returns at once
    _, records, *_ = loop
    sums = _sums(records[1:])
    assert sums["Time/update_wait"] > 10 * sums["Time/publish"] and sums["Time/update_wait"] > sums["Time/train_time"]


def test_counters_ride_the_jaxenv_section(loop):
    kind, records, *_ = loop
    last = records[-1]["jaxenv"]
    assert all(k in last for k in COUNTERS), last
    passes = 1 + (RESP // 4) * 5 if kind == "sdar_moe" else 1 + RESP
    positions = ENVS * (P + (RESP // 4) * 5 * 4) if kind == "sdar_moe" else ENVS * (P + RESP)
    assert [r["jaxenv"]["rollouts"] for r in records] == list(range(1, ITERATIONS + 1))
    assert last["env_steps"] == ITERATIONS * ENVS * RESP
    assert last["passes"] == ITERATIONS * passes and last["positions"] == ITERATIONS * positions
    assert all(r["jaxenv"]["params_age"] == 0 for r in records)  # the serial path acts with the newest weights
    # what the rollouts counted on the device (tests/test_envs_jax/test_lm_collect.py holds one rollout's to a count by
    # hand): cumulative, and no row reaches more than its k choices
    reached = [r["jaxenv"]["experts_reached"] for r in records]
    assert all(b > a for a, b in zip(reached, reached[1:]))
    assert 0 < reached[-1] < last["positions"] * 2 * 2  # (top-2, two routed layers)
    assert "held_assignments" not in last  # (the review took it out: nothing read it)


def test_an_iteration_fetches_as_often_as_before(loop):
    # one loss fetch an iteration (metric.fetch_every=1), and the collector's one fetch of its events, which since
    # ISSUE 34 brings one scalar more, the rollout's count (test_lm_collect.py counts the arrays: 4 where it was 3)
    _, records, loss_fetches, _ = loop
    assert loss_fetches == ITERATIONS
    assert records[-1]["jaxenv"]["event_fetches"] == ITERATIONS


@pytest.mark.parametrize("kind", KINDS)
def test_one_iteration_is_bit_equal_to_the_parents(kind, tmp_path):
    golden = lm_tiny.load_golden()
    want = golden["kinds"][kind]["params_after_one_iteration"]
    got = lm_tiny.params_after_one_iteration(kind, str(tmp_path))
    assert sorted(got) == sorted(want)
    if lm_tiny.canary() == golden["canary"]:
        differ = [k for k, v in got.items() if hashlib.sha256(np.ascontiguousarray(v).tobytes()).hexdigest() != want[k]["sha256"]]
        assert not differ, differ
    else:  # another machine's rounding: the leaves' sums instead of their bits
        for k, v in got.items():
            np.testing.assert_allclose(np.asarray(v, np.float64).sum(), want[k]["sum"], rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.abs(np.asarray(v, np.float64)).sum(), want[k]["abs_sum"], rtol=1e-4)
