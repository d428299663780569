"""The language-model policies' loop through ``cli.run`` (ISSUE 32), both kinds
at tiny widths on the CPU: ``ppo.main``'s root spans reach ``telemetry.jsonl``
and cover an iteration of the fused path, the collector's counters ride the
``jaxenv`` section, an iteration fetches from the device as often as it did
before the spans, and the parameters after one whole iteration are the bits
the parent commit gave (``tests/test_envs_jax/lm_golden.json``)."""

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
              "Time/publish", "Time/loss_fetch", "Time/log")
NEW_SPANS = ("Time/collect_wait", "Time/collect_events", "Time/pack", "Time/publish")
COUNTERS = ("passes", "positions", "params_age")


@pytest.fixture(scope="module")
def loops(tmp_path_factory):
    """kind -> (telemetry records of six iterations, one record each; loss fetches the loop made)."""
    done = {}

    def run_of(kind):
        if kind in done:
            return done[kind]
        import sheeprl_tpu.algos.ppo.ppo as ppo
        from sheeprl_tpu.cli import run

        tmp = tmp_path_factory.mktemp(f"lm_loop_{kind}")
        fetches, inner = [], ppo.device_get_metrics
        ppo.device_get_metrics = lambda metrics: (fetches.append(1), inner(metrics))[1]
        timer.reset()  # a run's last Time/log closes after its last reset: keep it out of this run's first record
        try:
            run(lm_tiny.overrides(kind, str(tmp), iterations=ITERATIONS, response=RESP))
        finally:
            ppo.device_get_metrics = inner
        files = glob.glob(f"{tmp}/{kind}/**/telemetry.jsonl", recursive=True)
        assert files, "the run wrote no telemetry.jsonl"
        done[kind] = (read_records(files[0]), len(fetches))
        return done[kind]

    return run_of


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
    _, records, _ = loop
    assert len(records) == ITERATIONS
    assert _sums(records).get(name, 0.0) > 0.0
    assert any(name in r["timer_percentiles_s"] for r in records)


def test_root_spans_leave_under_two_per_cent_uncovered(loop):
    # the first record holds the compiles; shares are taken over the later ones, as the benchmark takes them.
    # Time/log shows one record late (the span holds the reset of its own interval): over five records it evens out
    _, records, _ = loop
    wall = records[-1]["ts"] - records[0]["ts"]
    sums = _sums(records[1:])
    covered = sum(sums.get(k, 0.0) for k in ROOT_SPANS)
    assert 0.0 < covered <= wall + 1e-3
    assert (wall - covered) / wall < 0.02, {k: sums.get(k) for k in ROOT_SPANS} | {"wall": wall}


def test_the_wait_for_the_rollout_is_a_span_of_its_own(loop):
    # the dispatch returns at once and the events' fetch waits for the device: the wait no longer hides
    _, records, _ = loop
    sums = _sums(records[1:])
    assert sums["Time/collect_wait"] > sums["Time/env_interaction_time"]


def test_counters_ride_the_jaxenv_section(loop):
    kind, records, _ = loop
    last = records[-1]["jaxenv"]
    assert all(k in last for k in COUNTERS), last
    passes = 1 + (RESP // 4) * 5 if kind == "sdar_moe" else 1 + RESP
    positions = ENVS * (P + (RESP // 4) * 5 * 4) if kind == "sdar_moe" else ENVS * (P + RESP)
    assert [r["jaxenv"]["rollouts"] for r in records] == list(range(1, ITERATIONS + 1))
    assert last["env_steps"] == ITERATIONS * ENVS * RESP
    assert last["passes"] == ITERATIONS * passes and last["positions"] == ITERATIONS * positions
    assert all(r["jaxenv"]["params_age"] == 0 for r in records)  # the serial path acts with the newest weights


def test_an_iteration_fetches_as_often_as_before(loop):
    # one loss fetch an iteration (metric.fetch_every=1), and the collector's one fetch of its events
    _, records, loss_fetches = loop
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
