"""``span_reduce`` on hand-built event tables and telemetry records: nesting
and self time, an idle gap that lies across two spans, time under no span, and
the shares a program without the new timers (the parent) gives: none."""

import pytest

from chipbench import span_reduce as sr
from chipbench.layer_metrics import (
    loop_env_step_pct,
    loop_feed_dispatch_pct,
    loop_fetch_pct,
    loop_player_step_pct,
    loop_refresh_pct,
    loop_replay_add_pct,
    loop_uncovered_pct,
)

US = 1_000.0


def _table(ops, host):
    return {
        "devices": {"/device:TPU:0": {"ops": [list(o) for o in ops], "async": [], "modules": []}},
        "host": [list(h) for h in host],
    }


# one iteration: env_interaction [0, 600] holding player_step [10, 400], replay_add [410, 430] and
# env_step [440, 590]; then train_time [620, 640], params_refresh [650, 950]; a second replay_add,
# outside the step, [960, 970]
HOST = [
    ("Time/env_interaction_time", 0 * US, 600 * US), ("Time/player_step", 10 * US, 390 * US),
    ("Time/replay_add", 410 * US, 20 * US), ("Time/env_step", 440 * US, 150 * US),
    ("Time/train_time", 620 * US, 20 * US), ("Time/params_refresh", 650 * US, 300 * US),
    ("Time/replay_add", 960 * US, 10 * US), ("PjitFunction(step)", 20 * US, 100 * US),
]


def test_nesting_and_self_time():
    spans = [h for h in HOST if h[0].startswith(sr.SPAN_PREFIX)]
    roots = sr.forest(spans)
    assert [r["name"] for r in roots] == ["Time/env_interaction_time", "Time/train_time", "Time/params_refresh",
                                          "Time/replay_add"]
    assert [c["name"] for c in roots[0]["children"]] == ["Time/player_step", "Time/replay_add", "Time/env_step"]
    got = sr.self_seconds(spans)
    assert got["Time/env_interaction_time"]["seconds"] == pytest.approx(600e-6)
    # 600 - (390 + 20 + 150)
    assert got["Time/env_interaction_time"]["self_seconds"] == pytest.approx(40e-6)
    assert got["Time/replay_add"] == {"seconds": pytest.approx(30e-6), "self_seconds": pytest.approx(30e-6), "count": 2}
    assert got["Time/player_step"]["self_seconds"] == got["Time/player_step"]["seconds"] == pytest.approx(390e-6)


def test_a_gap_across_two_spans_is_cut_at_their_boundary():
    # the device runs [0, 100] and [700, 900]: idle [100, 700] and [900, 1000]
    ops = [("fusion.1 f32[8]", 0, 100 * US), ("fusion.2 f32[8]", 700 * US, 200 * US)]
    table = _table(ops, HOST)
    idle = sr.idle_by_span(table, (0.0, 1000 * US))
    assert idle["Time/player_step"] == pytest.approx(300e-6)  # [100, 400] of the first gap
    assert idle["Time/replay_add"] == pytest.approx(30e-6)  # [410, 430] and, in the second gap, [960, 970]
    assert idle["Time/env_step"] == pytest.approx(150e-6)
    assert idle["Time/env_interaction_time"] == pytest.approx(30e-6)  # its self time: [400,410] [430,440] [590,600]
    assert idle["Time/train_time"] == pytest.approx(20e-6)
    assert idle["Time/params_refresh"] == pytest.approx(100e-6)  # [650, 700] and [900, 950]
    # under no span: [600, 620], [640, 650], [950, 960], [970, 1000]
    assert idle[sr.UNSPANNED] == pytest.approx(70e-6)
    assert sum(idle.values()) == pytest.approx(700e-6)
    assert "PjitFunction(step)" not in idle


def test_no_device_lane_gives_nothing():
    assert sr.idle_by_span({"devices": {}, "host": [list(h) for h in HOST]}, (0.0, 1000 * US)) is None


def _records(timers):
    return [{"train_step": 1, "ts": 10.0, "timers_s": {}},
            {"train_step": 2, "ts": 12.0, "timers_s": timers},
            {"train_step": 3, "ts": 14.0, "timers_s": timers}]


def test_shares_over_the_records_wall():
    timers = {"Time/env_interaction_time": 1.2, "Time/player_step": 1.0, "Time/env_step": 0.1, "Time/replay_add": 0.06,
              "Time/feed_dispatch": 0.02, "Time/train_time": 0.04, "Time/params_refresh": 0.6, "Time/loss_fetch": 0.01,
              "Time/log": 0.03}
    evidence = {"telemetry": _records(timers)}
    assert loop_player_step_pct.read(evidence) == pytest.approx(50.0)  # 2 x 1.0 over 4 s
    assert loop_env_step_pct.read(evidence) == pytest.approx(5.0)
    assert loop_replay_add_pct.read(evidence) == pytest.approx(3.0)
    assert loop_feed_dispatch_pct.read(evidence) == pytest.approx(1.0)
    assert loop_refresh_pct.read(evidence) == pytest.approx(30.0)
    assert loop_fetch_pct.read(evidence) == pytest.approx(0.5)
    # 100 - (1.2 + 0.02 + 0.04 + 0.6 + 0.01 + 0.03) x 2 / 4
    assert loop_uncovered_pct.read(evidence) == pytest.approx(5.0)


def test_a_program_without_the_timers_reports_nothing():
    parent = {"telemetry": _records({"Time/env_interaction_time": 1.2, "Time/train_time": 0.04})}
    for reader in (loop_player_step_pct, loop_env_step_pct, loop_replay_add_pct, loop_feed_dispatch_pct,
                   loop_refresh_pct, loop_fetch_pct, loop_uncovered_pct):
        assert reader.read(parent) is None, reader.NAME
        assert reader.read({}) is None and reader.read({"telemetry": []}) is None
