"""The trace reduction: on the recorded v5e slice it gives the recorded
numbers; on hand-built event lists it does what its docstring says."""

import json
import os

import pytest

from chipbench import trace_reduce as tr
from chipbench.layer_metrics import device_idle_pct, replay_feed_device_ms, update_device_ms, update_scan_pct

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")
PROGRAMS = {"update": "^jit_(train|guarded)", "feed": "sample|gather|slice|getitem|squeeze"}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "dv3_XL_train_slice.expected.json")) as f:
        expected = json.load(f)
    return tr.reduce_events(tr.load_events(os.path.join(DATA, "dv3_XL_train_slice.json.gz"))), expected


def test_recorded_slice_busy_and_idle(recorded):
    got, want = recorded
    for key in ("window_s", "busy_s", "idle_share"):
        assert got[key] == pytest.approx(want[key], rel=1e-6), key


def test_recorded_slice_per_program(recorded):
    got, want = recorded
    for pattern, numbers in want["programs"].items():
        prog = tr.program_matching(got, pattern)
        assert prog is not None, pattern
        for key, value in numbers.items():
            assert prog[key] == pytest.approx(value, rel=1e-6, abs=1e-12), (pattern, key)
    assert got["device_ops"][0][0] == want["top_op"][0]
    assert got["device_ops"][0][1] == pytest.approx(want["top_op"][1], rel=1e-6)


def test_recorded_slice_layer_metrics(recorded):
    got, want = recorded
    evidence = {"trace": got, "programs": PROGRAMS}
    readers = {m.NAME: m for m in (update_device_ms, update_scan_pct, replay_feed_device_ms, device_idle_pct)}
    for name, value in want["layer_metrics"].items():
        assert readers[name].read(evidence) == pytest.approx(value, rel=1e-5), name


def _table(ops, host=(), modules=(), asyncs=()):
    return {
        "devices": {"/device:TPU:0": {"ops": [list(o) for o in ops], "async": [list(a) for a in asyncs],
                                      "modules": [list(m) for m in modules]}},
        "host": [list(h) for h in host],
    }


def test_overlapping_and_nested_ops_count_once():
    # a while of 100 us enclosing two ops, and an op overlapping the next
    ops = [("while.1 s32[]", 0, 100_000), ("fusion.1 f32[8]", 10_000, 30_000), ("fusion.2 f32[8]", 50_000, 40_000),
           ("fusion.3 f32[8]", 200_000, 100_000), ("fusion.4 f32[8]", 250_000, 100_000)]
    s = tr.reduce_events(_table(ops, modules=[("jit_train(1)", 0, 400_000)]), window=(0.0, 400_000.0))
    assert s["busy_s"] == pytest.approx(250e-6)  # [0,100] + [200,350], not the 370 us of the durations
    assert s["idle_share"] == pytest.approx(1 - 250 / 400)
    selfs = dict((name, t) for name, t in s["device_ops"])
    assert selfs["while.1 s32[]"] == pytest.approx(30e-6)  # 100 - (30 + 40)
    assert tr.program_matching(s, "^jit_train")["while_s"] == pytest.approx(100e-6)


def test_gap_goes_to_the_span_that_covers_it():
    ops = [("fusion.1 f32[8]", 0, 100_000), ("fusion.2 f32[8]", 600_000, 100_000), ("fusion.3 f32[8]", 1_000_000, 50_000)]
    host = [("chipbench:window", 0, 1_050_000), ("Time/env_interaction_time", 90_000, 520_000),
            ("PjitFunction(step)", 120_000, 30_000), ("block_until_ready", 690_000, 320_000)]
    s = tr.reduce_events(_table(ops, host=host))
    gaps = dict((name, t) for name, t in s["idle_gaps"])
    assert gaps["Time/env_interaction_time"] == pytest.approx(500e-6)  # the gap [100, 600] us
    assert gaps["block_until_ready"] == pytest.approx(300e-6)  # the gap [700, 1000] us
    assert "PjitFunction(step)" not in gaps and "chipbench:window" not in gaps


def test_unlabelled_gap_and_short_gaps():
    ops = [("fusion.1 f32[8]", 0, 100_000), ("fusion.2 f32[8]", 105_000, 100_000), ("fusion.3 f32[8]", 500_000, 10_000)]
    s = tr.reduce_events(_table(ops))
    gaps = dict((name, t) for name, t in s["idle_gaps"])
    assert gaps["unlabelled"] == pytest.approx(295e-6)
    assert gaps[tr.SHORT_GAP] == pytest.approx(5e-6)


def test_collectives_and_their_exposed_part():
    ops = [("fusion.1 f32[8]", 0, 100_000), ("all-reduce.1 f32[8]", 150_000, 50_000)]
    asyncs = [("all-reduce-start.2 f32[8]", 50_000, 100_000)]  # in flight 50..150 us, hidden until 100
    s = tr.reduce_events(_table(ops, asyncs=asyncs, modules=[("jit_train(1)", 0, 200_000)]), window=(0.0, 200_000.0))
    assert s["collective_s"] == pytest.approx(150e-6)
    assert s["collective_exposed_s"] == pytest.approx(100e-6)


def test_a_trace_without_a_device_plane_is_refused():
    with pytest.raises(RuntimeError, match="no device plane"):
        tr.reduce_events({"devices": {}, "host": [["chipbench:window", 0, 10]]})


def test_short_name():
    text = ("%fusion.7 = (bf16[8,4]{1,0:T(8,128)(2,1)}, f32[8]{0}) fusion(f32[8,4]{1,0} "
            "%params__world_model____rssm____kernel__.1, f32[] %sub.2), kind=kLoop")
    assert tr.short_name(text) == "fusion.7 bf16[8,4] <- params.world_model.rssm.kernel..1"
    assert tr.short_name("%while.12 = (s32[], f32[16]) while(%t), body=%b").startswith("while.12")
    assert tr.COLLECTIVE_RE.match(tr.short_name("%all-reduce-start.3 = f32[4]{0} all-reduce-start(f32[4] %x)"))
