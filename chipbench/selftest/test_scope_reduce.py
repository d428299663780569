"""``scope_reduce`` on hand-built ops (an op with two tokens, an op with none,
a ``while`` without a path, nesting) and on a hand-encoded ``xplane`` file."""

import pytest

from chipbench import scope_reduce as sc
from chipbench.layer_metrics import update_optim_ms, update_unscoped_pct, update_wm_dynamics_ms

US = 1_000.0


def test_owner_is_the_innermost_token():
    assert sc.owner("jit(train)/transpose(jvp(wm_dynamics))/RSSM._transition/dot_general:") == "wm_dynamics"
    assert sc.owner("jit(train)/wm_optim/add:") == "wm_optim"
    assert sc.owner("jit(train)/jvp(bh_imagine)/while/body/closed_call/bh_imagine/RSSM.imagination/mul:") == "bh_imagine"
    assert sc.owner("jit(train)/bh_actor/Actor/bh_critic/mul:") == "bh_critic"  # two tokens: the last
    assert sc.owner("jit(train)/reduce_sum:") is None and sc.owner("") is None
    assert sc.owner("jit(train)/xwm_heads_old/mul:") is None  # a token is a whole word


def _devices():
    ops = [
        ["fusion.a f32[8]", 0 * US, 100 * US, "jit(train)/jvp(wm_encoder)/Conv_0/conv_general_dilated:"],
        ["while.1 s32[]", 100 * US, 400 * US, ""],  # no path: most of its inside is wm_dynamics
        ["fusion.b f32[8]", 110 * US, 90 * US, "jit(train)/jvp(wm_dynamics)/while/body/dot_general:"],
        ["fusion.c f32[8]", 210 * US, 50 * US, ""],  # no path: takes the while's owner
        ["while.2 s32[]", 300 * US, 150 * US, ""],  # nested: not an outermost while
        ["fusion.d f32[8]", 310 * US, 130 * US, "jit(train)/transpose(jvp(wm_dynamics))/while/body/mul:"],
        ["fusion.e f32[8]", 500 * US, 100 * US, "jit(train)/bh_actor/x/bh_imagine/mul:"],
        ["fusion.f f32[8]", 600 * US, 50 * US, "jit(train)/reduce_sum:"],  # no token, nothing around it
        ["copy.1 u8[8]", 1010 * US, 100 * US, "jit(_sample)/gather:"],  # another program
        ["fusion.a f32[8]", 2000 * US, 100 * US, "jit(train)/jvp(wm_encoder)/Conv_0/conv_general_dilated:"],
    ]
    modules = [["jit_train(1)", 0.0, 1000 * US], ["jit__sample(2)", 1000 * US, 200 * US],
               ["jit_train(1)", 2000 * US, 1000 * US]]  # the second execution ends outside the window
    return {"/device:TPU:0": {"ops": ops, "modules": modules}}


def test_self_time_goes_to_the_owner():
    got = sc.by_scope(_devices(), (0.0, 2500 * US), "^jit_(train|guarded)")
    assert got["count"] == 1 and got["seconds"] == pytest.approx(1000e-6)
    s = got["self_s"]
    assert s["wm_encoder"] == pytest.approx(100e-6)
    # while.1 keeps 400 - (90 + 50 + 150) = 110, while.2 keeps 150 - 130 = 20; b, c and d add 90 + 50 + 130
    assert s["wm_dynamics"] == pytest.approx(400e-6)
    assert s["bh_imagine"] == pytest.approx(100e-6) and "bh_actor" not in s
    assert s[sc.UNSCOPED] == pytest.approx(50e-6)
    assert sum(s.values()) == pytest.approx(650e-6)  # nothing counted twice
    assert got["while_s"] == {"wm_dynamics": pytest.approx(400e-6)}  # the outermost while only
    assert sc.top_ops(got, 1)["wm_dynamics"] == [["fusion.d f32[8]", pytest.approx(130e-6)]]


def test_no_execution_in_the_window_gives_nothing():
    assert sc.by_scope(_devices(), (0.0, 500 * US), "^jit_(train|guarded)") is None
    assert sc.by_scope(_devices(), (0.0, 2500 * US), "^jit_nothing") is None


def test_readers_report_nothing_without_a_trace_or_scopes():
    for reader in (update_wm_dynamics_ms, update_optim_ms, update_unscoped_pct):
        assert reader.read({}) is None
        assert reader.read({"programs": {"update": "^jit_train"}}) is None  # an untraced run


# ------------------------------------------------------ a hand-encoded file
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(num, value):
    return _varint(num << 3) + _varint(value)


def _msg(num, payload):
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _double(num):
    return _varint(num << 3 | 1) + b"\x00" * 8


def _plane(name, lines, event_meta, stat_meta):
    out = _int(1, 7) + _msg(2, name)
    for line_name, t0_ns, events in lines:
        body = _int(1, 1) + _msg(2, line_name) + _int(3, t0_ns)
        for meta_id, offset_ps, dur_ps in events:
            body += _msg(4, _int(1, meta_id) + _int(2, offset_ps) + _int(3, dur_ps) + _msg(4, _int(1, 9) + _double(2)))
        out += _msg(3, body)
    for key, (text, stats) in event_meta.items():
        value = _int(1, key) + _msg(2, text) + b"".join(_msg(5, s) for s in stats)
        out += _msg(4, _int(1, key) + _msg(2, value))
    for key, text in stat_meta.items():
        out += _msg(5, _int(1, key) + _msg(2, _int(1, key) + _msg(2, text)))
    return out


def test_load_scoped_reads_the_path_from_the_event_metadata(tmp_path):
    stat_meta = {1: "flops", 2: sc.PATH_STAT, 3: "jit(train)/wm_optim/add:"}
    event_meta = {
        10: ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
             [_int(1, 1) + _int(3, 4096), _int(1, 2) + _msg(5, "jit(train)/jvp(wm_heads)/mul:")]),
        11: ("%fusion.8 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop", [_int(1, 2) + _int(7, 3)]),  # a ref_value
        12: ("%while.3 = (s32[]) while(%t), body=%b", []),
        20: ("jit_train(99)", []),
    }
    device = _plane("/device:TPU:0", [
        ("XLA Ops", 1000, [(10, 5_000_000, 2_000_000), (11, 8_000_000, 1_000_000), (12, 4_000_000, 6_000_000)]),
        ("XLA Modules", 1000, [(20, 4_000_000, 7_000_000)]),
        ("Steps", 1000, [(20, 4_000_000, 7_000_000)]),
    ], event_meta, stat_meta)
    host = _plane("/host:CPU", [("python", 0, [(10, 0, 1_000_000)])], {10: ("chipbench:window", [])}, {})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg(1, host) + _msg(1, device) + _msg(4, "host-1"))
    got = sc.load_scoped(str(path))
    assert list(got) == ["/device:TPU:0"]
    assert got["/device:TPU:0"]["modules"] == [["jit_train(99)", 5000.0, 7000.0]]  # 1000 ns + 4 us
    assert got["/device:TPU:0"]["ops"] == [
        ["fusion.7 f32[8]", 6000.0, 2000.0, "jit(train)/jvp(wm_heads)/mul:"],
        ["fusion.8 f32[8]", 9000.0, 1000.0, "jit(train)/wm_optim/add:"],
        ["while.3 s32[]", 5000.0, 6000.0, ""],
    ]
    reduced = sc.by_scope(got, (0.0, 20_000.0), "^jit_train")
    assert reduced["self_s"] == {"wm_heads": pytest.approx(5e-6), "wm_optim": pytest.approx(1e-6)}
    assert reduced["while_s"] == {"wm_heads": pytest.approx(6e-6)}
