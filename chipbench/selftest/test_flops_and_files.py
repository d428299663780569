"""The FLOP arithmetic against a hand count, the table of peaks, and that
``BENCHMARK.json`` and the files under ``chipbench/`` name each other."""

import importlib
import json
import os
import re

import pytest

from chipbench import flops, peaks

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# DreamerV3-S as configs/algo/dreamer_v3_S.yaml has it, 6 discrete actions
S = flops.DV3Shapes(batch=16, seq_len=64, horizon=15, image=64, channels=3, cnn_mult=32, recurrent=512, dense=512,
                    mlp_layers=2, hidden=512, stochastic=32, discrete=32, actions=6)


def test_dv3_s_conv_and_dense_macs_by_hand():
    m = flops.macs_per_row(S)
    # encoder: 4x4 kernels, stride 2, 64 -> 32 -> 16 -> 8 -> 4 pixels, channels 3 -> 32 -> 64 -> 128 -> 256
    assert m["encoder"] == 32 * 32 * 16 * 3 * 32 + 16 * 16 * 16 * 32 * 64 + 8 * 8 * 16 * 64 * 128 + 4 * 4 * 16 * 128 * 256
    assert m["encoder"] == 1_572_864 + 8_388_608 + 8_388_608 + 8_388_608
    # decoder: latent 1536 -> 4096, then 4x4 transposed, 4 -> 8 -> 16 -> 32 -> 64 pixels, 256 -> 128 -> 64 -> 32 -> 3
    assert m["decoder"] == 1536 * 4096 + 16 * 16 * 256 * 128 + 64 * 16 * 128 * 64 + 256 * 16 * 64 * 32 + 1024 * 16 * 32 * 3
    # recurrent model: (1024 + 6) -> 512, then the GRU's one matrix (512 + 512) -> 3 x 512
    assert m["recurrent"] == 1030 * 512 + 1024 * 1536
    assert m["transition"] == 512 * 512 + 512 * 1024
    assert m["representation"] == (512 + 4096) * 512 + 512 * 1024
    assert m["reward"] == 1536 * 512 + 512 * 512 + 512 * 255
    assert m["actor"] == 1536 * 512 + 512 * 512 + 512 * 6


def test_dv3_s_update_total_by_hand():
    m = flops.macs_per_row(S)
    frames, horizon = 16 * 64, 15
    wm = 3 * frames * sum(m[k] for k in ("encoder", "recurrent", "transition", "representation", "decoder", "reward", "continue"))
    rollout = horizon * frames * (m["recurrent"] + m["transition"] + m["actor"])
    imagined = (horizon + 1) * frames * (m["reward"] + m["continue"] + m["critic"] + 2 * m["actor"])
    critic = horizon * frames * 3 * m["critic"]
    assert flops.update_flops(S)["total"] == pytest.approx(2.0 * (wm + rollout + imagined + critic))
    # 67.7 M multiply-adds a frame in the world model: 0.416 TFLOP of the 0.827
    assert flops.update_flops(S)["world_model"] == pytest.approx(0.416e12, rel=5e-3)
    assert flops.update_flops(S)["total"] == pytest.approx(0.827e12, rel=5e-3)


def test_scales_to_xl_as_the_widths_say():
    with open(os.path.join(HERE, "configs", "dv3_XL.json")) as f:
        xl = flops.DV3Shapes.from_config(json.load(f), 16)
    ms, mx = flops.macs_per_row(S), flops.macs_per_row(xl)
    # every conv but the first grows with the square of the multiplier (96 / 32), the first linearly
    first_s, first_x = 32 * 32 * 16 * 3 * 32, 32 * 32 * 16 * 3 * 96
    assert (mx["encoder"] - first_x) == 9 * (ms["encoder"] - first_s)
    # the GRU matrix: (1024 + 4096) x 3 x 4096 against (512 + 512) x 3 x 512
    assert mx["recurrent"] - (1024 + 17) * 1024 == 40 * (ms["recurrent"] - 1030 * 512)
    assert flops.update_flops(xl)["total"] == pytest.approx(8.755e12, rel=1e-3)
    # global batch 64 on four chips is four times the work of 16 on one
    xl64 = flops.DV3Shapes.from_config(json.load(open(os.path.join(HERE, "configs", "dv3_XL.json"))), 64)
    assert flops.update_flops(xl64)["total"] == pytest.approx(4 * flops.update_flops(xl)["total"])


def test_mfu_and_peaks():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert flops.mfu_percent(9.85e12, 10.0, 1, v5e["bf16_flops_per_s"]) == pytest.approx(50.0)
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks_for("_source")


@pytest.fixture(scope="module")
def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units(benchmark):
    assert set(benchmark) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in benchmark["end_to_end"] + benchmark["per_layer"]]
    assert len(names) == len(set(names))
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        assert NAME_RE.match(m["name"]) and UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in benchmark["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for w in benchmark["workloads"]:
        assert all(NAME_RE.match(w[k]) for k in ("name", "config", "traffic")), w
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    four = [w for w in benchmark["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(benchmark["workloads"]) // 4)
    assert 1 <= benchmark["run_seconds"] <= 51
    assert len(json.dumps(benchmark)) < 64 * 1024


def test_every_cell_resolves_by_name(benchmark):
    configs = {c["name"]: c for c in benchmark["configs"]}
    e2e = {m["name"]: m for m in benchmark["end_to_end"]}
    layer = {m["name"]: m for m in benchmark["per_layer"]}
    cells = {w["name"] for w in benchmark["workloads"]}
    for w in benchmark["workloads"]:
        with open(os.path.join(HERE, "workloads", w["name"] + ".json")) as f:
            on_disk = json.load(f)
        assert {k: on_disk[k] for k in ("config", "traffic", "chips", "why")} == {
            k: w[k] for k in ("config", "traffic", "chips", "why")
        }
        with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
            config = json.load(f)
        assert config["source"] == configs[w["config"]]["source"] and config["reduced"] == configs[w["config"]]["reduced"]
        with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        importlib.import_module("chipbench.drivers." + traffic["driver"])
        reported_e2e = [m for m in e2e.values() if w["name"] in m.get("workloads", cells)]
        assert "setup_s" in {m["name"] for m in reported_e2e} and len(reported_e2e) >= 2
        assert on_disk["layer_metrics"], w["name"]
        for name in on_disk["layer_metrics"]:
            reader = importlib.import_module("chipbench.layer_metrics." + name)
            entry = layer[name]
            assert (reader.NAME, reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == (
                entry["name"], entry["unit"], entry["layer"], entry["source"], entry["moves"])
            assert w["name"] in entry.get("workloads", cells)
            # a per-layer metric is reported only where the metric it moves is
            assert w["name"] in e2e[entry["moves"]].get("workloads", cells), (name, w["name"])
    for entry in layer.values():
        for cell in entry.get("workloads", cells):
            with open(os.path.join(HERE, "workloads", cell + ".json")) as f:
                assert entry["name"] in json.load(f)["layer_metrics"]
