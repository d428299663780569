"""The per-layer metrics of PR 24 wait for a ``benchmark`` PR to register them
(appending to a workload file is an edit of a file the benchmark has).  Until
then each cell has an unregistered twin under ``workloads/`` that lists them;
this holds the twins, the readers and ``BENCHMARK.json`` to each other, as
``test_every_cell_resolves_by_name`` will once they are registered."""

import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TWINS = {"dv3_XL_loop_spans": "dv3_XL_loop", "dv3_XL_train_scopes": "dv3_XL_train",
         "dv3_XL_train_x4_scopes": "dv3_XL_train_x4"}


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("twin,cell", sorted(TWINS.items()))
def test_twin_is_its_cell_plus_new_readers(twin, cell):
    benchmark = _load("..", "BENCHMARK.json")
    assert twin not in {w["name"] for w in benchmark["workloads"]}
    e2e = {m["name"]: m for m in benchmark["end_to_end"]}
    layers = {m["layer"] for m in benchmark["per_layer"]}
    registered = {m["name"] for m in benchmark["per_layer"]}
    a, b = _load("workloads", twin + ".json"), _load("workloads", cell + ".json")
    assert {k: a[k] for k in ("config", "traffic", "chips")} == {k: b[k] for k in ("config", "traffic", "chips")}
    assert a["layer_metrics"][:len(b["layer_metrics"])] == b["layer_metrics"] and len(a["why"]) <= 200
    new = a["layer_metrics"][len(b["layer_metrics"]):]
    assert new and not registered & set(new)
    for name in new:
        reader = importlib.import_module("chipbench.layer_metrics." + name)
        assert reader.NAME == name and reader.UNIT in ("%", "ms") and reader.LAYER in layers
        assert reader.SOURCE in ("device_trace", "program_span")
        # reported only where the end-to-end metric it moves is
        assert cell in e2e[reader.MOVES]["workloads"]
        assert reader.read({}) is None  # nothing to read: nothing reported, nothing raised
