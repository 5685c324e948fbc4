"""Discovery by name, and the committed benchmark's files and shape."""
import json
import os
import re

import pytest

from perfbench import catalog

CHECKOUT = os.path.dirname(catalog.ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture
def root(tmp_path):
    for kind in ("configs", "traffic", "drivers", "metrics"):
        (tmp_path / kind).mkdir()
    return tmp_path


def test_files_dropped_in_are_found_by_name(root):
    (root / "configs" / "new-cfg.json").write_text('{"dim": 3}')
    (root / "traffic" / "burst.json").write_text('{"driver": "d"}')
    (root / "drivers" / "d.py").write_text("def run(s):\n    return 7\n")
    (root / "metrics" / "gap_ms.x.py").write_text(
        "def read(ctx):\n    return ctx['v'] * 2\n")
    assert catalog.load_json("configs", "new-cfg", str(root)) == {"dim": 3}
    assert catalog.load_json("traffic", "burst", str(root))["driver"] == "d"
    assert catalog.load_module("drivers", "d", str(root)).run(None) == 7
    m = catalog.load_module("metrics", "gap_ms.x", str(root))
    assert m.read({"v": 2.5}) == 5.0


@pytest.mark.parametrize("kind,name", [
    ("configs", "missing"), ("traffic", "missing"), ("metrics", "missing"),
    ("configs", "../configs/x"), ("drivers", "a/b"), ("metrics", "")])
def test_unknown_or_malformed_names_are_errors(root, kind, name):
    loader = catalog.load_json if kind in ("configs", "traffic") \
        else catalog.load_module
    with pytest.raises(catalog.UnknownName):
        loader(kind, name, str(root))


def test_unknown_cell_is_an_error():
    with pytest.raises(catalog.UnknownName):
        catalog.cell({"workloads": []}, "nope")


def test_metrics_for_a_cell():
    bench = {"end_to_end": [{"name": "a", "workloads": ["x"]},
                            {"name": "b", "workloads": ["y"]},
                            {"name": "setup_s"}],
             "per_layer": [{"name": "p", "moves": "a"},
                           {"name": "q", "moves": "b"},
                           {"name": "r", "moves": "b", "workloads": ["x"]}]}
    e2e = [m["name"] for m in catalog.metrics_for(bench, "x", "end_to_end")]
    assert e2e == ["a", "setup_s"]
    pl = [m["name"] for m in catalog.metrics_for(bench, "x", "per_layer")]
    assert pl == ["p", "r"]


def _bench():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_in_the_benchmark_has_its_files():
    bench = _bench()
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(CHECKOUT, c["file"]))
        assert c["file"] == f"perfbench/configs/{c['name']}.json"
    for w in bench["workloads"]:
        config = catalog.load_json("configs", w["config"])
        traffic = catalog.load_json("traffic", w["traffic"])
        catalog.load_module("drivers", traffic["driver"])
        limits = catalog.load_json("limits", w["name"])
        assert limits and config["dim"] == config["features"] \
            + config["classes"]
    for m in bench["per_layer"]:
        assert callable(catalog.load_module("metrics", m["name"]).read)


def test_the_benchmark_keeps_the_contract_shape():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "perfbench/run.py"]
    assert bench["paths"] == ["perfbench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        for k in c["reduced"]:
            assert NAME.match(k) and not k.endswith(("_dim", "_rank"))
        file_cfg = catalog.load_json("configs", c["name"])
        assert sorted(c["reduced"]) == sorted(file_cfg["reduced"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in names
            names.add(e["name"])
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                        and "\t" not in e[key] and e[key].isascii()
    for m in bench["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        cells = {w["name"] for w in bench["workloads"]}
        assert set(m.get("workloads", cells)) <= cells
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
        assert w["config"] in {c["name"] for c in bench["configs"]}
    assert len(json.dumps(bench)) < 64 * 1024
