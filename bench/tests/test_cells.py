"""Configurations, mixes, metrics and references are found by name, and a
new file is picked up with no edit to an existing one."""

import json

import pytest

import cells


def test_every_cell_resolves():
    bench = cells.load_benchmark()
    for w in bench["workloads"]:
        c = cells.cell(bench, w["name"])
        conf = cells.load_json("configs", c["config"])
        cells.load_json("traffic", c["traffic"])
        cells.load_module("refs", conf["ref"])
        for m in c["per_layer"]:
            assert hasattr(cells.load_module("metrics", m["name"]), "read")
        names = {m["name"] for m in c["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2 and c["per_layer"]
        assert all(m["moves"] in names for m in c["per_layer"])


@pytest.mark.parametrize("kind,name", [
    ("configs", "no-such-model"), ("traffic", "no_such_mix"),
    ("metrics", "no_such_metric"), ("refs", "no_such_family"),
    ("configs", "../BENCHMARK"), ("metrics", "a/b")])
def test_unknown_or_malformed_names_fail(kind, name):
    with pytest.raises(LookupError):
        if kind in ("metrics", "refs"):
            cells.load_module(kind, name)
        else:
            cells.load_json(kind, name)


def test_unknown_workload_fails():
    with pytest.raises(LookupError):
        cells.cell(cells.load_benchmark(), "no_such_cell")


def test_new_files_are_picked_up(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics" / "new_metric.batch.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (tmp_path / "traffic" / "new_mix.json").write_text(
        json.dumps({"loop": "open"}))
    assert cells.load_module("metrics", "new_metric.batch",
                             root=tmp_path).read(None) == 42.0
    assert cells.load_json("traffic", "new_mix", root=tmp_path) == \
        {"loop": "open"}
    bench = cells.load_benchmark()
    bench["per_layer"].append({"name": "new_metric.batch", "moves": "tok_s",
                               "workloads": ["yi6b_chat"]})
    assert "new_metric.batch" in [
        m["name"] for m in cells.cell(bench, "yi6b_chat")["per_layer"]]
    # a metric with no workloads key goes to every cell reporting its moves
    bench["per_layer"].append({"name": "everywhere", "moves": "tok_s"})
    for w in bench["workloads"]:
        assert "everywhere" in [m["name"] for m in
                                cells.cell(bench, w["name"])["per_layer"]]
