import json
import os

from benchmarks.harness import manifest as mf


def test_the_committed_manifest_is_sound():
    assert mf.check_manifest(mf.load_manifest()) == []


def test_every_layer_metric_moves_something_each_of_its_cells_reports():
    manifest = mf.load_manifest()
    for cell in manifest["workloads"]:
        e2e = {m["name"] for m in mf.metrics_of(manifest, cell, "end_to_end")}
        for m in mf.metrics_of(manifest, cell, "per_layer"):
            assert m["moves"] in e2e, (cell["name"], m["name"])


def test_metric_modules_repeat_what_the_manifest_says():
    manifest = mf.load_manifest()
    for m in manifest["per_layer"]:
        mod = mf.load_module("metrics", m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"]), m["name"]
    for m in manifest["end_to_end"]:
        mod = mf.load_module("metrics", m["name"])
        assert (mod.UNIT, mod.SOURCE) == (m["unit"], m["source"]), m["name"]


def test_a_bad_manifest_is_told_what_is_wrong():
    manifest = mf.load_manifest()
    manifest["per_layer"][0] = dict(manifest["per_layer"][0], unit="tokens per s")
    manifest["workloads"][0] = dict(manifest["workloads"][0], name="has space")
    said = " ".join(mf.check_manifest(manifest))
    assert "unit" in said and "has space" in said


def test_cells_find_their_files_by_name():
    manifest = mf.load_manifest()
    for cell in manifest["workloads"]:
        config = mf.config_of(manifest, cell)
        traffic = mf.load_json("traffic", cell["traffic"])
        assert os.path.isfile(os.path.join(
            mf.HERE, "builders", config["builder"] + ".py"))
        assert os.path.isfile(os.path.join(
            mf.HERE, "reference", cell["config"] + ".py"))
        assert os.path.isfile(os.path.join(
            mf.HERE, "drivers", traffic["kind"] + ".py"))
        assert len(json.dumps(config["source"])) <= 202
