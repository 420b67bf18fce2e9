"""`BENCHMARK.json` and the files it names say the same thing: every cell
resolves to a configuration, a traffic mix with a driver that exists and a
limit for every number it compares; every per-layer metric has a file whose
layer, unit and end-to-end metric agree with its entry and whose reader
exists."""

import importlib
import json
import os

import pytest

from benchmarks.harness import HERE, ROOT, load_json, resolve

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_a_cell_resolves_to_its_files(cell):
    parts = resolve(BENCH, cell)
    config, traffic = parts["config"], parts["traffic"]
    entry = next(c for c in BENCH["configs"] if c["name"] == parts["cell"]["config"])
    assert config["name"] == entry["name"] and config["source"] == entry["source"]
    # a cut of scale is listed in both places, with the limit that forces it
    assert config["reduced"] == entry["reduced"]
    assert set(config.get("reduced_why", {})) == set(config["reduced"])
    for key in ("source", "sizes", "tiny", "assumed", "guarantees", "precision", "roofline_shape"):
        assert key in config, key
    importlib.import_module("benchmarks.generators." + config["generator"])
    driver = importlib.import_module("benchmarks.drivers." + traffic["driver"].replace("-", "_"))
    assert hasattr(driver, "Driver")
    reported = {m["name"] for m in parts["end_to_end"]}
    assert set(traffic["end_to_end"]) | {"setup_s"} == reported
    assert len(reported) >= 2 and parts["per_layer"]
    assert int(traffic["traced_items"]) >= 1


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=[m["name"] for m in BENCH["per_layer"]])
def test_a_per_layer_metric_has_a_file_that_agrees_with_its_entry(entry):
    spec = load_json(os.path.join(HERE, "metrics", entry["name"] + ".json"))
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    assert callable(reader.read)
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}


def test_every_file_of_a_metric_or_a_mix_is_used():
    metrics = {f[:-5] for f in os.listdir(os.path.join(HERE, "metrics"))}
    assert metrics == {m["name"] for m in BENCH["per_layer"]}
    mixes = {f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))}
    assert mixes == {w["traffic"] for w in BENCH["workloads"]}
    assert json.dumps(BENCH)  # and the whole file is plain JSON
