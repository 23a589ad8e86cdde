"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix and per-layer metric is found by its name alone."""
import json
import os
import re

import pytest

import rehearse  # noqa: F401,I001 - puts bench/ on the path first
import common

BENCH = common.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(conf):
    path = os.path.join(common.ROOT, conf["file"])
    data = json.load(open(path))
    assert data["name"] == conf["name"] and NAME.match(conf["name"])
    assert sorted(data["reduced"]) == sorted(conf["reduced"])
    assert os.path.exists(os.path.join(common.BENCH_DIR, "reference",
                                       data["reference"] + ".py"))
    cfg = common.model_config(data)     # the program runs it as stated
    arch = data["architecture"]
    assert (cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab_size) == (
        arch["hidden_size"], arch["intermediate_size"],
        arch["num_hidden_layers"], arch["vocab_size"])


@pytest.mark.parametrize("work", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_and_metrics(work):
    assert NAME.match(work["name"]) and work["chips"] in (1, 4)
    assert work["name"] == f"{work['config']}.{work['traffic']}"
    mix = json.load(open(os.path.join(common.BENCH_DIR, "traffic",
                                      work["traffic"] + ".json")))
    assert os.path.exists(os.path.join(common.BENCH_DIR, "kinds",
                                       mix["kind"] + ".py"))
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if work["name"] in m.get("workloads", [work["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = [m for m in BENCH["per_layer"]
                 if work["name"] in m.get("workloads", [work["name"]])]
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader(metric):
    import run as bench_run
    reader = bench_run.load("metrics", metric["name"])
    assert reader.read({}) is None          # nothing to read: no number
    for w in metric.get("workloads", []):
        assert w in {x["name"] for x in BENCH["workloads"]}
