"""``BENCHMARK.json`` against the contract's own rules."""
import copy
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import manifest as mf  # noqa: E402
from benchmark import weights  # noqa: E402

M = mf.load_manifest()


def test_the_manifest_is_sound():
    assert mf.check(M) == []
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(M)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_each_cell_reports_setup_one_more_and_a_layer_metric(cell):
    c = mf.Cell(M, cell)
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in names
        assert callable(mf.reader(m["name"]))


@pytest.mark.parametrize("metric", [m["name"] for m in M["per_layer"]])
def test_every_layer_metric_has_a_reader_that_finds_nothing_in_nothing(
        metric):
    run = {"cell": "x", "phases": {
        "entry.compile_s": 1.0,
        "entry.cache_misses": 0, "entry.build_s": 1.0,
        "entry.warm_s": 1.0},
        "counters": {}, "end_to_end": {}, "trace": None, "peaks": None,
        "notes": {}}
    value = mf.reader(metric)(run)
    if metric.startswith("entry."):
        assert value is not None
    else:
        assert value is None     # nothing to read: nothing, never 0


def test_broken_manifests_are_named():
    m = copy.deepcopy(M)
    m["workloads"][0]["name"] = "has space"
    m["end_to_end"][0]["unit"] = "tokens per second"
    m["per_layer"][5]["moves"] = "nothing"
    m["end_to_end"][1]["bound"] = 0.5
    bad = " ".join(mf.check(m))
    for word in ("has space", "tokens per second", "nothing", "bound"):
        assert word in bad


def test_at_most_one_four_chip_cell_and_it_is_the_ddp_one():
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == ["gpt2-345m.train-dp4"]


@pytest.mark.parametrize("cfg", [c["name"] for c in M["configs"]])
def test_configurations_keep_published_widths(cfg):
    entry = {c["name"]: c for c in M["configs"]}[cfg]
    d = weights.model_dims(mf._json(os.path.join(mf.ROOT, entry["file"])))
    assert (d["layers"], d["hidden"], d["heads"], d["head_dim"],
            d["ffn"]) == (24, 1024, 16, 64, 4096)
    assert entry["reduced"] == []
    assert d["vocab"] % 128 == 0 and 0 <= d["vocab"] - d["vocab_published"] < 128
