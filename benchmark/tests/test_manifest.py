"""BENCHMARK.json keeps to the contract's letter, and every name in it
resolves to the file of its own."""

import importlib
import json
import os

import pytest

from benchmark import manifest as mf

M = mf.load()
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]


def test_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(mf.ROOT, "BENCHMARK.json")) < 65536
    assert 1 <= len(M["paths"]) <= 16 and len(M["command"]) <= 32
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in M["workloads"])
    assert len(four) <= max(1, len(M["workloads"]) // 4)
    # a full check fits its budget with all 24 cells
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("entry", M["configs"] + M["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert mf.NAME_RE.match(entry["name"])
    for key in ("config", "traffic", "moves"):
        if key in entry:
            assert mf.NAME_RE.match(entry[key])
    if "unit" in entry:
        assert mf.UNIT_RE.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in mf.SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            v = entry[key]
            assert 1 <= len(v) <= 200 and "\n" not in v and "\t" not in v


def test_entries_have_just_their_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in [e["name"] for e in M["end_to_end"]]
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names)) and "setup_s" in names
    assert len(CELLS) == len(set(CELLS))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve(cell):
    w = mf.cell(M, cell)
    config = mf.load_config(M, w["config"])
    assert config["name"] == w["config"]
    assert os.path.isfile(mf.reference_path(M, w["config"]))
    traffic = mf.load_traffic(w["traffic"])
    assert traffic["chips"] == w["chips"]
    assert hasattr(mf.driver_module(traffic["kind"]), "run")
    limits = mf.load_json(os.path.join(mf.HERE, "limits", cell + ".json"))
    assert all(v > 0 for v in limits["limits"].values())
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    e2e = [m["name"] for m in mf.cell_metrics(M, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert mf.cell_metrics(M, cell, "per_layer")
    for c in M["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in M["paths"]))
        assert any(x["config"] == c["name"] for x in M["workloads"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_reader_resolves(metric):
    assert callable(mf.load_reader(metric["name"]))
    for cell in metric.get("workloads", []):
        assert cell in CELLS


def test_workloads_key_limits_a_metric_to_its_cells():
    """`reports` on a manifest of its own: a metric with a `workloads` key
    belongs to those cells only (the `sync.*` readers wait for the
    four-chip cell this way), one without to every cell that reports the
    metric it moves."""
    m = {"end_to_end": [{"name": "rate"},
                        {"name": "tail", "workloads": ["serve"]}],
         "per_layer": [{"name": "sync.x", "moves": "rate",
                        "workloads": ["four"]},
                       {"name": "mfu", "moves": "rate"},
                       {"name": "queue", "moves": "tail"}]}
    names = lambda cell: [x["name"] for x in mf.cell_metrics(m, cell,
                                                             "per_layer")]
    assert names("one") == ["mfu"]
    assert names("four") == ["sync.x", "mfu"]
    assert names("serve") == ["mfu", "queue"]
    assert [x["name"] for x in mf.cell_metrics(m, "one", "end_to_end")] == \
        ["rate"]


@pytest.mark.parametrize("name", ["sync.collective_ms_per_step",
                                  "sync.exposed_ms_per_step"])
def test_readers_kept_ready_for_the_four_chip_cell_resolve(name):
    assert callable(mf.load_reader(name))
    assert mf.load_traffic("train-epochs-ddp-weak4")["chips"] == 4


def test_peak_table_names_its_source():
    peaks = json.load(open(os.path.join(mf.HERE, "peaks.json")))
    assert "Google Cloud" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
