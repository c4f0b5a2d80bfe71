"""BENCHMARK.json and the files it names.  No JAX here: the tests and the
result line's assembly import this on any machine.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the NAME in
BENCHMARK.json:

  configuration  benchmark/configs/<name>.json   (+ <name>.py, its plain reference)
  traffic mix    benchmark/traffic/<name>.json   ("kind" names the driver module
                                                  benchmark/drivers/<kind, - as _>.py)
  per-layer      benchmark/metrics/<name>.json   ("reader": "module:function")
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class ManifestError(ValueError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_peaks() -> dict:
    return load_json(os.path.join(HERE, "peaks.json"))["devices"]


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise ManifestError(f"no config {name!r} in BENCHMARK.json")


def load_config(manifest: dict, name: str, root: str = ROOT) -> dict:
    return load_json(os.path.join(root, config_entry(manifest, name)["file"]))


def reference_path(manifest: dict, name: str, root: str = ROOT) -> str:
    """The configuration's plain reference sits beside its file of sizes."""
    base, _ = os.path.splitext(config_entry(manifest, name)["file"])
    return os.path.join(root, base + ".py")


def load_module_from_path(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_traffic(name: str) -> dict:
    return load_json(os.path.join(HERE, "traffic", name + ".json"))


def driver_module(kind: str):
    return importlib.import_module(
        "benchmark.drivers." + kind.replace("-", "_"))


def reports(metric: dict, cell_name: str, manifest: dict) -> bool:
    """Does this metric belong to the cell?  With a `workloads` key: the
    cells listed.  Without: every cell that reports the metric it moves
    (an end-to-end metric without the key: every cell)."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    moved = metric.get("moves")
    if moved is None:
        return True
    target = next(m for m in manifest["end_to_end"] if m["name"] == moved)
    return reports(target, cell_name, manifest)


def cell_metrics(manifest: dict, cell_name: str, kind: str) -> list:
    return [m for m in manifest[kind] if reports(m, cell_name, manifest)]


def load_reader(metric_name: str):
    """benchmark/metrics/<name>.json -> the reader function it names."""
    desc = load_json(os.path.join(HERE, "metrics", metric_name + ".json"))
    modname, _, fn = desc["reader"].partition(":")
    return getattr(importlib.import_module(modname), fn)
