"""`moe.touched_over_live` from a window's unit records.  No JAX."""

import types

from benchmark import manifest as mf
from benchmark.readers import moe_prefix


def run_of(units):
    return types.SimpleNamespace(
        window=types.SimpleNamespace(units=units), counters={}, trace={})


def test_ratio_of_the_windows_sums():
    units = [{"moe_rows_local": 9000.0, "moe_rows_touched": 16384.0},
             {"moe_rows_local": 8000.0, "moe_rows_touched": 8192.0}]
    assert moe_prefix.touched_over_live(run_of(units)) == 24576.0 / 17000.0


def test_none_where_the_program_has_no_such_counter():
    """The parent's unit records hold the routed rows and not the touched
    ones; an empty window holds neither."""
    assert moe_prefix.touched_over_live(
        run_of([{"moe_rows_local": 9000.0}])) is None
    assert moe_prefix.touched_over_live(run_of([])) is None


def test_the_manifest_names_the_reader_for_the_decoder_cell_only():
    manifest = mf.load()
    assert mf.load_reader("moe.touched_over_live") \
        is moe_prefix.touched_over_live
    cells = [w["name"] for w in manifest["workloads"] if any(
        m["name"] == "moe.touched_over_live"
        for m in mf.cell_metrics(manifest, w["name"], "per_layer"))]
    assert cells == ["sdar-ep8-blockdiff-train-1chip"]
